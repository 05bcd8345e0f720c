"""hvdshard — static sharding & communication-plan analysis (HVD4xx).

Acceptance coverage (ISSUE 17):

* COMM_CENSUS bytes on a hand-built 2-axis program equal HAND-COMPUTED
  bytes exactly (payload x communicator group size, per-axis
  attribution, ICI/DCN split);
* a seeded corpus fires each of HVD400-HVD404 exactly where expected —
  jaxpr-level (implicit reshard with estimated bytes, budget overshoot,
  replicated-large operand, undeclared/mixed-fabric collective, dead
  mesh axis) and AST-level (pinned lines) — with clean-fixture
  negatives: deliberate resharding via an explicit constraint, an
  ICI-only program under a DCN budget, scan-carried shardings
  unchanged;
* ``check_replica_plan()`` rejects a plan whose per-step DCN bytes
  exceed the budget and admits the ICI-only equivalent; the serve
  engine exposes the verdict on ``kv_stats`` (→ healthz);
* COMM_CENSUS counters land on the Timeline and the HVD_ANALYZE hook
  attaches ``comm`` to shard_step reports on the SAME trace the
  collective/memory censuses use.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu import core as _core
from horovod_tpu.analysis import hook, shardplan, unsuppressed

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

F32 = 4  # bytes


def _mesh(shape, names):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), names)


@pytest.fixture()
def analyze_env(monkeypatch):
    monkeypatch.setenv("HVD_ANALYZE", "1")
    hook.reset()
    _core._state.analysis_reports = []
    yield
    hook.reset()


# ---------------------------------------------------------------------------
# Census: hand-computed bytes
# ---------------------------------------------------------------------------

def test_census_bytes_two_axis_hand_computed():
    """Hand-built 2-axis program: psum of 64 payload bytes over 'local'
    (group 4) = 256 wire bytes; psum of 32 payload bytes over both axes
    (group 8) = 256 wire bytes.  Totals and the per-axis attribution
    (every collective that names an axis charges it) must match these
    numbers EXACTLY."""
    def step(x, y):
        return jax.lax.psum(x, "local"), jax.lax.psum(y, ("cross", "local"))

    r = shardplan.measure_step_fn_comm(
        step, (jnp.ones((16,), jnp.float32), jnp.ones((8,), jnp.float32)),
        axis_env=[("cross", 2), ("local", 4)], label="two_axis")
    assert r.by_primitive["psum"]["count"] == 2
    assert r.by_primitive["psum"]["bytes"] == 16 * F32 + 8 * F32
    assert r.by_primitive["psum"]["wire_bytes"] == 256 + 256
    assert r.total_wire_bytes == 512
    assert r.dcn_wire_bytes == 0
    assert r.by_axis["local"] == {"fabric": "ici", "size": 4,
                                  "count": 2, "wire_bytes": 512}
    assert r.by_axis["cross"] == {"fabric": "ici", "size": 2,
                                  "count": 1, "wire_bytes": 256}
    assert not r.findings


def test_shard_map_census_group_size():
    """Through jax.shard_map: the per-shard psum payload is (1, 128) f32 = 512 bytes, wire = 512 x group 8."""
    mesh = _mesh((8,), ("hvd",))

    def step(x):
        return jax.lax.psum(x, "hvd")

    mapped = jax.shard_map(step, mesh=mesh, in_specs=P("hvd"),
                           out_specs=P("hvd"))
    closed = jax.make_jaxpr(mapped)(jnp.zeros((8, 128), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="sm", mesh=mesh)
    assert r.by_primitive["psum"] == {"count": 1, "bytes": 512,
                                      "wire_bytes": 4096, "dcn_bytes": 0}
    assert r.axes_declared == {"hvd": 8}
    assert not r.findings


def test_psum_of_varying_value_counts_as_psum():
    """Under varying-axes tracking a psum of a varying value traces as
    the psum_invariant primitive — the census counts it as psum, like
    the untracked (check_vma=False) trace of the same program."""
    mesh = _mesh((8,), ("hvd",))

    def step(x):
        return jax.lax.psum(x, "hvd")

    for check_vma in (True, False):
        mapped = jax.shard_map(step, mesh=mesh, in_specs=P("hvd"),
                               out_specs=P(), check_vma=check_vma)
        closed = jax.make_jaxpr(mapped)(jnp.zeros((8, 128), jnp.float32))
        r = shardplan.measure_closed_jaxpr_comm(closed, label="sm2",
                                                mesh=mesh)
        assert "psum_invariant" not in r.by_primitive
        assert r.by_primitive["psum"]["count"] == 1
        assert r.by_primitive["psum"]["wire_bytes"] == 4096


def test_scan_census_multiplied_and_carried_sharding_clean():
    """A psum inside a length-5 scan executes 5 times (unlike the
    MEMORY census, wire bytes DO multiply by trip count); the scan
    carry's sharding never changes, so no HVD400."""
    def step(x):
        def body(c, _):
            return jax.lax.psum(c, "hvd"), ()
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    r = shardplan.measure_step_fn_comm(
        step, (jnp.ones((16,), jnp.float32),),
        axis_env=[("hvd", 8)], label="scan")
    assert r.by_primitive["psum"]["count"] == 5
    assert r.by_primitive["psum"]["wire_bytes"] == 5 * (16 * F32) * 8
    assert not [f for f in r.findings if f.rule == "HVD400"]


# ---------------------------------------------------------------------------
# ICI/DCN classification
# ---------------------------------------------------------------------------

def test_classify_mesh_axes_single_host_ici_and_override():
    """Single-process CPU mesh: every axis is ICI (process_index never
    changes along any dim); HVD_COMM_DCN_AXES-style override forces the
    listed axis to DCN."""
    mesh = _mesh((2, 4), ("cross", "local"))
    assert shardplan.classify_mesh_axes(mesh) == \
        {"cross": "ici", "local": "ici"}
    assert shardplan.classify_mesh_axes(mesh, dcn_axes=("cross",)) == \
        {"cross": "dcn", "local": "ici"}


# ---------------------------------------------------------------------------
# HVD400: implicit resharding (jaxpr)
# ---------------------------------------------------------------------------

def _row_col(mesh):
    return (NamedSharding(mesh, P("hvd", None)),
            NamedSharding(mesh, P(None, "hvd")))


def test_implicit_reshard_fires_with_estimated_bytes():
    """Produced row-sharded, consumed column-sharded: HVD400 with the
    full array size as the transfer estimate (512x512 f32 = 1 MiB)."""
    mesh = _mesh((8,), ("hvd",))
    row, col = _row_col(mesh)
    inner1 = jax.jit(lambda x: x * 2.0, in_shardings=(row,),
                     out_shardings=row)
    inner2 = jax.jit(lambda x: x + 1.0, in_shardings=(col,),
                     out_shardings=col)
    closed = jax.make_jaxpr(lambda x: inner2(inner1(x)))(
        jnp.zeros((512, 512), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="reshard",
                                            mesh=mesh)
    fired = [f for f in r.findings if f.rule == "HVD400"]
    assert len(fired) == 1, [f.format() for f in r.findings]
    assert r.reshard_bytes == 512 * 512 * F32
    assert r.total_wire_bytes == 512 * 512 * F32
    (ev,) = r.reshard_events
    assert ev["from"] == "P(hvd, None)"
    assert ev["to"] == "P(None, hvd)"
    assert ev["bytes"] == 512 * 512 * F32


def test_explicit_constraint_resharding_is_clean():
    """The SAME layout change via with_sharding_constraint is the
    deliberate-resharding idiom: the constraint updates the value's
    sharding and the downstream consumption matches — no HVD400."""
    mesh = _mesh((8,), ("hvd",))
    row, col = _row_col(mesh)
    inner1 = jax.jit(lambda x: x * 2.0, in_shardings=(row,),
                     out_shardings=row)
    inner2 = jax.jit(lambda x: x + 1.0, in_shardings=(col,),
                     out_shardings=col)

    def prog(x):
        y = inner1(x)
        y = jax.lax.with_sharding_constraint(y, col)
        return inner2(y)

    closed = jax.make_jaxpr(prog)(jnp.zeros((512, 512), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="deliberate",
                                            mesh=mesh)
    assert not [f for f in r.findings if f.rule == "HVD400"], \
        [f.format() for f in r.findings]
    assert r.reshard_bytes == 0


def test_reshard_below_floor_is_noise_not_finding():
    """A re-laid-out 16 KiB value is under RESHARD_MIN_BYTES: counted
    nowhere, flagged nowhere."""
    mesh = _mesh((8,), ("hvd",))
    row, col = _row_col(mesh)
    inner1 = jax.jit(lambda x: x * 2.0, in_shardings=(row,),
                     out_shardings=row)
    inner2 = jax.jit(lambda x: x + 1.0, in_shardings=(col,),
                     out_shardings=col)
    closed = jax.make_jaxpr(lambda x: inner2(inner1(x)))(
        jnp.zeros((64, 64), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="small",
                                            mesh=mesh)
    assert not r.findings
    assert r.reshard_bytes == 0


# ---------------------------------------------------------------------------
# HVD401: comm budget (and the DCN sub-budget)
# ---------------------------------------------------------------------------

def test_comm_budget_overshoot_fires():
    def step(x):
        return jax.lax.psum(x, "hvd")

    r = shardplan.measure_step_fn_comm(
        step, (jnp.ones((128,), jnp.float32),),
        axis_env=[("hvd", 8)], budget_bytes=1000, label="budget")
    # wire = 512 payload x group 8 = 4096 > 1000
    fired = [f for f in r.findings if f.rule == "HVD401"]
    assert len(fired) == 1
    assert r.headroom_bytes == 1000 - 4096


def test_dcn_sub_budget_fires_only_for_dcn_bytes():
    """The same program under the same DCN sub-budget: over budget when
    its axis is DCN, clean when ICI-only (dcn_wire_bytes stays 0) —
    the ISSUE's ICI-only-under-DCN-budget negative."""
    def step(x):
        return jax.lax.psum(x, "hvd")

    args = (jnp.ones((128,), jnp.float32),)
    dcn = shardplan.measure_step_fn_comm(
        step, args, axis_env=[("hvd", 8)], dcn_axes=("hvd",),
        dcn_budget=1000, label="dcn_heavy")
    assert dcn.dcn_wire_bytes == 4096
    fired = [f for f in dcn.findings if f.rule == "HVD401"]
    assert len(fired) == 1 and "DCN" in fired[0].message

    ici = shardplan.measure_step_fn_comm(
        step, args, axis_env=[("hvd", 8)], dcn_axes=(),
        dcn_budget=1000, label="ici_only")
    assert ici.dcn_wire_bytes == 0
    assert not [f for f in ici.findings if f.rule == "HVD401"]


def test_budget_env_knobs(monkeypatch):
    monkeypatch.setenv("HVD_COMM_BUDGET_BYTES", "123")
    assert shardplan.comm_budget_bytes() == 123
    monkeypatch.setenv("HVD_COMM_BUDGET_BYTES", "not-a-number")
    assert shardplan.comm_budget_bytes() is None
    monkeypatch.setenv("HVD_COMM_DCN_BUDGET_BYTES", "77")
    assert shardplan.dcn_budget_bytes() == 77
    monkeypatch.setenv("HVD_COMM_DCN_AXES", "cross, pp")
    assert shardplan.dcn_axes_override() == ("cross", "pp")


# ---------------------------------------------------------------------------
# HVD402: replicated-large operand
# ---------------------------------------------------------------------------

def test_replicated_large_operand_fires():
    """A 1 MiB fully-replicated operand next to an 'hvd'-sharded peer,
    with 8 | 512: sharding it would save 7/8 of the copy per device."""
    mesh = _mesh((8,), ("hvd",))
    row = NamedSharding(mesh, P("hvd", None))
    rep = NamedSharding(mesh, P(None, None))
    inner = jax.jit(lambda x, w: x @ w, in_shardings=(row, rep),
                    out_shardings=row)
    closed = jax.make_jaxpr(inner)(
        jnp.zeros((512, 512), jnp.float32),
        jnp.zeros((512, 512), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="replicated",
                                            mesh=mesh)
    fired = [f for f in r.findings if f.rule == "HVD402"]
    assert len(fired) == 1, [f.format() for f in r.findings]
    assert "'hvd'" in fired[0].message


def test_replicated_small_bias_is_clean():
    """The normal data-parallel layout — a replicated 2 KiB bias next to
    a sharded batch — is NOT a finding (under REPLICATED_MIN_BYTES)."""
    mesh = _mesh((8,), ("hvd",))
    row = NamedSharding(mesh, P("hvd", None))
    rep = NamedSharding(mesh, P(None))
    inner = jax.jit(lambda x, b: x + b, in_shardings=(row, rep),
                    out_shardings=row)
    closed = jax.make_jaxpr(inner)(
        jnp.zeros((512, 512), jnp.float32),
        jnp.zeros((512,), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="bias",
                                            mesh=mesh)
    assert not [f for f in r.findings if f.rule == "HVD402"]


# ---------------------------------------------------------------------------
# HVD403: undeclared axis / mixed process-set scopes
# ---------------------------------------------------------------------------

def test_undeclared_axis_collective_fires():
    """The deployment mesh declares only 'hvd'; a collective over
    'rogue' names a process set that does not exist there."""
    def step(x):
        return jax.lax.psum(x, "rogue")

    closed = jax.make_jaxpr(step, axis_env=[("rogue", 2)])(
        jnp.ones((4,), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(closed, label="rogue",
                                            axis_sizes={"hvd": 8})
    fired = [f for f in r.findings if f.rule == "HVD403"]
    assert len(fired) == 1 and "'rogue'" in fired[0].message


def test_mixed_ici_dcn_flat_collective_fires():
    """One flat psum spanning an ICI axis and a DCN axis moves the whole
    payload at DCN speed — flagged; the wire bytes count as DCN."""
    def step(x):
        return jax.lax.psum(x, ("cross", "local"))

    r = shardplan.measure_step_fn_comm(
        step, (jnp.ones((8,), jnp.float32),),
        axis_env=[("cross", 2), ("local", 4)], dcn_axes=("cross",),
        label="mixed")
    fired = [f for f in r.findings if f.rule == "HVD403"]
    assert len(fired) == 1 and "hierarchically" in fired[0].message
    assert r.dcn_wire_bytes == r.total_wire_bytes == 8 * F32 * 8


# ---------------------------------------------------------------------------
# HVD404: dead mesh axes (jaxpr)
# ---------------------------------------------------------------------------

def test_dead_mesh_axis_fires_size_one_exempt():
    """'dead' (size 4) is never named by a collective or a spec → HVD404;
    a size-1 axis is free and never flagged."""
    def step(x):
        return jax.lax.psum(x, "hvd")

    closed = jax.make_jaxpr(step, axis_env=[("hvd", 8)])(
        jnp.ones((4,), jnp.float32))
    r = shardplan.measure_closed_jaxpr_comm(
        closed, label="dead",
        axis_sizes={"hvd": 8, "dead": 4, "solo": 1})
    fired = [f for f in r.findings if f.rule == "HVD404"]
    assert len(fired) == 1 and "'dead'" in fired[0].message
    assert r.axes_used == {"hvd"}


# ---------------------------------------------------------------------------
# check_replica_plan: the serve-layer go/no-go
# ---------------------------------------------------------------------------

def test_replica_plan_rejects_dcn_over_budget_admits_ici_equivalent():
    """The acceptance pair: identical plans except where the bytes flow —
    the DCN-heavy one is rejected (HVD401), the ICI-only one admitted."""
    bad = shardplan.check_replica_plan(
        "plan:dcn", step_comm_bytes=1 << 20, step_dcn_bytes=1 << 20,
        comm_budget=1 << 22, dcn_budget=1 << 16)
    assert bad.go is False
    assert [f.rule for f in bad.findings] == ["HVD401"]
    assert bad.comm["dcn_headroom_bytes"] == (1 << 16) - (1 << 20)

    good = shardplan.check_replica_plan(
        "plan:ici", step_comm_bytes=1 << 20, step_dcn_bytes=0,
        comm_budget=1 << 22, dcn_budget=1 << 16)
    assert good.go is True and not good.findings
    assert good.comm["headroom_bytes"] == (1 << 22) - (1 << 20)


def test_replica_plan_folds_mem_verdict():
    """A pool past the memory budget fails the plan through hvdmem's
    HVD302 — one combined verdict, not two surfaces to check."""
    bad = shardplan.check_replica_plan(
        "plan:mem", pool_bytes=2 << 20, weight_bytes=0,
        mem_budget_bytes=1 << 20)
    assert bad.go is False
    assert [f.rule for f in bad.findings] == ["HVD302"]
    assert bad.mem["headroom_bytes"] < 0


def _small_engine(**kw):
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.serve import (InferenceEngine, ServeMetrics,
                                   TransformerAdapter)
    cfg = TransformerConfig(vocab_size=64, causal=True,
                            dtype=jnp.float32, scan_layers=False,
                            num_layers=2, num_heads=2, d_model=32,
                            d_ff=64, max_len=32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    adapter = TransformerAdapter(cfg, params, block_tokens=8)
    engine = InferenceEngine(adapter, max_batch=2,
                             metrics=ServeMetrics(),
                             replica_id="shardplan-test", **kw)
    return adapter, engine


def test_engine_exposes_plan_go_on_kv_stats(monkeypatch):
    """A data-parallel replica (zero step comm bytes) passes trivially;
    the verdict rides kv_stats → replica healthz."""
    monkeypatch.setenv("HVD_MEM_BUDGET_BYTES", str(1 << 30))
    _core._state.analysis_reports = []
    _, engine = _small_engine()
    stats = engine.kv_stats()
    assert stats["plan_go"] is True
    assert stats["plan_findings"] == 0


def test_engine_plan_rejects_dcn_heavy_adapter(monkeypatch):
    """An adapter declaring per-step DCN bytes past the sub-budget is
    flagged at CONSTRUCTION (no traffic needed): plan_go False on
    kv_stats, the verdict published to analysis_reports."""
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.serve import (InferenceEngine, ServeMetrics,
                                   TransformerAdapter)
    monkeypatch.setenv("HVD_MEM_BUDGET_BYTES", str(1 << 30))
    monkeypatch.setenv("HVD_COMM_DCN_BUDGET_BYTES", "1024")
    _core._state.analysis_reports = []
    cfg = TransformerConfig(vocab_size=64, causal=True,
                            dtype=jnp.float32, scan_layers=False,
                            num_layers=2, num_heads=2, d_model=32,
                            d_ff=64, max_len=32)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    adapter = TransformerAdapter(cfg, params, block_tokens=8)
    adapter.step_comm_bytes = 1 << 20
    adapter.step_dcn_bytes = 1 << 20
    engine = InferenceEngine(adapter, max_batch=2,
                             metrics=ServeMetrics(),
                             replica_id="shardplan-dcn")
    stats = engine.kv_stats()
    assert stats["plan_go"] is False
    assert stats["plan_findings"] >= 1
    published = [r for r in _core.analysis_reports()
                 if getattr(r, "label", "").endswith(":plan")]
    assert published and published[-1].go is False


# ---------------------------------------------------------------------------
# Surfacing: Timeline counters + the HVD_ANALYZE hook ride-along
# ---------------------------------------------------------------------------

def test_comm_census_lands_on_timeline(tmp_path):
    """COMM_CENSUS counter events mirror MEMORY_CENSUS: one totals
    counter, one per collective primitive, one per axis tagged with its
    fabric."""
    from horovod_tpu.timeline import Timeline

    def step(x):
        return jax.lax.psum(x, "hvd")

    r = shardplan.measure_step_fn_comm(
        step, (jnp.ones((128,), jnp.float32),),
        axis_env=[("hvd", 8)], label="comm_step")
    path = str(tmp_path / "comm_timeline.json")
    tl = Timeline(path, rank=0)
    tl.comm_census("comm_step", r.to_dict())
    tl.close()
    with open(path) as fh:
        events = json.load(fh)
    names = [e.get("name", "") for e in events]
    assert "COMM_CENSUS/comm_step" in names
    assert "COMM_CENSUS/comm_step/psum" in names
    assert "COMM_CENSUS/comm_step/axis/hvd[ici]" in names
    totals = next(e for e in events
                  if e.get("name") == "COMM_CENSUS/comm_step")
    assert totals["ph"] == "C"
    assert totals["args"]["total_wire_bytes"] == r.total_wire_bytes == 4096


def test_hook_attaches_comm_to_training_reports(analyze_env, hvd8):
    """The HVD_ANALYZE hook runs the sharding walk on the SAME trace as
    the collective + memory censuses — a shard_step report carries all
    three, and the mesh seeds the declared axes."""
    import horovod_tpu as hvd

    def local_step(x):
        return jax.lax.psum(x * 2.0, "hvd")

    step = hvd.shard_step(local_step, in_specs=(P("hvd"),),
                          out_specs=P("hvd"))
    step(jnp.ones((8, 4), jnp.float32))
    reports = [r for r in _core.analysis_reports()
               if getattr(r, "comm", None)]
    assert reports, "no report carried a comm census"
    comm = reports[-1].comm
    assert comm["by_primitive"]["psum"]["count"] >= 1
    assert comm["axes_declared"] == {"hvd": 8}
    assert comm["by_axis"]["hvd"]["fabric"] == "ici"


# ---------------------------------------------------------------------------
# AST corpus: HVD400/HVD404 source shapes at pinned lines
# ---------------------------------------------------------------------------

SRC_HVD400 = """\
import jax
from jax.sharding import PartitionSpec as P

def step(x, w):
    a = jax.lax.with_sharding_constraint(x, P("dp"))
    b = jax.lax.with_sharding_constraint(x, P(None, "tp"))
    return a + b + w
"""

SRC_HVD400_REBIND_CLEAN = """\
import jax
from jax.sharding import PartitionSpec as P

def step(x):
    y = jax.lax.with_sharding_constraint(x, P("dp"))
    z = jax.lax.with_sharding_constraint(y, P(None, "tp"))
    return z
"""

SRC_HVD404 = """\
from jax.sharding import Mesh, PartitionSpec as P

def layout(devs):
    mesh = Mesh(devs, ("dp", "tp"))
    spec = P("dp")
    return spec
"""

SRC_HVD404_ESCAPED_CLEAN = """\
from jax.sharding import Mesh, PartitionSpec as P

def layout(devs):
    mesh = Mesh(devs, ("dp", "tp"))
    spec = P("dp")
    return mesh
"""


def _rules_lines(findings):
    return [(f.rule, f.line) for f in unsuppressed(findings)]


def test_ast_hvd400_second_annotation_pinned_line():
    fs = shardplan.analyze_source(SRC_HVD400, "corpus.py")
    assert _rules_lines(fs) == [("HVD400", 6)]
    assert "'x'" in fs[0].message


def test_ast_hvd400_rebinding_is_the_clean_idiom():
    assert shardplan.analyze_source(SRC_HVD400_REBIND_CLEAN,
                                    "clean.py") == []


def test_ast_hvd404_dead_axis_pinned_at_mesh_ctor():
    fs = shardplan.analyze_source(SRC_HVD404, "corpus.py")
    assert _rules_lines(fs) == [("HVD404", 4)]
    assert "'tp'" in fs[0].message


def test_ast_hvd404_escaped_mesh_is_clean():
    """A returned mesh's axes may be exercised by callers — skipped."""
    assert shardplan.analyze_source(SRC_HVD404_ESCAPED_CLEAN,
                                    "clean.py") == []


def test_ast_pragma_suppression_retained_for_audit():
    src = SRC_HVD400.replace(
        '    b = jax.lax.with_sharding_constraint(x, P(None, "tp"))',
        '    b = jax.lax.with_sharding_constraint(x, P(None, "tp"))'
        '  # hvdlint: disable=HVD400')
    fs = shardplan.analyze_source(src, "sup.py")
    assert len(fs) == 1 and fs[0].suppressed
    assert unsuppressed(fs) == []


def test_ast_select_ignore_prefix_contract():
    assert shardplan.analyze_source(SRC_HVD400, "s.py",
                                    select=["HVD4"])
    assert shardplan.analyze_source(SRC_HVD400, "s.py",
                                    select=["HVD404"]) == []
    assert shardplan.analyze_source(SRC_HVD400, "s.py",
                                    ignore=["HVD4"]) == []


def test_ast_parse_failure_is_a_finding_not_a_crash():
    fs = shardplan.analyze_source("def broken(:\n", "bad.py")
    assert [f.rule for f in fs] == ["HVD000"]
