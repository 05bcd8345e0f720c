"""hvdmem — static HBM liveness, donation, and budget analysis (HVD3xx).

Acceptance coverage (ISSUE 10):

* liveness-walk unit tests with HAND-COMPUTED peak bytes for
  straight-line / scan (carry-aware, not multiplied by trip count) /
  cond (branches max'd) / pjit (wrapper unwrapped, donation honored)
  jaxprs;
* a seeded corpus firing each of HVD300-HVD304 exactly where expected,
  with clean-fixture negatives (donated arg, scan-carry reuse, small
  intentional f32 islands, under-threshold fusion buckets);
* HVD301 statically flags a regression-test reproduction of the PR 4
  donated-then-consumed cache bug;
* HVD302 flags a BlockManager pool deliberately sized past a 1 GiB
  HVD_MEM_BUDGET_BYTES, and the headroom surfaces as
  ``kv_headroom_bytes`` on kv_stats/healthz/metrics;
* the liveness estimate for the serve decode program is within 2x of
  the summed cache+weights bytes the engine actually allocates (live
  array nbytes on the CPU backend);
* ROADMAP-5 lint gap: the serve prefill/decode programs get a
  collective census under HVD_ANALYZE=1 and census ZERO collectives;
* the ``--mem`` CLI honors the shared exit-code / pragma / prefix
  ``--select HVD3`` contract.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import core as _core
from horovod_tpu.analysis import hook, memplan, unsuppressed
from horovod_tpu.analysis.cli import main as cli_main

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

F32 = 4  # bytes


# ---------------------------------------------------------------------------
# Liveness walk: hand-computed peaks
# ---------------------------------------------------------------------------

def test_straight_line_peak_donated():
    """x(4KB) -> y=x*2 -> z=y+1, x donated: the peak is x+y at the mul
    (x dies there); the add runs at y+z = the same 8KB."""
    def f(x):
        return x * 2.0 + 1.0

    r = memplan.measure_step_fn(f, (jnp.ones(1024, jnp.float32),),
                                label="line", donate_argnums=(0,))
    assert r.input_bytes == 1024 * F32
    assert r.output_bytes == 1024 * F32
    assert r.peak_live_bytes == 2 * 1024 * F32
    assert r.by_primitive["mul"] == {"count": 1, "bytes": 4096}
    assert r.by_primitive["add"] == {"count": 1, "bytes": 4096}


def test_straight_line_peak_pinned_inputs():
    """Same program, donation unknown: the caller still holds x, so the
    add's live set is x+y+z = 12KB."""
    def f(x):
        return x * 2.0 + 1.0

    r = memplan.measure_step_fn(f, (jnp.ones(1024, jnp.float32),),
                                label="pinned")
    assert r.peak_live_bytes == 3 * 1024 * F32


def test_scan_body_counted_once_not_times_trip_count():
    """A scan body's working set exists once per iteration SEQUENTIALLY:
    peak must be carry-aware (x + out + body transient), identical for
    length 3 and length 300 — never multiplied by trip count."""
    def make(length):
        def f(x):
            def body(c, _):
                return c * 2.0 + 1.0, ()
            out, _ = jax.lax.scan(body, x, None, length=length)
            return out
        return f

    r3 = memplan.measure_step_fn(make(3), (jnp.ones(1024, jnp.float32),),
                                 label="scan3", donate_argnums=(0,))
    r300 = memplan.measure_step_fn(make(300),
                                   (jnp.ones(1024, jnp.float32),),
                                   label="scan300", donate_argnums=(0,))
    assert r3.peak_live_bytes == r300.peak_live_bytes
    # x(4K) + scan-out(4K) + body transient (c*2 lives next to c and the
    # add result beyond the 4K boundary: 4K) = 12K.
    assert r3.peak_live_bytes == 3 * 1024 * F32


def test_cond_branches_maxed_not_summed():
    """Branches are exclusive at runtime: a fat branch (two 4KB temps
    beyond the boundary) and a thin one (none) contribute max(8K, 0),
    not the sum."""
    def f(x):
        def fat(z):
            return (z * 2.0) + (z * 3.0)

        def thin(z):
            return z

        return jax.lax.cond(jnp.sum(x) > 0, fat, thin, x)

    r = memplan.measure_step_fn(f, (jnp.ones(1024, jnp.float32),),
                                label="cond", donate_argnums=(0,))
    # entry x=4K; cond eqn: out 4K + transient(fat) = max over branch
    # programs. fat: boundary 4K; z*2 -> 8K; z*3 -> 12K (z still live);
    # add -> 12K; transient = 12K - 4K = 8K.  Peak = 16K (+ the
    # predicate scalars) — and decisively NOT fat+thin summed (20K+).
    assert 4 * 1024 * F32 <= r.peak_live_bytes <= 4 * 1024 * F32 + 64
    assert r.peak_live_bytes < 5 * 1024 * F32


def test_pjit_wrapper_unwrapped_and_donation_read_from_it():
    """make_jaxpr of a jitted fn yields one pjit eqn; the walker descends
    into it and reads donated_invars off the wrapper — the donated cache
    dies at its last use instead of pinning."""
    def f(cache, x):
        return cache.at[0].set(x.sum()), x * 2.0

    big = jnp.ones((2048,), jnp.float32)  # 8KB
    small = jnp.ones((256,), jnp.float32)  # 1KB
    donated = memplan.measure_step_fn(jax.jit(f, donate_argnums=(0,)),
                                      (big, small), label="dj")
    pinned = memplan.measure_step_fn(jax.jit(f), (big, small), label="pj")
    assert donated.peak_live_bytes < pinned.peak_live_bytes
    # Both walked the INNER program, not just one opaque pjit eqn.
    assert "scatter" in donated.by_primitive


def test_closure_captured_consts_stay_pinned():
    """Closure-captured weights land in the jaxpr's constvars under
    make_jaxpr; the caller (ClosedJaxpr.consts) holds them for the whole
    call, so the walk must pin them like non-donated invars — not free
    them after their last read (which masked HVD302 on closed-over
    params)."""
    w = jnp.ones(1024, jnp.float32)  # 4KB, used ONLY in the first eqn

    def f(x):
        y = x + w
        big = jnp.concatenate([y, y, y, y])  # 16KB
        return big * 2.0                     # 16KB

    r = memplan.measure_step_fn(f, (jnp.ones(1024, jnp.float32),),
                                label="const-pin", donate_argnums=(0,))
    # Entry w+x=8K; add: +y=12K, x dies -> 8K; concat: +16K=24K, y dies
    # -> 20K; mul: +16K = 36K peak WITH w still resident.  An unpinned
    # walk frees w after the add and lands at 32K.
    assert r.peak_live_bytes == 9 * 1024 * F32


def test_sharding_divisor_reads_spec_axes():
    """pjit sharded dims divide by the product of the named mesh axis
    sizes (duck-typed: any .spec/.mesh.shape sharding works)."""
    class _Mesh:
        shape = {"dp": 8, "tp": 4}

    class _Sharding:
        spec = ("dp", None)
        mesh = _Mesh()

    class _Both:
        spec = (("dp", "tp"), None)
        mesh = _Mesh()

    assert memplan.sharding_divisor(_Sharding()) == 8
    assert memplan.sharding_divisor(_Both()) == 32
    assert memplan.sharding_divisor(object()) == 1


def test_shard_map_accounts_per_shard_bytes(hvd8):
    """A shard_map wrapper's body avals are per-shard: the walk of a
    jit(shard_map(f)) program sees bytes already divided by the mesh
    axis size for the sharded dim."""
    from jax.sharding import PartitionSpec as P
    mesh = hvd8.mesh()

    def local(x):
        return x + x  # no scalar constant: its varying cast counts 4 bytes

    stepped = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("hvd"),
                                    out_specs=P("hvd")))
    n = hvd8.num_slots()
    r = memplan.measure_step_fn(stepped, (jnp.ones((n * 1024,),
                                                   jnp.float32),),
                                label="sharded")
    # Per-shard: 1024 f32 in + 1024 f32 out (input pinned: donation
    # unknown) = 8KB, NOT the global 8KB * n.
    assert r.input_bytes == 1024 * F32
    assert r.peak_live_bytes == 2 * 1024 * F32


# ---------------------------------------------------------------------------
# Jaxpr rules: HVD300 / HVD302 / HVD303 / HVD304 + negatives
# ---------------------------------------------------------------------------

def test_hvd300_fires_on_undonated_matching_arg_and_not_when_donated():
    def f(cache, t):
        return cache.at[0].set(t.sum()), t * 1.0

    big = jnp.ones((1 << 19,), jnp.float32)  # 2 MiB: above the floor
    r = memplan.measure_step_fn(jax.jit(f), (big, jnp.ones(4)),
                                label="undonated")
    assert [x.rule for x in r.findings] == ["HVD300"]
    assert "donate" in r.findings[0].message
    r_ok = memplan.measure_step_fn(jax.jit(f, donate_argnums=(0,)),
                                   (big, jnp.ones(4)), label="donated")
    assert r_ok.ok(), [x.message for x in r_ok.findings]


def test_hvd300_ignores_small_args():
    """Donating a [B]-sized token vector saves nothing — below the
    byte floor no finding fires (the serve decode programs' token rows
    stay clean)."""
    def f(tok):
        return tok + 1

    r = memplan.measure_step_fn(jax.jit(f), (jnp.ones(8, jnp.int32),),
                                label="small")
    assert r.ok()


def test_hvd300_donated_arg_consumes_its_aliased_output():
    """fn(new, old) donating arg 0 with ONE output of that shape+dtype:
    XLA aliases the output to the donated buffer, so the output is
    spoken for — arg 1 must NOT be flagged (donating it buys nothing)."""
    def f(new, old):
        return new + old

    big = jnp.ones((1 << 19,), jnp.float32)  # 2 MiB each
    r = memplan.measure_step_fn(jax.jit(f, donate_argnums=(0,)),
                                (big, big + 1), label="aliased")
    assert r.ok(), [x.message for x in r.findings]


def test_hvd300_one_output_flags_at_most_one_of_two_matching_args():
    """f(a, b) -> one matching output: at most ONE donation is usable,
    so exactly one HVD300 fires — matches are consumed, not re-counted
    per arg."""
    def f(a, b):
        return a + b

    big = jnp.ones((1 << 19,), jnp.float32)
    r = memplan.measure_step_fn(jax.jit(f), (big, big + 1), label="pair")
    assert [x.rule for x in r.findings] == ["HVD300"]


def test_hvd302_peak_exceeds_budget():
    def f(x):
        return x * 2.0 + 1.0

    x = jnp.ones((1024,), jnp.float32)
    r = memplan.measure_step_fn(f, (x,), label="tight",
                                budget_bytes=8 * 1024)
    assert [x_.rule for x_ in r.findings] == ["HVD302"]
    assert r.headroom_bytes < 0
    ok = memplan.measure_step_fn(f, (x,), label="roomy",
                                 budget_bytes=1 << 20)
    assert ok.ok() and ok.headroom_bytes > 0


def test_hvd303_upcast_blowup_and_small_island_negative():
    def widen(p):
        return p.astype(jnp.float32) * 2.0

    p = jnp.ones((4096,), jnp.bfloat16)
    r = memplan.measure_step_fn(widen, (p,), label="widen",
                                upcast_min_bytes=1024)
    assert [x.rule for x in r.findings] == ["HVD303"]
    assert r.upcast_f32_bytes == 4096 * F32
    # The intentional f32 island under the documented knob (layernorm-
    # style, a few KB) stays below the default floor: clean.
    r_ok = memplan.measure_step_fn(widen, (p,), label="island")
    assert r_ok.ok()


def test_upcast_floor_knob_read_per_call_and_malformed_degrades(monkeypatch):
    """HVD_MEM_UPCAST_MIN_BYTES is read per call (not frozen at import)
    and a malformed value degrades to the 8 MiB default instead of
    raising — one typo'd env var must never brick the package import."""
    monkeypatch.setenv("HVD_MEM_UPCAST_MIN_BYTES", "8MB")
    assert memplan.upcast_min_bytes_default() == 8 << 20

    def widen(p):
        return p.astype(jnp.float32) * 2.0

    p = jnp.ones((4096,), jnp.bfloat16)
    monkeypatch.setenv("HVD_MEM_UPCAST_MIN_BYTES", "1024")
    r = memplan.measure_step_fn(widen, (p,), label="widen-env")
    assert [x.rule for x in r.findings] == ["HVD303"]


def test_hvd304_fusion_bucket_overshoot_and_under_threshold_negative():
    def fused(a, b):
        return jnp.concatenate([a.reshape(-1), b.reshape(-1)])

    a = jnp.ones((1024,), jnp.float32)
    b = jnp.ones((1024,), jnp.float32)
    r = memplan.measure_step_fn(fused, (a, b), label="bucket",
                                fusion_threshold=4 * 1024)
    assert [x.rule for x in r.findings] == ["HVD304"]
    assert "HOROVOD_FUSION_THRESHOLD" in r.findings[0].message
    r_ok = memplan.measure_step_fn(fused, (a, b), label="bucket-ok",
                                   fusion_threshold=64 * 1024)
    assert r_ok.ok()


# ---------------------------------------------------------------------------
# AST rules: HVD301 (the PR 4 hazard) / HVD300 source shapes
# ---------------------------------------------------------------------------

_PR4_REPRO = """
import jax

def decode_step(cache, tok):
    cache = cache.at[0].set(tok)
    return cache, tok + 1

def engine_loop(cache, tok):
    step = jax.jit(decode_step, donate_argnums=(0,))
    new_cache, nxt = step(cache, tok)
    stale = cache[0]
    return new_cache, nxt, stale
"""

_PR4_FIXED = _PR4_REPRO.replace(
    "    new_cache, nxt = step(cache, tok)\n    stale = cache[0]\n"
    "    return new_cache, nxt, stale",
    "    cache, nxt = step(cache, tok)\n    stale = cache[0]\n"
    "    return cache, nxt, stale")


def test_hvd301_flags_the_pr4_donated_then_consumed_bug():
    """Acceptance: the PR 4 cache hazard — cache donated into the jitted
    decode step, then read again — is flagged STATICALLY (instead of the
    runtime is_deleted check catching the deleted buffer mid-serve)."""
    findings = memplan.analyze_source(_PR4_REPRO, "pr4_repro.py")
    assert [f.rule for f in findings] == ["HVD301"]
    assert "donated" in findings[0].message
    assert findings[0].line == 11  # the stale read, not the call


def test_hvd301_rebinding_the_donated_name_is_clean():
    assert memplan.analyze_source(_PR4_FIXED, "pr4_fixed.py") == []


def test_hvd301_tracks_self_attribute_callables():
    src = """
import jax

class Engine:
    def setup(self, step):
        self._fn = jax.jit(step, donate_argnums=(1,))

    def run(self, params, cache, tok):
        out, nxt = self._fn(params, cache, tok)
        return out, nxt, cache["k"]
"""
    findings = memplan.analyze_source(src, "attr.py")
    assert [f.rule for f in findings] == ["HVD301"]


def test_hvd300_ast_jit_without_donation_of_updated_param():
    src = """
import jax

def build():
    def fn(params, cache, tok):
        ck = cache["k"]
        ck = ck.at[0].set(tok)
        return {"k": ck}, tok
    return jax.jit(fn)
"""
    findings = memplan.analyze_source(src, "h300.py")
    assert [f.rule for f in findings] == ["HVD300"]
    fixed = src.replace("jax.jit(fn)", "jax.jit(fn, donate_argnums=(1,))")
    assert memplan.analyze_source(fixed, "h300ok.py") == []


def test_hvd300_ast_scan_carry_reuse_is_exempt():
    """The scan-carry idiom: the body updates ITS OWN carry parameter —
    that is the clean functional-threading pattern, not a donation gap
    at the jit site (taint is scoped per function)."""
    src = """
import jax
import jax.numpy as jnp

def outer():
    def body(carry, x):
        carry = carry.at[0].set(x)
        return carry, x

    def fn(xs):
        c, ys = jax.lax.scan(body, jnp.zeros(4), xs)
        return ys
    return jax.jit(fn)
"""
    assert memplan.analyze_source(src, "scan.py") == []


def test_pragma_suppression_and_audit_trail():
    src = _PR4_REPRO.replace(
        "    stale = cache[0]",
        "    stale = cache[0]  # hvdlint: disable=HVD301")
    findings = memplan.analyze_source(src, "sup.py")
    assert [f.rule for f in findings] == ["HVD301"]
    assert findings[0].suppressed  # still reported: auditable
    assert unsuppressed(findings) == []


# ---------------------------------------------------------------------------
# CLI contract: --mem rides the shared pass registry
# ---------------------------------------------------------------------------

def test_mem_cli_exit_contract(tmp_path, capsys):
    """--mem honors the exact 0/1/2 contract lint and --race define: 0
    clean, 1 findings (incl. HVD000 parse failures and missing paths)."""
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text(_PR4_REPRO)
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")

    for args, expected in (
            ([str(clean)], 0),
            ([str(dirty)], 1),
            ([str(bad)], 1),
            (["/nonexistent/mem/path"], 1)):
        rc = cli_main(["--mem"] + args)
        capsys.readouterr()
        assert rc == expected, (args, rc)
    # Parse-failure / missing-path classes agree across all three passes.
    for args in ([str(bad)], ["/nonexistent/mem/path"]):
        rcs = {cli_main(flag + args)
               for flag in ([], ["--race"], ["--mem"])}
        capsys.readouterr()
        assert rcs == {1}


def test_select_prefix_works_uniformly_across_passes(tmp_path, capsys):
    """--select HVD3 (a prefix) runs the whole HVD3xx family; the same
    prefix under the lint pass selects nothing — one filter, every
    pass."""
    dirty = tmp_path / "dirty.py"
    dirty.write_text(_PR4_REPRO)
    assert cli_main(["--mem", "--select", "HVD3", str(dirty)]) == 1
    capsys.readouterr()
    assert cli_main(["--mem", "--select", "HVD302", str(dirty)]) == 0
    capsys.readouterr()
    assert cli_main(["--select", "HVD3", str(dirty)]) == 0  # lint pass
    capsys.readouterr()


def test_mem_cli_json_format(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(_PR4_REPRO)
    rc = cli_main(["--mem", "--format", "json", str(dirty)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["pass"] == "mem"
    assert out["summary"]["by_rule"] == {"HVD301": 1}


def test_mem_dogfood_command_exits_zero(capsys):
    """The acceptance command: python -m horovod_tpu.analysis --mem
    horovod_tpu examples (in-process — same code path)."""
    rc = cli_main(["--mem", os.path.join(_REPO, "horovod_tpu"),
                   os.path.join(_REPO, "examples")])
    capsys.readouterr()
    assert rc == 0


# ---------------------------------------------------------------------------
# Serve integration: HVD_ANALYZE census + liveness vs real allocation,
# pool-budget HVD302, kv_headroom_bytes surfaces
# ---------------------------------------------------------------------------

@pytest.fixture()
def analyze_env(monkeypatch):
    monkeypatch.setenv("HVD_ANALYZE", "1")
    hook.reset()
    _core._state.analysis_reports = []
    yield
    hook.reset()


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
                             replica_id="memplan-test", **kw)
    return adapter, engine


def test_serve_programs_census_zero_collectives(analyze_env):
    """ROADMAP-5 lint gap closed: the engine's prefill/decode builders
    register with the HVD_ANALYZE hook, so their first compile gets the
    HVD1xx walk + collective census — and a serving replica, being
    data-parallel and self-contained, must census ZERO collectives.
    This is the invariant that catches a future model-parallel serve
    program sneaking a collective into an unregistered path."""
    adapter, engine = _small_engine()
    out = engine.generate([1, 2, 3, 4, 5], max_new_tokens=4)
    engine.stop()
    assert len(out) == 4
    reports = _core.analysis_reports()
    serve_labels = [r.label for r in reports
                    if r.label.startswith("serve:")]
    assert any("prefill_chunk" in lb for lb in serve_labels)
    assert any("decode_paged" in lb for lb in serve_labels)
    for r in reports:
        if r.label.startswith("serve:"):
            assert r.census == {}, (r.label, r.census)
            assert not [f for f in r.findings if f.rule != "HVD303"], \
                [(f.rule, f.message) for f in r.findings]


def test_serve_decode_liveness_within_2x_of_real_allocation(analyze_env):
    """Acceptance: the liveness estimate for the serve decode program is
    within 2x of the summed cache+weights bytes the engine actually
    allocates (live array nbytes on the CPU backend).  The walk's only
    systematic over-count is the one transient pool copy at the scatter
    (XLA aliases it via donation), which is bounded by the pool size —
    hence < 2x by construction."""
    adapter, engine = _small_engine()
    engine.generate([1, 2, 3, 4, 5], max_new_tokens=4)
    engine.stop()
    reports = [r for r in _core.analysis_reports()
               if r.label.startswith("serve:decode_paged")]
    assert reports, [r.label for r in _core.analysis_reports()]
    peak = reports[0].memory["peak_live_bytes"]
    actual = (memplan.params_bytes(adapter.params)
              + memplan.params_bytes(engine._cache))
    assert actual > 0
    assert actual / 2 <= peak <= actual * 2, (peak, actual)


def test_hvd302_flags_pool_past_1gib_budget(monkeypatch):
    """Acceptance: a BlockManager pool deliberately sized past a 1 GiB
    HVD_MEM_BUDGET_BYTES fires HVD302 at engine construction (before
    anything OOMs), and the negative headroom is visible on
    kv_stats/healthz/metrics."""
    from horovod_tpu.serve import (InferenceEngine, MLPAdapter, Replica,
                                   ServeMetrics)
    from horovod_tpu.models import create_mlp

    monkeypatch.setenv("HVD_MEM_BUDGET_BYTES", str(1 << 30))  # 1 GiB
    _core._state.analysis_reports = []

    vocab = 16
    mlp = create_mlp(features=(8, vocab))
    params = mlp.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, vocab)))["params"]

    class _FatBlockAdapter(MLPAdapter):
        """Reports a 64 MiB per-block cost without allocating it — the
        budget check verifies the ACCOUNTING plan, not a real 2 GiB
        allocation on the test box."""
        max_blocks_per_seq = 4
        block_tokens = 16
        kv_token_cost = 0

        def paged_block_bytes(self):
            return 64 << 20

    adapter = _FatBlockAdapter(mlp, params, vocab_size=vocab)
    metrics = ServeMetrics()
    engine = InferenceEngine(adapter, max_batch=2,
                             num_blocks=32,  # 32 x 64 MiB = 2 GiB
                             metrics=metrics, replica_id="fat-pool")
    # HVD302 published at construction.
    mem_reports = [r for r in _core.analysis_reports()
                   if getattr(r, "label", "").endswith("kv-pool")]
    assert mem_reports
    assert [f.rule for f in mem_reports[0].findings] == ["HVD302"]
    assert "exceeds the memory budget" in mem_reports[0].findings[0].message
    # Negative headroom on every surface: kv_stats, healthz, /metrics.
    stats = engine.kv_stats()
    assert stats["pool_bytes"] == 32 * (64 << 20)
    assert stats["kv_headroom_bytes"] < 0
    replica = Replica("fat-pool", None, engine)
    assert replica.to_dict()["kv_blocks"]["kv_headroom_bytes"] < 0
    metrics.register_kv_stats("fat-pool", engine.kv_stats)
    exposition = metrics.render()
    assert 'hvd_serve_kv_headroom_bytes{replica="fat-pool"}' in exposition


def test_pool_within_budget_has_positive_headroom(monkeypatch):
    monkeypatch.setenv("HVD_MEM_BUDGET_BYTES", str(1 << 30))
    _core._state.analysis_reports = []
    adapter, engine = _small_engine()
    stats = engine.kv_stats()
    assert stats["kv_headroom_bytes"] > 0
    assert not [r for r in _core.analysis_reports()
                if getattr(r, "label", "").endswith("kv-pool")]


def test_memory_census_lands_on_timeline(tmp_path):
    """The MEMORY_CENSUS counter events mirror the collective census:
    one totals counter + one per allocating primitive."""
    from horovod_tpu.timeline import Timeline

    def f(x):
        return x * 2.0 + 1.0

    r = memplan.measure_step_fn(f, (jnp.ones(1024, jnp.float32),),
                                label="mem_step", donate_argnums=(0,))
    path = str(tmp_path / "mem_timeline.json")
    tl = Timeline(path, rank=0)
    tl.memory_census("mem_step", r.to_dict())
    tl.close()
    with open(path) as fh:
        events = json.load(fh)
    names = [e.get("name", "") for e in events]
    assert "MEMORY_CENSUS/mem_step" in names
    assert "MEMORY_CENSUS/mem_step/mul" in names
    totals = next(e for e in events
                  if e.get("name") == "MEMORY_CENSUS/mem_step")
    assert totals["ph"] == "C"
    assert totals["args"]["peak_live_bytes"] == r.peak_live_bytes


def test_hook_attaches_memory_to_training_reports(analyze_env, hvd8):
    """The HVD_ANALYZE hook runs the liveness walk on the SAME trace as
    the collective census — a shard_step report carries both."""
    from jax.sharding import PartitionSpec as P
    import horovod_tpu as hvd

    def local_step(x):
        return jax.lax.psum(x * 2.0, "hvd")

    step = hvd.shard_step(local_step, in_specs=(P("hvd"),),
                          out_specs=P("hvd"))
    step(jnp.ones((8, 128), jnp.float32))
    reports = _core.analysis_reports()
    assert len(reports) == 1
    assert reports[0].census["psum"]["count"] == 1
    assert reports[0].memory["peak_live_bytes"] > 0
    assert reports[0].memory["by_primitive"]
