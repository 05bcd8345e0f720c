"""Jaxpr collective-consistency checker + HVD_ANALYZE trace-time hook.

Acceptance coverage (ISSUE 2): a deliberately branch-mismatched
``lax.cond`` collective and an undeclared axis name are detected; a clean
``DistributedOptimizer`` step passes with zero findings; the per-step
collective census (count + bytes)
for a DistributedOptimizer step is asserted and surfaced via
timeline.py's counter events.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import core as _core
from horovod_tpu.analysis import check_closed_jaxpr, check_step_fn, hook
from horovod_tpu.timeline import Timeline

N = 8


# ---------------------------------------------------------------------------
# Detection: the two seeded inconsistencies
# ---------------------------------------------------------------------------

def test_detects_branch_mismatched_cond_collective():
    def step(x):
        def sync(z):
            return jax.lax.psum(z, "hvd")

        def skip(z):
            return z

        return jax.lax.cond(jnp.sum(x) > 0, sync, skip, x)

    report = check_step_fn(step, (jnp.ones(4),), axis_env=[("hvd", N)])
    assert [f.rule for f in report.findings] == ["HVD102"]
    assert "psum" in report.findings[0].message
    # The census still counts the branch's psum (static upper bound).
    assert report.census["psum"]["count"] == 1


def test_matched_cond_branches_are_clean():
    def step(x):
        def a(z):
            return jax.lax.psum(z, "hvd") * 2.0

        def b(z):
            return jax.lax.psum(z, "hvd") + 1.0

        return jax.lax.cond(jnp.sum(x) > 0, a, b, x)

    report = check_step_fn(step, (jnp.ones(4),), axis_env=[("hvd", N)])
    assert report.ok(), [f.message for f in report.findings]


def test_detects_undeclared_axis_against_declared_set():
    def step(x):
        return jax.lax.psum(x, "tp")

    report = check_step_fn(step, (jnp.ones(4),),
                           axis_env=[("hvd", N), ("tp", 2)],
                           declared_axes=("hvd",))
    assert [f.rule for f in report.findings] == ["HVD101"]
    assert "'tp'" in report.findings[0].message


def test_unbound_axis_trace_failure_reported_not_raised():
    def step(x):
        return jax.lax.psum(x, "no_such_axis")

    report = check_step_fn(step, (jnp.ones(4),), axis_env=[("hvd", N)])
    assert [f.rule for f in report.findings] == ["HVD101"]
    assert "unbound axis" in report.findings[0].message


def test_trace_failure_reported_as_hvd100_not_raised():
    def step(x):
        raise RuntimeError("synthetic trace bomb")

    report = check_step_fn(step, (jnp.ones(4),))
    assert [f.rule for f in report.findings] == ["HVD100"]
    assert "synthetic trace bomb" in report.findings[0].message


def test_plain_python_nameerror_is_hvd100_not_axis_finding():
    """A typo NameError in the user's step fn must not masquerade as an
    unbound-axis HVD101 — even when the typo'd name contains 'axis'
    (review regression)."""
    def step(x):
        return x * axis_scale  # noqa: F821

    report = check_step_fn(step, (jnp.ones(4),))
    assert [f.rule for f in report.findings] == ["HVD100"]
    assert "axis_scale" in report.findings[0].message


def test_cond_branches_with_different_scan_trip_counts_mismatch():
    """psum scanned 2x vs 5x is a different runtime collective sequence —
    the signature must expand scans by length (review regression)."""
    def scanned(n):
        def branch(z):
            def body(c, _):
                return jax.lax.psum(c, "hvd"), None
            out, _ = jax.lax.scan(body, z, None, length=n)
            return out
        return branch

    def step(x):
        return jax.lax.cond(jnp.sum(x) > 0, scanned(2), scanned(5), x)

    report = check_step_fn(step, (jnp.ones(4),), axis_env=[("hvd", N)])
    assert [f.rule for f in report.findings] == ["HVD102"]
    assert report.census["psum"]["count"] == 7  # 2 + 5, both branches


# ---------------------------------------------------------------------------
# Census mechanics
# ---------------------------------------------------------------------------

def test_census_counts_bytes_and_scan_trip_expansion():
    def step(x):
        def body(c, _):
            return jax.lax.psum(c, "hvd"), None

        y, _ = jax.lax.scan(body, x, None, length=5)
        return y + jax.lax.ppermute(
            x, "hvd", [(i, (i + 1) % N) for i in range(N)])

    report = check_step_fn(step, (jnp.ones(4, jnp.float32),),
                           axis_env=[("hvd", N)])
    assert report.ok()
    assert report.census["psum"] == {"count": 5, "bytes": 5 * 16}
    assert report.census["ppermute"] == {"count": 1, "bytes": 16}
    assert report.total_collectives() == 6
    assert report.total_bytes() == 96


def test_while_loop_counts_once_and_marks_dynamic():
    def step(x):
        def cond(c):
            return jnp.sum(c) < 100.0

        def body(c):
            return jax.lax.psum(c, "hvd")

        return jax.lax.while_loop(cond, body, x)

    report = check_step_fn(step, (jnp.ones(4),), axis_env=[("hvd", N)])
    assert report.ok()
    assert report.census["psum"]["count"] == 1
    assert report.dynamic_loops == 1


def test_shard_map_program_declares_its_own_axes(hvd8):
    """A fully wrapped jit(shard_map) step needs no axis_env: the walker
    reads the declared axes off the shard_map eqn's mesh."""
    mesh = hvd8.mesh()

    def local(x):
        return jax.lax.psum(x, "hvd")

    stepped = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("hvd"),
                                    out_specs=P("hvd")))
    report = check_step_fn(stepped, (jnp.ones((N, 4)),), label="wrapped")
    assert report.ok(), [f.message for f in report.findings]
    assert report.census["psum"]["count"] == 1


# ---------------------------------------------------------------------------
# The DistributedOptimizer acceptance trio: clean step, census, timeline
# ---------------------------------------------------------------------------

def _opt_fixture():
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    params = {"w": jnp.ones((3, 2), jnp.float32),
              "b": jnp.ones((2,), jnp.float32)}
    state = opt.init(params)
    grads = {"w": jnp.full((3, 2), 0.5, jnp.float32),
             "b": jnp.full((2,), 0.5, jnp.float32)}
    return opt, params, state, grads


def test_clean_distributed_optimizer_step_zero_findings(hvd8):
    opt, params, state, grads = _opt_fixture()

    def update(g):
        u, _ = opt.update(g, state, params)
        return u

    report = check_step_fn(update, (grads,),
                           axis_env=[(hvd.mesh_axis(), hvd.num_slots())],
                           label="opt_step")
    assert report.ok(), [f.message for f in report.findings]
    # One psum per gradient leaf; payload = the two leaves' f32 bytes.
    assert report.census["psum"]["count"] == 2
    assert report.census["psum"]["bytes"] == (6 + 2) * 4


def test_optimizer_census_surfaced_via_timeline(hvd8, tmp_path):
    opt, params, state, grads = _opt_fixture()

    def update(g):
        u, _ = opt.update(g, state, params)
        return u

    report = check_step_fn(update, (grads,),
                           axis_env=[(hvd.mesh_axis(), hvd.num_slots())],
                           label="opt_step")
    path = str(tmp_path / "census_timeline.json")
    tl = Timeline(path, rank=0)
    tl.collective_census("opt_step", report.census)
    tl.close()
    with open(path) as f:
        events = json.load(f)
    census_events = [e for e in events
                     if str(e.get("name", "")).startswith(
                         "COLLECTIVE_CENSUS/opt_step/")]
    assert len(census_events) == 1
    ev = census_events[0]
    assert ev["ph"] == "C"
    assert ev["name"] == "COLLECTIVE_CENSUS/opt_step/psum"
    assert ev["args"] == {"count": 2, "bytes": 32}


def test_full_training_step_census_includes_metric_allreduce(hvd8):
    """A realistic shard_step body: grads + loss-allreduce both appear."""
    opt, params, state, grads = _opt_fixture()

    def local_step(p, s, xb):
        def loss_fn(p_):
            return jnp.sum((xb @ p_["w"] + p_["b"]) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(p)
        u, s = opt.update(g, s, p)
        loss = hvd.allreduce(loss, op=hvd.Average)
        return optax.apply_updates(p, u), s, loss

    mesh = hvd8.mesh()
    mapped = jax.shard_map(local_step, mesh=mesh,
                           in_specs=(P(), P(), P("hvd")),
                           out_specs=(P(), P(), P()))
    xb = jnp.ones((N, 3), jnp.float32)
    report = check_step_fn(mapped, (params, state, xb), label="train")
    assert report.ok(), [f.message for f in report.findings]
    assert report.census["psum"]["count"] == 3  # w, b, loss


# ---------------------------------------------------------------------------
# HVD_ANALYZE=1 trace-time hook
# ---------------------------------------------------------------------------

@pytest.fixture()
def analyze_env(monkeypatch):
    monkeypatch.setenv("HVD_ANALYZE", "1")
    hook.reset()
    yield
    hook.reset()


def test_hook_shard_step_publishes_report(analyze_env, hvd8):
    opt, params, state, grads = _opt_fixture()

    def local_step(p, s, xb):
        def loss_fn(p_):
            return jnp.sum((xb @ p_["w"] + p_["b"]) ** 2)

        g = jax.grad(loss_fn)(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    _core._state.analysis_reports = []
    step = hvd.shard_step(local_step, in_specs=(P(), P(), P("hvd")),
                          out_specs=(P(), P()))
    xb = jnp.ones((N, 3), jnp.float32)
    p1, s1 = step(params, state, xb)
    p1, s1 = step(p1, s1, xb)  # second call: no re-analysis
    reports = hvd.core.analysis_reports()
    labels = [r.label for r in reports]
    assert labels == ["shard_step:local_step/3"]
    assert reports[0].ok(), [f.message for f in reports[0].findings]
    assert reports[0].census["psum"]["count"] == 2
    # And training actually trained: params moved.
    assert not np.allclose(np.asarray(p1["w"]), np.asarray(params["w"]))


def test_hook_eager_optimizer_publishes_census(analyze_env, hvd8):
    _core._state.analysis_reports = []
    opt, params, state, grads = _opt_fixture()
    updates, _ = opt.update(grads, state, params)  # eager dispatch
    reports = hvd.core.analysis_reports()
    assert len(reports) == 1
    assert reports[0].label.startswith("DistributedOptimizer:")
    assert reports[0].ok(), [f.message for f in reports[0].findings]
    # Census of the in-trace-equivalent reduction: one psum per leaf.
    assert reports[0].census["psum"]["count"] == 2
    assert reports[0].census["psum"]["bytes"] == 32
    # The hook must not alter the update's structure/results.
    assert jax.tree_util.tree_structure(updates) == \
        jax.tree_util.tree_structure(grads)
    # Analyzed once per optimizer instance: a second update is silent.
    opt.update(grads, state, params)
    assert len(hvd.core.analysis_reports()) == 1


def test_hook_never_crashes_training_on_untraceable_step(analyze_env, hvd8,
                                                         caplog):
    """Loud-but-graceful: a step that cannot be re-traced by the checker
    still runs; the failure lands in analysis_reports as HVD100."""
    _core._state.analysis_reports = []
    calls = {"n": 0}

    def flaky(x):
        # Raises only on the checker's trace (which runs FIRST, before the
        # real jit compile): the hook must swallow that and keep training.
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("refuses the analysis trace")
        return x * 2.0

    step = hvd.shard_step(flaky, in_specs=(P("hvd"),),
                          out_specs=P("hvd"))
    out = step(jnp.ones((N,)))  # must not raise
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(N))
    reports = hvd.core.analysis_reports()
    assert len(reports) == 1
    assert [f.rule for f in reports[0].findings] == ["HVD100"]
    assert "refuses the analysis trace" in reports[0].findings[0].message


def test_hook_analyzes_same_named_distinct_steps(analyze_env, hvd8):
    """Two different step fns sharing a name+arity each get their own
    analysis (review regression: name-keyed dedup skipped the second)."""
    _core._state.analysis_reports = []

    def make(scale):
        def step(x):  # same __name__ 'step' for both instances
            return jax.lax.psum(x * scale, "hvd")
        return hvd.shard_step(step, in_specs=(P("hvd"),),
                              out_specs=P("hvd"))

    s1, s2 = make(1.0), make(2.0)
    s1(jnp.ones((N,)))
    s2(jnp.ones((N,)))
    assert len(hvd.core.analysis_reports()) == 2


def test_hook_analyzes_every_optimizer_instance(analyze_env, hvd8):
    """Each DistributedOptimizer instance is checked (review regression:
    id()-keyed dedup could skip a later instance)."""
    _core._state.analysis_reports = []
    for _ in range(2):
        opt, params, state, grads = _opt_fixture()
        opt.update(grads, state, params)
    labels = [r.label for r in hvd.core.analysis_reports()]
    assert len(labels) == 2 and labels[0] != labels[1]


def test_hook_disabled_is_inert(monkeypatch, hvd8):
    monkeypatch.delenv("HVD_ANALYZE", raising=False)
    hook.reset()
    _core._state.analysis_reports = []
    opt, params, state, grads = _opt_fixture()
    opt.update(grads, state, params)
    assert hvd.core.analysis_reports() == []
