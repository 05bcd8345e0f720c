"""Double-buffered ring schedule (ISSUE 1): the overlapped schedule must
match the serial schedule — forward and all three gradients, every mask
mode, f32 and bf16 — the contiguous-causal skip branch must provably never
invoke the flash kernel, the double-buffered ``_ring_reduce`` must stay
exact, and the per-hop timeline events must land in the trace."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd  # noqa: F401  (installs jax API shims)
from horovod_tpu.parallel import ring as ring_mod
from horovod_tpu.parallel.ring import (ring_attention, ring_flash_attention,
                                       stripe_sequence)

N = 8
MASK_MODES = [(False, False), (True, False), (True, True)]  # (causal, striped)


def _qkv(seed, B=2, S=64, H=4, D=16, dtype=np.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(dtype) * 0.3)
    return mk(), mk(), mk()


def _runner(hvd_mod, fn, causal, striped, schedule, **kw):
    """fwd + (dq, dk, dv) for the given ring fn/config, sharded over hvd."""
    def run(q, k, v):
        def loss(q, k, v):
            return jnp.mean(fn(q, k, v, axis_name="hvd", causal=causal,
                               striped=striped, schedule=schedule, **kw) ** 2)
        return (fn(q, k, v, axis_name="hvd", causal=causal, striped=striped,
                   schedule=schedule, **kw),
                *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    return jax.jit(jax.shard_map(
        run, mesh=hvd_mod.mesh(), in_specs=(P(None, "hvd"),) * 3,
        out_specs=(P(None, "hvd"),) * 4, check_vma=False))


@pytest.mark.parametrize("causal,striped", MASK_MODES)
def test_ring_attention_overlap_matches_serial(hvd8, causal, striped):
    """Double-buffered overlap (+ true skip on contiguous-causal hops) vs
    the legacy serial schedule: same fold order, same values — forward and
    all three gradients within the existing ring test tolerances."""
    q, k, v = _qkv(0)
    if striped:
        q, k, v = (stripe_sequence(t, N) for t in (q, k, v))
    serial = _runner(hvd8, ring_attention, causal, striped, "serial")(q, k, v)
    overlap = _runner(hvd8, ring_attention, causal, striped,
                      "overlap")(q, k, v)
    for a, b in zip(serial, overlap):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize(
    "causal,striped",
    [(False, False),
     # causal flash variants ~20s each on the tier-1 box: nightly tier
     pytest.param(True, False, marks=pytest.mark.slow),
     pytest.param(True, True, marks=pytest.mark.slow)])
def test_ring_flash_overlap_matches_serial(hvd8, causal, striped):
    q, k, v = _qkv(1, S=128, H=2)
    if striped:
        q, k, v = (stripe_sequence(t, N) for t in (q, k, v))
    serial = _runner(hvd8, ring_flash_attention, causal, striped,
                     "serial")(q, k, v)
    overlap = _runner(hvd8, ring_flash_attention, causal, striped,
                      "overlap")(q, k, v)
    for a, b in zip(serial, overlap):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize(
    "fn",
    [ring_attention,
     # flash bf16 variant ~20s on the tier-1 box: nightly tier
     pytest.param(ring_flash_attention, marks=pytest.mark.slow)],
    ids=["ring", "ring_flash"])
def test_overlap_matches_serial_bf16(hvd8, fn):
    """bf16 inputs ride the same f32 carries in both schedules."""
    q, k, v = _qkv(2, S=128, H=2, dtype=np.float32)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    serial = _runner(hvd8, fn, True, False, "serial")(qb, kb, vb)
    overlap = _runner(hvd8, fn, True, False, "overlap")(qb, kb, vb)
    assert overlap[0].dtype == jnp.bfloat16
    for a, b in zip(serial, overlap):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_invalid_schedule_rejected(hvd8):
    q, k, v = _qkv(3)
    with pytest.raises(ValueError, match="schedule"):
        _runner(hvd8, ring_attention, True, False, "eager")(q, k, v)


def test_contiguous_causal_skip_never_invokes_kernel(hvd8):
    """The acceptance proof for the true-skip arm: count RUNTIME flash
    kernel executions via the ring kernel callback (jax.debug.callback
    fires only inside the branch lax.switch actually runs).  Contiguous
    causal on n shards has sum(my+1) = n(n+1)/2 attended hops; the serial
    schedule runs a (masked, discarded) kernel on every hop = n^2."""
    q, k, v = _qkv(4, S=128, H=2)
    counts = []
    ring_mod.set_ring_kernel_callback(lambda mode: counts.append(mode))
    try:
        def build(schedule):
            def run(q, k, v):
                return ring_flash_attention(q, k, v, axis_name="hvd",
                                            causal=True, schedule=schedule)
            return jax.jit(jax.shard_map(
                run, mesh=hvd8.mesh(), in_specs=(P(None, "hvd"),) * 3,
                out_specs=P(None, "hvd"), check_vma=False))

        jax.block_until_ready(build("overlap")(q, k, v))
        jax.effects_barrier()
        assert len(counts) == N * (N + 1) // 2, len(counts)

        counts.clear()
        jax.block_until_ready(build("serial")(q, k, v))
        jax.effects_barrier()
        assert len(counts) == N * N, len(counts)
    finally:
        ring_mod.set_ring_kernel_callback(None)


def test_striped_single_row_strict_hops_skip(hvd8):
    """S_local == 1 is the one striped case where a strict hop is provably
    empty as a whole — the skip arm must replace the STRICT kernel: only
    owner <= my hops (n(n+1)/2 total) invoke a kernel."""
    q, k, v = _qkv(5, S=N, H=2, D=16)  # one row per shard
    qs, ks, vs = (stripe_sequence(t, N) for t in (q, k, v))
    counts = []
    ring_mod.set_ring_kernel_callback(lambda mode: counts.append(mode))
    try:
        run = jax.jit(jax.shard_map(
            lambda a, b, c: ring_flash_attention(
                a, b, c, axis_name="hvd", causal=True, striped=True),
            mesh=hvd8.mesh(), in_specs=(P(None, "hvd"),) * 3,
            out_specs=P(None, "hvd"), check_vma=False))
        jax.block_until_ready(run(qs, ks, vs))
        jax.effects_barrier()
        assert len(counts) == N * (N + 1) // 2, len(counts)
    finally:
        ring_mod.set_ring_kernel_callback(None)


def test_ring_reduce_double_buffered_product(hvd8):
    """The double-buffered _ring_reduce keeps PRODUCT allreduce exact and
    rank-identical (fold order unchanged, leader canonicalization)."""
    vals = np.asarray([1.5, -2.0, 0.5, 3.0, 1.25, -1.0, 2.0, 0.25],
                      np.float32)
    x = jnp.asarray(vals).reshape(N, 1)

    def f(x):
        return hvd.ops.collective_ops.allreduce(
            x, hvd.Product, axis_name="hvd")

    out = jax.jit(jax.shard_map(
        f, mesh=hvd8.mesh(), in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False))(x)
    arr = np.asarray(out).ravel()
    np.testing.assert_allclose(arr, np.full(N, np.prod(vals)), rtol=1e-6)
    assert len(set(arr.tolist())) == 1  # bitwise-identical on every rank


def test_timeline_records_hop_schedule(hvd8, tmp_path):
    """set_ring_timeline: tracing a ring collective emits one RING_HOP
    event per hop with bytes rotated, mask rule, schedule, and the
    skipped-shard count of the true-skip arm."""
    from horovod_tpu.timeline import Timeline
    path = str(tmp_path / "ring_tl.json")
    tl = Timeline(path)
    ring_mod.set_ring_timeline(tl, "tltest")
    try:
        q, k, v = _qkv(6)
        out = _runner(hvd8, ring_attention, True, False, "overlap")(q, k, v)
        jax.block_until_ready(out)
    finally:
        ring_mod.set_ring_timeline(None)
        tl.close()
    events = [e for e in json.load(open(path))
              if e.get("name", "").startswith("RING_HOP")]
    hops = {e["args"]["hop"]: e["args"] for e in events
            if e["tid"] == "tltest/ring_attention"}
    assert set(hops) == set(range(N))
    B, S, H, D = 2, 64 // N, 4, 16
    for hop, args in hops.items():
        assert args["bytes_rotated"] == 2 * B * S * H * D * 4
        assert args["mask"] == "causal-contiguous"
        assert args["schedule"] == "overlap"
        assert args["skipped_shards"] == (N - hop if hop else 0)


@pytest.mark.integration
@pytest.mark.slow  # ~7s bench smoke
def test_bench_ring_microbench_smoke():
    """bench.py BENCH_MODEL=ring end-to-end on the emulated 8-device CPU
    mesh: one JSON line with the overlapped step time, the serial/overlap
    ratio, the full variant matrix, and per-hop kernel/transfer spans."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_MODEL="ring", BENCH_SMOKE="1",
               HVD_TPU_EMULATE_RANKS="8",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("HOROVOD_TIMELINE", None)
    r = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    records = [json.loads(l) for l in r.stdout.splitlines()
               if l.strip().startswith("{")]
    last = records[-1]
    assert last["metric"] == "ring_sp_causal_ms_per_step"
    assert set(last["variants"]) == {
        "contiguous_causal_serial", "contiguous_causal_overlap",
        "striped_causal_overlap", "full_overlap"}
    assert last["per_hop"]["transfer_ms"] >= 0
    assert last["per_hop"]["kernel_ms"] > 0
    assert last["vs_baseline"] > 0
