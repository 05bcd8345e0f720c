"""QK-norm and rotary embedding as one pass (``parallel/qk_rope.py``)
against the separate passes it replaces, ``rotary(rms_norm(...))`` of
``models/sdar_moe.py`` transposed head-major; the head-major way into the
flash kernels against ``flash_attention``; both decoders through the
kernels against their jobs' plain references.  The Pallas kernels run under
the interpreter here; ``tests/test_tpu_compile.py`` compiles them for the
chip."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import afmoe, sdar_moe
from horovod_tpu.parallel import flash, qk_rope
from horovod_tpu.parallel.qk_rope import qk_norm_rope, rope_tables

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
EPS, THETA, LENGTH = 1e-6, 1e6, 384   # LENGTH: ``positions % L``'s L
KINDS = ("plain", "modulo", "none")


def positions_of(kind, seq):
    plain = jnp.arange(seq, dtype=jnp.int32)
    return {"plain": plain, "modulo": plain % LENGTH, "none": None}[kind]


def separate_passes(x, weight, positions, heads):
    """What the models did before the pass: ``[heads, S, head_dim]``."""
    seq = x.shape[0]
    n = sdar_moe.rms_norm(x.reshape(1, seq, heads, -1), weight, EPS)
    if positions is not None:
        n = sdar_moe.rotary(n, positions, THETA)
    return n[0].transpose(1, 0, 2)


def the_pass(x, weight, positions, heads):
    head_dim = x.shape[1] // heads
    return qk_norm_rope(x, weight, None if positions is None else
                        rope_tables(positions, head_dim, THETA), heads, EPS)


def operands(seq, heads, head_dim, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(seq, heads * head_dim), dtype),
            jnp.asarray(1 + 0.2 * rng.randn(head_dim), jnp.float32),
            jnp.asarray(rng.randn(heads, seq, head_dim), dtype))


def both_ways(x, weight, dy, positions, heads):
    """``[(y, dx, dweight)]`` of the pass and of the separate passes."""
    found = []
    for fn in (the_pass, separate_passes):
        y, vjp = jax.vjp(lambda x, w: fn(x, w, positions, heads), x, weight)
        found.append((y,) + vjp(dy))
    return found


def close(got, want, rel):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("seq,rows", [(512, 512), (1536, 512), (80, 16)],
                         ids=["1block", "3blocks", "5blocks"])
@pytest.mark.parametrize("heads", [32, 4, 1])
def test_the_pass_equals_the_separate_passes_in_float32(heads, seq, rows,
                                                        kind, monkeypatch):
    ran = []
    call = qk_rope._call
    monkeypatch.setattr(qk_rope, "_call", lambda *a, **kw: (
        ran.append((a[1], kw["in_specs"][0].block_shape)), call(*a, **kw))[1])
    x, weight, dy = operands(seq, heads, 128, jnp.float32)
    (y, dx, dw), (want_y, want_dx, want_dw) = both_ways(
        x, weight, dy, positions_of(kind, seq), heads)
    # A block is the largest power of two up to ``ROWS`` that divides S.
    assert ran == [("hvd_qk_rope_fwd", (rows, 128)),
                   ("hvd_qk_rope_bwd", (rows, 128))]
    assert y.shape == (heads, seq, 128) and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=0, atol=1e-6 * float(
        jnp.abs(want_y).max()))
    close(dx, want_dx, 1e-4)
    close(dw, want_dw, 1e-4)


def test_a_long_sequence_takes_blocks_of_the_modules_rows(monkeypatch):
    ran = []
    call = qk_rope._call
    monkeypatch.setattr(qk_rope, "_call", lambda *a, **kw: (
        ran.append(kw["in_specs"][0].block_shape), call(*a, **kw))[1])
    seq = 2 * qk_rope.ROWS
    x, weight, dy = operands(seq, 2, 128, jnp.float32)
    (y, dx, dw), (want_y, want_dx, want_dw) = both_ways(
        x, weight, dy, positions_of("modulo", seq), 2)
    assert ran == [(qk_rope.ROWS, 128)] * 2
    close(y, want_y, 1e-6)
    close(dx, want_dx, 1e-4)
    close(dw, want_dw, 1e-4)


@pytest.mark.parametrize("kind", KINDS)
def test_in_bf16_the_pass_is_within_one_rounding_of_float32(kind):
    """One rounding at the store (the separate passes round twice): the
    output and ``dx`` within a unit in the last place of bf16 of the
    float32 result from the same bf16 operands, ``dweight`` float32."""
    heads, seq = 4, 1024
    x, weight, dy = operands(seq, heads, 128, jnp.bfloat16)
    positions = positions_of(kind, seq)
    y, vjp = jax.vjp(lambda x, w: the_pass(x, w, positions, heads), x,
                     weight)
    dx, dw = vjp(dy)
    assert y.dtype == dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    (_, (want_y, want_dx, want_dw)) = both_ways(
        x.astype(jnp.float32), weight, dy.astype(jnp.float32), positions,
        heads)
    # Half a unit in the last place of bf16, and float32's own noise where
    # a sum cancels.
    for got, want in ((y, want_y), (dx, want_dx)):
        want = np.asarray(want, np.float64)
        gap = np.abs(np.asarray(got, np.float64) - want)
        assert (gap <= 2.0 ** -8 * np.abs(want)
                + 1e-5 * np.abs(want).max()).all()
    close(dw, want_dw, 1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("seq,head_dim", [(64, 64), (520, 128), (36, 128)],
                         ids=["head64", "520rows", "36rows"])
@pytest.mark.parametrize("kind", ["modulo", "none"])
def test_small_heads_and_ragged_sequences_take_the_separate_passes(
        kind, seq, head_dim, dtype, monkeypatch):
    """A head that is no multiple of the 128 lanes, or a sequence that is
    no multiple of 16 rows: no kernel, the separate passes' own arithmetic
    and roundings."""
    monkeypatch.setattr(qk_rope, "_call", None)     # a kernel would raise
    x, weight, dy = operands(seq, 4, head_dim, dtype, seed=1)
    (y, dx, dw), (want_y, want_dx, want_dw) = both_ways(
        x, weight, dy, positions_of(kind, seq), 4)
    assert y.dtype == dtype
    rel = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    close(y, want_y, 1e-6 if dtype == jnp.float32 else 2.0 ** -7)
    close(dx, want_dx, rel)
    close(dw, want_dw, rel)


def test_tables_are_the_half_split_rotation():
    cos, sin_signed = rope_tables(jnp.arange(40) % 16, 8, 1e4)
    assert cos.shape == sin_signed.shape == (40, 8)
    assert cos.dtype == sin_signed.dtype == jnp.float32
    np.testing.assert_array_equal(cos[:, :4], cos[:, 4:])
    np.testing.assert_array_equal(sin_signed[:, :4], -sin_signed[:, 4:])
    np.testing.assert_array_equal(cos[16:32], cos[:16])
    np.testing.assert_allclose(cos ** 2 + sin_signed ** 2, 1.0, atol=1e-6)
    assert float(sin_signed[1, 4]) == pytest.approx(np.sin(1.0))


MASKS = {"none": flash.MASK_NONE, "causal": flash.MASK_CAUSAL,
         "strict": flash.MASK_STRICT, "window": flash.window_mask(24),
         "block_diffusion": flash.block_diffusion_mask(4, 32)}


@pytest.mark.parametrize("mask", sorted(MASKS))
def test_heads_first_entry_is_flash_attention_without_its_transposes(mask):
    """Output and the three gradients through ``flash_attention_heads_
    first`` on head-major operands, bit for bit those through
    ``flash_attention``."""
    rng = np.random.RandomState(3)
    q, k, v, c = (jnp.asarray(rng.randn(2, 64, h, d), jnp.float32)
                  for h, d in ((4, 16), (2, 16), (2, 8), (4, 8)))
    first = lambda a: a.transpose(0, 2, 1, 3).reshape(-1, *a.shape[1::2])
    kwargs = dict(mask_mode=MASKS[mask], block_q=16, block_k=16)
    out, vjp = jax.vjp(lambda *a: flash.flash_attention(*a, **kwargs),
                       q, k, v)
    got, got_vjp = jax.vjp(lambda *a: flash.flash_attention_heads_first(
        *a, **kwargs), first(q), first(k), first(v))
    np.testing.assert_array_equal(got, first(out))
    for g, w in zip(got_vjp(first(c)), vjp(c)):
        np.testing.assert_array_equal(g, first(w))


def test_heads_first_entry_checks_its_shapes():
    q, k = jnp.zeros((8, 64, 16)), jnp.zeros((3, 64, 16))
    with pytest.raises(ValueError, match="divide"):
        flash.flash_attention_heads_first(q, k, k)
    with pytest.raises(ValueError, match="divisible"):
        flash.flash_attention_heads_first(q[:, :50], q[:, :50], q[:, :50],
                                          block_q=32, block_k=32)
    with pytest.raises(ValueError, match="batches"):
        flash.flash_attention(jnp.zeros((2, 64, 4, 16)),
                              *[jnp.zeros((1, 64, 4, 16))] * 2)


def load_job(name):
    sys.path.insert(0, BENCH)       # a job finds ``harness`` by name
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_jobs_" + name, os.path.join(BENCH, "jobs", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


@pytest.mark.parametrize("name,model,tiny", [
    ("sdar_moe", sdar_moe, "sdar-tiny"), ("afmoe", afmoe, "trinity-tiny")])
def test_a_decoder_with_heads_of_128_runs_the_kernels_and_equals_its_reference(
        name, model, tiny, monkeypatch):
    """The rehearsal configuration with the published head size: the
    model's loss and gradients go through the two kernels (under
    ``positions % L`` for block diffusion; with and without rotation for
    the window and the full layers) and equal the job's plain float32
    reference, the head norms' weights off their seeded ones."""
    job = load_job(name)
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           tiny + ".json")) as f:
        config = dict(json.load(f), head_dim=128)
    ran = []
    call = qk_rope._call
    monkeypatch.setattr(qk_rope, "_call", lambda *a, **kw: (
        ran.append((a[1], len(kw["in_specs"]))), call(*a, **kw))[1])
    rng = np.random.RandomState(2)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * jnp.asarray(
            1 + 0.2 * rng.randn(*leaf.shape), leaf.dtype)
        if path[-1].key in ("q_norm", "k_norm") else leaf,
        job.seeded_params(config, 11))
    batch = job.seeded_batch(config, 11, 2)
    cfg = job.model_config(config)
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss_fn(p, *batch, cfg), has_aux=True)(params)
    want_loss, want = jax.value_and_grad(
        lambda p: job.reference_loss(config, p, *batch))(params)
    kernels = {kernel for kernel, _ in ran}
    assert kernels == {"hvd_qk_rope_fwd", "hvd_qk_rope_bwd"}
    # x, the weight and the two tables; a full layer's call has no tables.
    assert {n for kernel, n in ran if kernel == "hvd_qk_rope_fwd"} == (
        {4} if name == "sdar_moe" else {2, 4})
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    for (path, got), w in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            got, w, rtol=2e-3, atol=2e-5 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


CELLS = ["sdar30b-train-blockdiff-4k", "trinity-mini-train-8k"]


def test_qk_rope_share_has_its_file_and_its_entry_and_reads_the_span(
        monkeypatch):
    """``qk_rope_share.train``: one reader file, one appended entry that
    lists exactly the two cells whose decoders call the pass; on a
    hand-built trace the time under ``hvd::qk_rope`` (its two kernels and
    what else sits under the span, in every pass) over all operations'
    time; ``None`` where the program writes no such span (the parent) and
    without a device trace."""
    import types
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = [m for m in manifest["per_layer"]
               if m["name"] == "qk_rope_share.train"]
    assert entries == [{
        "name": "qk_rope_share.train", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_samples_per_s", "workloads": CELLS}]
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       "qk_rope_share.train.py"))
    monkeypatch.syspath_prepend(BENCH)
    from harness import manifest as mf
    from harness import scope_times
    read = mf.load_module("layer_metrics", "qk_rope_share.train").read
    step = "jit(local_step)/shard_map/decoder/hvd::layer_loop/while/body/"
    span = step + "checkpoint/hvd::window_attention/hvd::qk_rope/"
    names = {
        "custom-call.1": span + "hvd_qk_rope_fwd/pallas_call",
        "custom-call.2": span.replace("decoder", "transpose(jvp(decoder))")
        + "hvd_qk_rope_bwd/pallas_call",
        "fusion.3": span.replace("checkpoint", "rematted_computation")
        + "reduce_sum",
        "custom-call.4": step + "hvd::window_attention/hvd_flash_fwd/"
        "pallas_call",
        "while.5": step.rstrip("/body/")}
    codes = {"custom-call.1": "custom-call", "custom-call.2": "custom-call",
             "fusion.3": "fusion", "custom-call.4": "custom-call",
             "while.5": "while"}
    event = "%{0} = f32[8]{{0}} op(%x)".format
    durations = {"custom-call.1": 30, "custom-call.2": 50, "fusion.3": 20,
                 "custom-call.4": 300, "while.5": 400}
    devices = {"/device:TPU:0": {
        "ops": [(event(name), 0, ns) for name, ns in durations.items()],
        "modules": [("jit_local_step(5)", 0, 1000)]}}
    table = scope_times.reduce(devices, names, codes, scope_times.KERNELS)
    run = types.SimpleNamespace(scopes={"scope_times": table})
    assert read(run) == pytest.approx(100.0 * (30 + 50 + 20) / 400)
    assert scope_times.share_under(run, "hvd::window_attention") == 100.0
    parent = scope_times.reduce(
        devices, {k: v.replace("hvd::qk_rope/", "") for k, v in
                  names.items()}, codes, scope_times.KERNELS)
    assert read(types.SimpleNamespace(scopes={"scope_times": parent})) is None
    assert read(types.SimpleNamespace(scopes={}, results={})) is None
