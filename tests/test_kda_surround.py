"""What surrounds Kimi Delta Attention's scan as one pass each way
(``parallel/kda_surround.py``) against the plain definitions: the short
convolution with SiLU and the L2 norm (``models/kimi_linear.py:
short_conv``, ``_unit``), the decay, the gated norm; values and every
gradient, float32 and bf16, over several row blocks so that the halo is
crossed; the model through the kernels against its job's plain reference;
the benchmark's reader of the three spans.  The Pallas kernels run under the
interpreter here; ``tests/test_tpu_compile.py`` compiles them for the chip."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kimi_linear
from horovod_tpu.models.sdar_moe import rms_norm
from horovod_tpu.parallel import kda_surround as surround

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
D, L2_EPS, RMS_EPS = 128, 1e-6, 1e-5
#: ``unit`` of the convolution's pass for q, k and v.
UNITS = {"q": D ** -0.5, "k": 1.0, "v": None}
#: (sequence, rows a block): one block, three, five of the smallest.
BLOCKS = [(64, 64), (192, 64), (80, 16)]
BLOCK_IDS = ["1block", "3blocks", "5blocks"]


@pytest.fixture()
def ran(monkeypatch):
    """``[(kernel, its first operand's block)]`` of the kernels a test
    launched, with blocks of at most 64 rows."""
    monkeypatch.setattr(surround, "ROWS", 64)
    launched = []
    call = surround._call
    monkeypatch.setattr(surround, "_call", lambda *a, **kw: (
        launched.append((a[1], kw["in_specs"][0].block_shape)),
        call(*a, **kw))[1])
    return launched


@pytest.fixture()
def no_kernel(monkeypatch):
    monkeypatch.setattr(surround, "_call", None)    # a kernel would raise


def close(got, want, rel):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


def within_a_rounding(got, want):
    """Half a unit in the last place of bf16 of the float32 result, and
    float32's own noise where a sum cancels."""
    assert got.dtype == jnp.bfloat16
    want = np.asarray(want, np.float64)
    gap = np.abs(np.asarray(got, np.float64) - want)
    assert (gap <= 2.0 ** -8 * np.abs(want) + 1e-5 * np.abs(want).max()).all()


def by_head(x, heads):
    return x.reshape(x.shape[0], heads, -1)


# -- the definitions ----------------------------------------------------------

def plain_conv(x, weight, heads, unit):
    a = kimi_linear.short_conv(x, weight)
    if unit is not None:
        a = (kimi_linear._unit(by_head(a, heads), L2_EPS) * unit).reshape(
            x.shape).astype(x.dtype)
    return a


def plain_decay(x, dt_bias, a_log, heads):
    g = -jnp.exp(a_log.astype(jnp.float32))[None, :, None] * by_head(
        jax.nn.softplus(x.astype(jnp.float32) + dt_bias), heads)
    return g.reshape(x.shape)


def plain_gated_norm(o, gate, weight, heads):
    normed = rms_norm(by_head(o.astype(jnp.float32), heads), weight, RMS_EPS)
    return (normed.reshape(o.shape) * jax.nn.sigmoid(
        gate.astype(jnp.float32))).astype(o.dtype)


def both_ways(fn, plain, args, dy):
    """``[(y, *gradients)]`` of a pass and of its definition."""
    found = []
    for f in (fn, plain):
        y, vjp = jax.vjp(f, *args)
        found.append((y,) + vjp(dy.astype(y.dtype)))
    return found


def conv_operands(seq, heads, dtype, seed=0, taps=4):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(seq, heads * D), dtype),
            jnp.asarray(0.5 * rng.randn(heads * D, taps), jnp.float32),
            jnp.asarray(rng.randn(seq, heads * D), dtype))


# -- the short convolution, SiLU, the L2 norm --------------------------------

@pytest.mark.parametrize("which", sorted(UNITS))
@pytest.mark.parametrize("seq,rows", BLOCKS, ids=BLOCK_IDS)
@pytest.mark.parametrize("heads", [2, 1, 3])
def test_the_convolutions_pass_equals_its_definition_in_float32(
        heads, seq, rows, which, ran):
    """Value, ``dx`` and ``dw`` over one block and over several: a block
    takes its first three rows' history from the block before it and its
    last three rows' gradients from the block after it; the first block
    starts from a zero history as ``short_conv`` does."""
    unit = UNITS[which]
    x, weight, dy = conv_operands(seq, heads, jnp.float32)
    (y, dx, dw), (want_y, want_dx, want_dw) = both_ways(
        lambda x, w: surround.short_conv_silu(x, w, heads, unit, L2_EPS),
        lambda x, w: plain_conv(x, w, heads, unit), (x, weight), dy)
    assert ran == [("hvd_kda_conv_fwd", (rows, D)),
                   ("hvd_kda_conv_bwd", (rows, D))]
    assert y.shape == x.shape and y.dtype == jnp.float32
    assert dw.shape == weight.shape
    close(y, want_y, 2e-6)
    close(dx, want_dx, 1e-5)
    close(dw, want_dw, 1e-5)


@pytest.mark.parametrize("taps", [1, 2, 9])
def test_a_convolution_of_any_width_up_to_the_halo(taps, ran):
    x, weight, dy = conv_operands(192, 1, jnp.float32, seed=3, taps=taps)
    (y, dx, dw), (want_y, want_dx, want_dw) = both_ways(
        lambda x, w: surround.short_conv_silu(x, w, 1, 1.0, L2_EPS),
        lambda x, w: plain_conv(x, w, 1, 1.0), (x, weight), dy)
    assert [kernel for kernel, _ in ran] == ["hvd_kda_conv_fwd",
                                             "hvd_kda_conv_bwd"]
    close(y, want_y, 2e-6)
    close(dx, want_dx, 1e-5)
    close(dw, want_dw, 1e-5)


@pytest.mark.parametrize("which", ["q", "k"])
def test_rows_where_a_head_is_all_zero_keep_the_eps_under_the_root(which,
                                                                   ran):
    """A head whose convolution sees nothing but zeros (eight rows of them,
    across a block's edge): the output is 0 and not NaN, and the gradient
    is ``dy unit / sqrt(eps)`` through the SiLU's slope of a half."""
    heads, seq = 2, 192
    x, weight, dy = conv_operands(seq, heads, jnp.float32, seed=4)
    x = x.at[60:68, :D].set(0.0)
    (y, dx, dw), (want_y, want_dx, want_dw) = both_ways(
        lambda x, w: surround.short_conv_silu(x, w, heads, UNITS[which],
                                              L2_EPS),
        lambda x, w: plain_conv(x, w, heads, UNITS[which]), (x, weight), dy)
    assert len(ran) == 2
    assert not np.asarray(y[63:68, :D]).any() and np.asarray(y[63:68, D:]).all()
    assert np.isfinite(np.asarray(dx)).all()
    assert float(jnp.abs(dx[60:68, :D]).max()) > 10 * float(
        jnp.abs(dx[:56]).max())
    close(y, want_y, 2e-6)
    close(dx, want_dx, 1e-5)
    close(dw, want_dw, 1e-5)


@pytest.mark.parametrize("which", sorted(UNITS))
def test_in_bf16_the_convolutions_pass_is_within_one_rounding_of_float32(
        which, ran):
    heads, seq = 2, 192
    x, weight, dy = conv_operands(seq, heads, jnp.bfloat16, seed=1)
    fn = lambda x, w: surround.short_conv_silu(x, w, heads, UNITS[which],
                                               L2_EPS)
    y, vjp = jax.vjp(fn, x, weight)
    dx, dw = vjp(dy)
    assert len(ran) == 2 and dw.dtype == jnp.float32
    f32 = lambda t: t.astype(jnp.float32)
    (_, (want_y, want_dx, want_dw)) = both_ways(
        fn, lambda x, w: plain_conv(x, w, heads, UNITS[which]),
        (f32(x), weight), f32(dy))
    within_a_rounding(y, want_y)
    within_a_rounding(dx, want_dx)
    close(dw, want_dw, 1e-5)


# -- the decay ---------------------------------------------------------------

def decay_operands(seq, heads, dtype, scale=1.0, shift=0.0, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(scale * rng.randn(seq, heads * D) + shift, dtype),
            jnp.asarray(rng.randn(heads * D), jnp.float32),
            jnp.asarray(np.log(rng.uniform(1.0, 16.0, heads)), jnp.float32),
            jnp.asarray(rng.randn(seq, heads * D), jnp.float32))


@pytest.mark.parametrize("seq,rows", BLOCKS, ids=BLOCK_IDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("hard", [False, True], ids=["seeded", "decay25"])
def test_the_decays_pass_equals_its_definition(hard, dtype, seq, rows, ran):
    """``g`` float32 whatever the operand, ``dx`` in the operand's dtype,
    ``d dt_bias`` and ``d A_log``; ``decay25``: pre-activations of 1.5 to 3
    under rates up to 16, a decay of 25 a step and more sustained (PR 37's
    hard case), and pre-activations of -30, whose softplus underflows to
    the ``exp`` it is."""
    heads = 2
    x, dt_bias, a_log, dg = decay_operands(
        seq, heads, dtype, *((0.3, 2.0) if hard else (1.0, 0.0)))
    if hard:
        a_log = jnp.full_like(a_log, np.log(16.0))
        x = x.at[:, :8].set(-30.0)
    fn = lambda *a: surround.decay(*a, heads)
    g, vjp = jax.vjp(fn, x, dt_bias, a_log)
    dx, dbias, da = vjp(dg)
    assert ran == [("hvd_kda_decay_fwd", (rows, D)),
                   ("hvd_kda_decay_bwd", (rows, D))]
    assert g.dtype == jnp.float32 and dx.dtype == dtype
    assert dbias.shape == dt_bias.shape and da.shape == a_log.shape
    assert float(g.max()) <= 0.0
    if hard:
        assert float(g[:, D:].mean()) < -25.0
        assert 0.0 < float(-g[:, :8].max()) < 1e-10
    (_, (want_g, want_dx, want_dbias, want_da)) = both_ways(
        fn, lambda *a: plain_decay(*a, heads),
        (x.astype(jnp.float32), dt_bias, a_log), dg)
    close(g, want_g, 2e-6)
    if dtype == jnp.float32:
        close(dx, want_dx, 1e-5)
    else:
        within_a_rounding(dx, want_dx)
    close(dbias, want_dbias, 1e-5)
    close(da, want_da, 1e-5)


# -- the gated norm ----------------------------------------------------------

def out_operands(seq, heads, dtype, seed=0):
    rng = np.random.RandomState(seed)
    wide = lambda scale: jnp.asarray(scale * rng.randn(seq, heads * D), dtype)
    return (wide(0.1), wide(2.0),
            jnp.asarray(1 + 0.2 * rng.randn(D), jnp.float32), wide(1.0))


@pytest.mark.parametrize("seq,rows", BLOCKS, ids=BLOCK_IDS)
@pytest.mark.parametrize("heads", [2, 1, 3])
def test_the_gated_norms_pass_equals_its_definition_in_float32(heads, seq,
                                                               rows, ran):
    o, gate, weight, dy = out_operands(seq, heads, jnp.float32)
    o = o.at[5, :D].set(0.0)            # a head the scan wrote nothing to
    (y, do, dgate, dw), want = both_ways(
        lambda *a: surround.gated_norm(*a, heads, RMS_EPS),
        lambda *a: plain_gated_norm(*a, heads), (o, gate, weight), dy)
    assert ran == [("hvd_kda_out_fwd", (rows, D)),
                   ("hvd_kda_out_bwd", (rows, D))]
    assert y.shape == o.shape and dw.shape == weight.shape
    assert not np.asarray(y[5, :D]).any()
    close(y, want[0], 2e-6)
    for got, wanted in zip((do, dgate, dw), want[1:]):
        close(got, wanted, 1e-5)


def test_in_bf16_the_gated_norms_pass_is_within_one_rounding_of_float32(ran):
    heads = 2
    o, gate, weight, dy = out_operands(192, heads, jnp.bfloat16, seed=1)
    fn = lambda *a: surround.gated_norm(*a, heads, RMS_EPS)
    y, vjp = jax.vjp(fn, o, gate, weight)
    do, dgate, dw = vjp(dy)
    assert len(ran) == 2 and dw.dtype == jnp.float32
    f32 = lambda t: t.astype(jnp.float32)
    (_, (want_y, want_do, want_dgate, want_dw)) = both_ways(
        fn, lambda *a: plain_gated_norm(*a, heads),
        (f32(o), f32(gate), weight), f32(dy))
    within_a_rounding(y, want_y)
    within_a_rounding(do, want_do)
    within_a_rounding(dgate, want_dgate)
    close(dw, want_dw, 1e-5)


# -- where the kernels do not run --------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("seq,d", [(64, 64), (64, 256), (520, 128),
                                   (36, 128)],
                         ids=["head64", "head256", "520rows", "36rows"])
def test_shapes_the_kernels_refuse_take_the_same_formulas_in_jax_numpy(
        seq, d, dtype, no_kernel):
    """A head that is not the 128 lanes, or a sequence that is no multiple
    of 16 rows: no kernel, the same numbers (float32 inside, one rounding
    at the end)."""
    heads = 2
    rng = np.random.RandomState(7)
    wide = lambda: jnp.asarray(rng.randn(seq, heads * d), dtype)
    rel = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    f32 = lambda t: t.astype(jnp.float32) if t.ndim == 2 else t

    def held(fn, plain, args):
        dy = wide()
        got = both_ways(fn, fn, args, dy)[0]
        want = both_ways(plain, plain, tuple(map(f32, args)), f32(dy))[0]
        assert got[0].dtype == want[0].astype(got[0].dtype).dtype
        for a, b in zip(got, want):
            close(a, b, rel)

    weight = jnp.asarray(0.5 * rng.randn(heads * d, 4), jnp.float32)
    for unit in (d ** -0.5, None):
        held(lambda x, w: surround.short_conv_silu(x, w, heads, unit, L2_EPS),
             lambda x, w: (kimi_linear._unit(by_head(kimi_linear.short_conv(
                 x, w), heads), L2_EPS) * unit).reshape(x.shape)
             if unit else kimi_linear.short_conv(x, w), (wide(), weight))
    dt_bias = jnp.asarray(rng.randn(heads * d), jnp.float32)
    a_log = jnp.asarray(rng.rand(heads), jnp.float32)
    g = surround.decay(wide(), dt_bias, a_log, heads)
    assert g.dtype == jnp.float32
    x = wide()
    close(jax.grad(lambda *a: surround.decay(*a, heads).sum(), argnums=2)(
        x, dt_bias, a_log), jax.grad(lambda *a: plain_decay(*a, heads).sum(),
                                     argnums=2)(f32(x), dt_bias, a_log), rel)
    norm = jnp.asarray(1 + 0.2 * rng.randn(d), jnp.float32)
    held(lambda *a: surround.gated_norm(*a, heads, RMS_EPS),
         lambda *a: plain_gated_norm(*a, heads), (wide(), wide(), norm))


def test_a_convolution_wider_than_the_halo_takes_jax_numpy(no_kernel):
    x, weight, dy = conv_operands(64, 1, jnp.float32, taps=10)
    (y, dx, dw), want = both_ways(
        lambda x, w: surround.short_conv_silu(x, w, 1, None),
        kimi_linear.short_conv, (x, weight), dy)
    for got, wanted in zip((y, dx, dw), want):
        close(got, wanted, 1e-5)


# -- the model through the passes --------------------------------------------

@pytest.fixture(scope="module")
def job(bench_job):
    return bench_job("kimi_linear")


@pytest.fixture(scope="module")
def wide_heads():
    """The rehearsal configuration with KDA's published head size, so that
    its two KDA layers take the kernels (two heads of 128)."""
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "kimi-tiny.json")) as f:
        config = json.load(f)
    config["linear_attn_config"] = dict(config["linear_attn_config"],
                                        head_dim=D)
    return config


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_kda_operands_keep_their_shapes_and_dtypes(job, wide_heads, dtype,
                                                   ran):
    cfg = dataclasses.replace(job.model_config(wide_heads), dtype=dtype)
    run = jax.tree.map(lambda leaf: leaf[0],
                       job.seeded_params(wide_heads, 11)["runs"][0])
    seq, heads = 64, cfg.kda_num_heads
    a = jnp.asarray(np.random.RandomState(0).randn(seq, cfg.hidden_size),
                    dtype)
    q, k, v, g, beta, gate = kimi_linear.kda_operands(cfg, a, run)
    assert [kernel for kernel, _ in ran] == ["hvd_kda_conv_fwd"] * 3 + [
        "hvd_kda_decay_fwd"]
    assert q.shape == k.shape == v.shape == g.shape == (seq, heads, D)
    assert q.dtype == k.dtype == v.dtype == dtype
    assert g.dtype == beta.dtype == jnp.float32
    assert beta.shape == (seq, heads)
    assert gate.shape == (seq, heads * D) and gate.dtype == dtype
    f32 = lambda t: np.asarray(t, np.float32)
    np.testing.assert_allclose(np.linalg.norm(f32(k), axis=-1), 1.0,
                               atol=1e-2)
    np.testing.assert_allclose(np.linalg.norm(f32(q), axis=-1), D ** -0.5,
                               atol=1e-3)
    assert float(g.max()) < 0.0


def test_the_model_through_the_kernels_equals_its_reference(job, wide_heads,
                                                            ran):
    """Loss and every gradient leaf of the rehearsal model with heads of
    128 (the convolutions' weights, ``dt_bias``, ``A_log`` and ``o_norm``
    among them) against the job's plain float32 reference, which has its
    own convolution, norm and gates."""
    rng = np.random.RandomState(2)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * jnp.asarray(
            1 + 0.2 * rng.randn(*leaf.shape), leaf.dtype)
        if path[-1].key == "o_norm" else leaf,
        job.seeded_params(wide_heads, 11))
    batch = job.seeded_batch(wide_heads, 11, 1)
    cfg = job.model_config(wide_heads)
    (loss, _), grads = jax.value_and_grad(
        lambda p: kimi_linear.loss_fn(p, *batch, cfg), has_aux=True)(params)
    want_loss, want = jax.value_and_grad(
        lambda p: job.reference_loss(wide_heads, p, *batch))(params)
    assert {kernel for kernel, _ in ran} == {
        "hvd_kda_conv_fwd", "hvd_kda_conv_bwd", "hvd_kda_decay_fwd",
        "hvd_kda_decay_bwd", "hvd_kda_out_fwd", "hvd_kda_out_bwd"}
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    for (path, got), w in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            got, w, rtol=2e-3, atol=2e-5 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


# -- the benchmark's reader ---------------------------------------------------

def test_kda_surround_share_has_its_file_and_its_entry_and_sums_the_spans(
        monkeypatch):
    """``kda_surround_share.train``: one reader file, one appended entry
    for the Kimi-Linear cell; on a hand-built trace the time under
    ``hvd::kda_attention::conv``, ``::gates`` and ``::out`` (siblings: no
    time counted twice; kernels or plain fusions alike, in every pass)
    over all operations' time; the spans that are written where one is
    not; ``None`` where none is, and without a device trace."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [m for m in manifest["per_layer"]
            if m["name"] == "kda_surround_share.train"] == [{
        "name": "kda_surround_share.train", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_samples_per_s",
        "workloads": ["kimi-linear-train-8k"]}]
    assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                       "kda_surround_share.train.py"))
    monkeypatch.syspath_prepend(BENCH)
    from harness import manifest as mf
    from harness import scope_times
    read = mf.load_module("layer_metrics", "kda_surround_share.train").read
    step = "jit(local_step)/shard_map/decoder/hvd::layer_loop/while/body/"
    kda = step + "checkpoint/hvd::kda_attention/"
    names = {
        "custom-call.1": kda + "hvd::kda_attention::conv/hvd_kda_conv_fwd/"
        "pallas_call",
        "custom-call.2": kda.replace("decoder", "transpose(jvp(decoder))")
        + "hvd::kda_attention::conv/hvd_kda_conv_bwd/pallas_call",
        "fusion.3": kda.replace("checkpoint", "rematted_computation")
        + "hvd::kda_attention::gates/logistic",
        "custom-call.4": kda + "hvd::kda_attention::out/hvd_kda_out_fwd/"
        "pallas_call",
        "fusion.5": kda + "hvd::kda_attention::out/dot_general",
        "custom-call.6": kda + "hvd::kda_attention::scan/hvd_kda_fwd/"
        "pallas_call",
        "fusion.7": kda + "hvd::kda_attention::project/dot_general",
        "while.8": step.rstrip("/body/")}
    codes = {name: {"custom-call": "custom-call", "fusion": "fusion",
                    "while": "while"}[name.split(".")[0]] for name in names}
    event = "%{0} = f32[8]{{0}} op(%x)".format
    durations = {"custom-call.1": 30, "custom-call.2": 50, "fusion.3": 20,
                 "custom-call.4": 15, "fusion.5": 35, "custom-call.6": 250,
                 "fusion.7": 100, "while.8": 500}
    devices = {"/device:TPU:0": {
        "ops": [(event(name), 0, ns) for name, ns in durations.items()],
        "modules": [("jit_local_step(5)", 0, 1000)]}}

    def run_of(names):
        return types.SimpleNamespace(scopes={"scope_times": scope_times.reduce(
            devices, names, codes, scope_times.KERNELS)})

    run = run_of(names)
    assert read(run) == pytest.approx(100.0 * (30 + 50 + 20 + 15 + 35) / 500)
    assert scope_times.share_under(run, "hvd::kda_attention") == 100.0
    without = lambda span: {k: v.replace(span + "/", "")
                            for k, v in names.items()}
    assert read(run_of(without("hvd::kda_attention::gates"))) == \
        pytest.approx(100.0 * (30 + 50 + 15 + 35) / 500)
    nothing = {k: v for k, v in names.items() if k in ("custom-call.6",
                                                       "fusion.7", "while.8")}
    assert read(run_of(nothing)) is None
    assert read(types.SimpleNamespace(scopes={}, results={})) is None
