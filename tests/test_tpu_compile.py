"""The Pallas kernels of the main path compile for the chip, and the
data-parallel step compiles to gradient all-reduces that run behind it.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: a block shape off the (8, 128) tiling, an unsupported
vector type, too much VMEM.  These tests ask that compiler directly — it
is installed with jaxlib and compiles for a chip that is *described*, not
attached — at the shapes ``chip_smoke.py`` runs on the v5e, with
``interpret=False``.  Nothing executes, so they say nothing about results
or times; ``chip_smoke.py`` checks the numerics on the chip.

The step test reads the compiled text of a small ``hvd.shard_step`` over
the described chips: what the wrapper's compiler options (``parallel/
__init__.py``) make of the gradients' all-reduces is a count in that text.

All of them live in this one file and describe the topology inside a
fixture: only one process at a time may load the TPU library, and under
pytest-xdist only the worker that is handed this file does.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import horovod_tpu as hvd
from horovod_tpu import parallel
from horovod_tpu.parallel.flash import (block_diffusion_mask,
                                        flash_attention, window_mask)
from horovod_tpu.parallel.grouped import gmm
from horovod_tpu.serve.paged_attention import (SCALE_DTYPE,
                                               paged_decode_attention,
                                               paged_prefill_attention)

# GPT-2 small serving geometry (12 heads of 64) and the BERT/GPT-2 medium
# training geometry (16 heads of 64), as chip_smoke.py's kernel phase.
FLASH_B, FLASH_H, FLASH_D = 8, 16, 64
NB, BT, H, DH = 256, 16, 12, 64
SERVE_B, SERVE_MB, PREFILL_C = 8, 16, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no log files in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel_compiles(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("seq", [128, 1024])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          backward, seq, causal):
    qkv = jax.ShapeDtypeStruct((FLASH_B, seq, FLASH_H, FLASH_D),
                               jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    _assert_kernel_compiles(fwd_bwd if backward else fwd, qkv, qkv, qkv)


# SDAR-30B-A3B-Chat's attention as the benchmark cell runs it: one sequence
# of 2 x 4,096 positions, 32 query heads on 4 key/value heads of 128, tiles
# of 512; and its expert products: a sequence's buffer of 16,384 rows
# through 16 experts of 2,048 x 768.
SDAR_L, SDAR_H, SDAR_HKV, SDAR_D, SDAR_TILE = 4096, 32, 4, 128, 512
SDAR_ROWS, SDAR_EXPERTS, SDAR_HIDDEN, SDAR_WIDTH = 16384, 16, 2048, 768
TRINITY_WIDTH = 1024             # moe_intermediate_size of models/afmoe.py


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_block_diffusion_flash_compiles_for_v5e(one_chip,
                                                no_persistent_cache,
                                                backward):
    """The block-diffusion mask and grouped key/value heads, forward and
    the dQ and dK/dV kernels, at the published head sizes."""
    def sds(heads):
        return jax.ShapeDtypeStruct((1, 2 * SDAR_L, heads, SDAR_D),
                                    jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(
            q, k, v, mask_mode=block_diffusion_mask(4, SDAR_L),
            block_q=SDAR_TILE, block_k=SDAR_TILE, interpret=False)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    fn = fwd_bwd if backward else fwd
    text = jax.jit(fn).lower(sds(SDAR_H), sds(SDAR_HKV),
                             sds(SDAR_HKV)).compile().as_text()
    kernels = [line for line in text.split("\n")
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(kernels) == (3 if backward else 1)
    # K and V go into every kernel as they are, 4 heads: no copy of them
    # at the 32 query heads' size is made.
    for line in kernels:
        assert line.count(f"bf16[{SDAR_HKV},{2 * SDAR_L},{SDAR_D}]") >= 2


@pytest.mark.parametrize("window", [2048, 8192], ids=["w2048", "w8192"])
def test_window_flash_compiles_for_v5e(one_chip, no_persistent_cache,
                                       window):
    """Trinity-Mini's attention as its cell runs it: one sequence of 8,192
    positions, 32 query heads on 4 key/value heads of 128, tiles of 512,
    forward and both backward kernels under the causal window (2,048, and
    the sequence's own length, which keeps what ``MASK_CAUSAL`` keeps)."""
    def sds(heads):
        return jax.ShapeDtypeStruct((1, 2 * SDAR_L, heads, SDAR_D),
                                    jnp.bfloat16, sharding=one_chip)

    text = jax.jit(jax.grad(
        lambda *a: flash_attention(
            *a, mask_mode=window_mask(window), block_q=SDAR_TILE,
            block_k=SDAR_TILE, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(
            sds(SDAR_H), sds(SDAR_HKV), sds(SDAR_HKV)).compile().as_text()
    kernels = [line for line in text.split("\n")
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(kernels) == 3
    # The tile list is an operand: 70 (or 136) steps a query head in the
    # forward and dQ kernels, eight heads' worth a key/value head in dK/dV.
    steps = 70 if window == 2048 else 136
    assert sum(f"s32[6,{steps}]" in line for line in kernels) == 2
    assert sum(f"s32[6,{8 * steps}]" in line for line in kernels) == 1


def test_latent_attention_flash_compiles_for_v5e(one_chip,
                                                 no_persistent_cache):
    """JoyAI-LLM-Flash's attention as its cell runs it: one sequence of
    8,192 positions, 32 heads with keys of 192 (128 and the shared rotary
    64) and values of 128, tiles of 512, forward and both backward kernels
    under the causal mask: blocks one and a half lane widths wide."""
    def sds(width):
        return jax.ShapeDtypeStruct((1, 2 * SDAR_L, SDAR_H, width),
                                    jnp.bfloat16, sharding=one_chip)

    text = jax.jit(jax.grad(
        lambda *a: flash_attention(
            *a, causal=True, block_q=SDAR_TILE, block_k=SDAR_TILE,
            interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(
            sds(192), sds(192), sds(SDAR_D)).compile().as_text()
    kernels = [line for line in text.split("\n")
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(kernels) == 3
    wide, narrow = (f"bf16[{SDAR_H},{2 * SDAR_L},{width}]"
                    for width in (192, SDAR_D))
    # Forward: q, k in at 192, v in and the output at 128; dQ: dO in at 128,
    # dQ out at 192; dK/dV: one of each out.
    assert sorted((line.count(wide), line.count(narrow))
                  for line in kernels) == [(2, 2), (3, 2), (3, 3)]


def test_kda_scan_kernels_compile_for_v5e(one_chip, no_persistent_cache):
    """The chunked scan of Kimi Delta Attention as the Kimi-Linear cell runs
    it: one sequence of 8,192 positions, 32 heads of 128, chunks of 64 in
    blocks of 512 rows, bf16 operands and float32 log-decays, forward and
    backward: the triangular system's float32 products, the cumulative sum
    at the highest precision, ``beta`` turned between rows and columns."""
    from horovod_tpu.parallel.kda import kda_scan
    shape = (2 * SDAR_L, SDAR_H, SDAR_D)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    wide = sds(shape, jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda *a: kda_scan(*a, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(
            wide, wide, wide, sds(shape, jnp.float32),
            sds(shape[:2], jnp.float32)).compile().as_text()
    kernels = [line for line in text.split("\n")
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(kernels) == 2
    # The forward kernel writes the state at the start of every chunk.
    assert sum(f"f32[{SDAR_H},{2 * SDAR_L // 64},{SDAR_D},{SDAR_D}]" in line
               for line in kernels) == 2
    assert (2 * SDAR_L, SDAR_H, SDAR_D) == (8192, 32, 128)


def test_kda_surround_passes_compile_for_v5e(one_chip, no_persistent_cache):
    """What surrounds the scan of Kimi Delta Attention as the Kimi-Linear
    cell runs it: one sequence of 8,192 positions, ``[S, 32 x 128]`` bf16,
    row blocks of ``kda_surround.ROWS`` with their halo blocks: the three
    convolutions with SiLU (two of them with the L2 norm), the decay in
    float32 and the gated norm, forward and backward: ten kernels, every
    one on ``[S, P]`` as the projections write it, no view by head."""
    from horovod_tpu.parallel import kda_surround as surround
    seq, heads, d = 2 * SDAR_L, SDAR_H, SDAR_D

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xq, xk, xv, xd, o, gate, wq, wk, wv, dt_bias, a_log, o_norm):
        made = [surround.short_conv_silu(x, w, heads, unit, 1e-6,
                                         interpret=False)
                for x, w, unit in ((xq, wq, d ** -0.5), (xk, wk, 1.0),
                                   (xv, wv, None))]
        made.append(surround.decay(xd, dt_bias, a_log, heads,
                                   interpret=False))
        made.append(surround.gated_norm(o, gate, o_norm, heads, 1e-5,
                                        interpret=False))
        return sum(t.astype(jnp.float32).sum() for t in made)

    wide, f32 = sds(seq, heads * d), jnp.float32
    text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(12)))).lower(
        *[wide] * 6, *[sds(heads * d, 4, dtype=f32)] * 3,
        sds(heads * d, dtype=f32), sds(heads, dtype=f32),
        sds(d, dtype=f32)).compile().as_text()
    kernels = [line for line in text.split("\n")
               if "tpu_custom_call" in line and " custom-call(" in line]
    named = lambda name: [line for line in kernels
                          if name in line.split(" = ")[0]]
    assert len(kernels) == 10
    assert len(named("hvd_kda_conv_fwd")) == len(named("hvd_kda_conv_bwd")) == 3
    for name in ("hvd_kda_decay_fwd", "hvd_kda_decay_bwd", "hvd_kda_out_fwd",
                 "hvd_kda_out_bwd"):
        assert len(named(name)) == 1
    assert f"f32[{seq},{heads * d}]" in named("hvd_kda_decay_fwd")[0].split(
        " custom-call(")[0]
    assert f"[{seq},{heads},{d}]" not in text
    assert (seq, heads, d) == (8192, 32, 128)


@pytest.mark.parametrize("rotate", [True, False],
                         ids=["rotary", "no_positions"])
def test_qk_norm_rope_into_the_flash_kernels_compiles_for_v5e(
        one_chip, no_persistent_cache, rotate):
    """The attention of ``sdar`` and ``trinity`` between the projections'
    outputs and the output projection's input as their cells run it: one
    sequence of 8,192 positions, ``[S, 32 x 128]`` and ``[S, 4 x 128]``
    bf16 through ``qk_norm_rope`` (row blocks of ``qk_rope.ROWS``; with
    the two rotary tables, and without as on a full layer) into
    ``flash_attention_heads_first``, forward and backward: two calls of
    each of the pass's kernels, one of each flash kernel, and the pass's
    results head-major as they leave it."""
    from horovod_tpu.parallel.flash import flash_attention_heads_first
    from horovod_tpu.parallel.qk_rope import qk_norm_rope
    seq = 2 * SDAR_L

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, q_norm, k_norm, tables):
        q = qk_norm_rope(q, q_norm, tables, SDAR_H, 1e-6, interpret=False)
        k = qk_norm_rope(k, k_norm, tables, SDAR_HKV, 1e-6, interpret=False)
        return flash_attention_heads_first(
            q, k, v, causal=True, block_q=SDAR_TILE, block_k=SDAR_TILE,
            interpret=False).astype(jnp.float32).sum()

    table = sds(seq, SDAR_D, dtype=jnp.float32)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        sds(seq, SDAR_H * SDAR_D), sds(seq, SDAR_HKV * SDAR_D),
        sds(SDAR_HKV, seq, SDAR_D), sds(SDAR_D, dtype=jnp.float32),
        sds(SDAR_D, dtype=jnp.float32),
        (table, table) if rotate else None).compile().as_text()
    kernels = [line for line in text.split("\n")
               if "tpu_custom_call" in line and " custom-call(" in line]
    named = lambda name: [line for line in kernels
                          if name in line.split(" = ")[0]]
    assert len(kernels) == 7
    assert len(named("hvd_qk_rope_fwd")) == len(named("hvd_qk_rope_bwd")) == 2
    for heads in (SDAR_H, SDAR_HKV):
        flat, first = (f"bf16[{seq},{heads * SDAR_D}]",
                       f"bf16[{heads},{seq},{SDAR_D}]")
        assert sum(line.split(" custom-call(")[0].count(first)
                   and flat in line for line in named("hvd_qk_rope_fwd")) == 1
        assert sum(line.split(" custom-call(")[0].count(flat)
                   and first in line for line in named("hvd_qk_rope_bwd")) == 1
    assert all((f"f32[{seq},{SDAR_D}]" in line) == rotate
               for line in named("hvd_qk_rope"))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("stored", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("width", [SDAR_WIDTH, TRINITY_WIDTH])
def test_grouped_product_compiles_for_v5e(one_chip, no_persistent_cache,
                                          width, stored, backward):
    """``parallel/grouped.py``: the gated products' shapes at the three
    cells' widths, the matrices in the rows' dtype or as a model stores
    them (float32: a group's 2,048 x ``width`` slab is one block, 6 and 8
    MiB, cast in VMEM), with the sequential grid dimension as long as the
    group sizes say."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd(rows, weights, sizes):
        return gmm(rows, weights, sizes, interpret=False)

    def fwd_bwd(rows, weights, sizes):
        return jax.grad(
            lambda r, w: fwd(r, w, sizes).astype(jnp.float32).sum(),
            argnums=(0, 1))(rows, weights)

    _assert_kernel_compiles(
        fwd_bwd if backward else fwd,
        sds((SDAR_ROWS, SDAR_HIDDEN)),
        sds((SDAR_EXPERTS, SDAR_HIDDEN, width), stored),
        sds((SDAR_EXPERTS,), jnp.int32))


def test_dropless_layers_under_scan_make_no_copy_of_the_stacked_weights(
        one_chip, no_persistent_cache):
    """Three expert layers under ``lax.scan`` over their stacked float32
    parameters, bf16 tokens, forward and gradients: the compiled text
    holds the experts' matrices in float32 alone.  A conversion in the
    layer is moved out of the scan by XLA and made for every layer at
    once, a bf16 copy of the stack that lives through the whole step."""
    from horovod_tpu.parallel.moe import dropless_expert_ffn
    layers, tokens, experts = 3, 2048, 128

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, stacked):
        def layer(x, p):
            return x + dropless_expert_ffn(x, *p, top_k=8,
                                           interpret=False).out, None

        return jax.lax.scan(layer, x, stacked)[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sds(tokens, SDAR_HIDDEN, dtype=jnp.bfloat16),
        (sds(layers, SDAR_HIDDEN, experts),
         sds(layers, SDAR_EXPERTS, SDAR_HIDDEN, SDAR_WIDTH),
         sds(layers, SDAR_EXPERTS, SDAR_HIDDEN, SDAR_WIDTH),
         sds(layers, SDAR_EXPERTS, SDAR_WIDTH, SDAR_HIDDEN))).compile(
             ).as_text()
    matrices = r"\[(\d+,)?%d,(%d,%d|%d,%d)\]" % (
        SDAR_EXPERTS, SDAR_HIDDEN, SDAR_WIDTH, SDAR_WIDTH, SDAR_HIDDEN)
    assert re.search("f32" + matrices, text)
    assert not re.search("bf16" + matrices, text)
    assert text.count("hvd_gmm") and text.count("hvd_tgmm")


@pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          phase, kv):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q_dtype = jnp.bfloat16 if kv == "bf16" else jnp.float32
    pool_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                  "int8": jnp.int8}[kv]
    q_shape = ((SERVE_B, H, DH) if phase == "decode"
               else (SERVE_B, PREFILL_C, H, DH))
    attend = (paged_decode_attention if phase == "decode"
              else paged_prefill_attention)
    pool = sds((NB, BT, H, DH), pool_dtype)
    scales = (sds((NB, BT, H), SCALE_DTYPE),) * 2 if kv == "int8" else ()

    def fn(q, k, v, tables, positions, *scale_rows):
        k_scale, v_scale = scale_rows or (None, None)
        return attend(q, k, v, tables, positions, k_scale=k_scale,
                      v_scale=v_scale, interpret=False)

    _assert_kernel_compiles(
        fn, sds(q_shape, q_dtype), pool, pool,
        sds((SERVE_B, SERVE_MB), jnp.int32), sds((SERVE_B,), jnp.int32),
        *scales)


# -- the data-parallel step ---------------------------------------------------

STEP_LAYERS, STEP_WIDTH, STEP_ROWS = 4, 2048, 512


def _compiled_step(devices):
    """``(text, options)`` of a small data-parallel training step through
    ``hvd.shard_step`` over a mesh of ``devices``: four dense layers of 8
    MiB of bf16 gradient each (a bucket each, all above the wrapper's
    bucket size), parameters replicated, SGD with momentum through
    ``DistributedOptimizer``."""
    mesh = Mesh(np.asarray(devices), ("hvd",))
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))

    def local_step(params, opt_state, x):
        def loss(p):
            h = x.astype(jnp.bfloat16)
            for w in p:
                h = jnp.tanh(h @ w.astype(jnp.bfloat16))
            return (h.astype(jnp.float32) ** 2).mean()

        value, grads = jax.value_and_grad(loss)(params)
        value = hvd.allreduce(value, op=hvd.Average)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value

    step = hvd.shard_step(local_step, mesh=mesh,
                          in_specs=(P(), P(), P("hvd")),
                          out_specs=(P(), P(), P()), donate_argnums=(0, 1))
    replicated, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("hvd"))
    params = [jax.ShapeDtypeStruct((STEP_WIDTH, STEP_WIDTH), jnp.float32,
                                   sharding=replicated)] * STEP_LAYERS
    opt_state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=replicated),
        jax.eval_shape(opt.init, params))
    x = jax.ShapeDtypeStruct((STEP_ROWS * len(devices), STEP_WIDTH),
                             jnp.float32, sharding=rows)
    return (step.lower(params, opt_state, x).compile().as_text(),
            parallel._compiler_options(mesh))


def _gradient_all_reduces(text):
    """``(asynchronous, synchronous)`` all-reduces of the backward pass:
    an asynchronous one is the ``all-reduce`` in the fused computation that
    ends in the ``AsyncCollectiveStart`` custom call (the other pieces of
    the fusion hold copies of it), or an ``all-reduce-start``; a
    synchronous one is an ``all-reduce`` of the entry computation."""
    backward = r"[^\n]*op_name=\"[^\"]*transpose\(jvp"
    starts = 0
    for computation in re.split(r"\n\n", text):
        if 'custom_call_target="AsyncCollectiveStart"' in computation:
            starts += len(re.findall(r" all-reduce\(" + backward,
                                     computation))
    entry = text[text.index("\nENTRY "):]
    starts += len(re.findall(r" all-reduce-start\(" + backward, entry))
    return starts, len(re.findall(r" all-reduce\(" + backward, entry))


def test_dp_step_reduces_its_gradients_in_asynchronous_buckets(
        topo, no_persistent_cache):
    text, options = _compiled_step(topo.devices)
    assert options["xla_jf_crs_combiner_threshold_in_bytes"] == \
        parallel._BUCKET_BYTES
    asynchronous, synchronous = _gradient_all_reduces(text)
    assert asynchronous > 1, (asynchronous, synchronous)
    assert asynchronous + synchronous == STEP_LAYERS


def test_one_device_step_gets_no_option_and_has_no_collective(
        topo, no_persistent_cache):
    text, options = _compiled_step(topo.devices[:1])
    assert options == {}
    assert not re.search(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|AsyncCollective",
                         text)


def test_option_names_the_compiler_refuses_fall_back_to_no_option(
        topo, monkeypatch):
    """The names are one libtpu release's: a compiler that has renamed one
    must cost the overlap, not every multi-chip compile."""
    mesh = Mesh(np.asarray(topo.devices), ("hvd",))
    assert parallel._compiler_options(mesh) == parallel._ASYNC_BUCKETS
    monkeypatch.setattr(parallel, "_ASYNC_BUCKETS", dict(
        parallel._ASYNC_BUCKETS, xla_tpu_no_such_option_of_any_release=True))
    with pytest.warns(UserWarning, match="refuses the options"):
        assert parallel._compiler_options(mesh) == {}
