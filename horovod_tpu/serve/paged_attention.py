"""Fused Pallas paged-attention kernels for the serving engine.

The gather path in ``engine.py`` reassembles each sequence's logical K/V
context from the block pool with ``jnp.take`` over its block table and
materializes the gathered ``[B, S, H, Dh]`` copies in HBM before a dense
attention — the CPU-exercisable form of PagedAttention, explicitly shaped
for this swap.  These kernels consume the pool and the block tables
*directly* (vLLM's PagedAttention, Kwon et al. SOSP '23, mapped onto the
Mosaic pipeline the way ``parallel/flash.py`` maps FlashAttention-2):

* **decode** — grid ``(B, num_logical_blocks)`` with the logical-block
  index as the sequential (``arbitrary``) dimension; a block takes all
  heads of one pool block and the kernel loops over them.  The block
  tables and positions ride in as **scalar-prefetch** operands
  (``pltpu.PrefetchScalarGridSpec``), so each K/V block's BlockSpec
  ``index_map`` reads ``tables[b, j]`` and Mosaic double-buffers the
  HBM→VMEM DMA of physical block ``tables[b, j+1]`` against the MXU work
  on block ``tables[b, j]`` — no gathered copy ever exists in HBM.  The
  online-softmax state (running max / sum / accumulator) lives in VMEM
  scratch persisting across the block dimension, via
  ``online_softmax_block``/``online_softmax_flush`` of ``parallel/flash.py``,
  which this kernel alone calls (the training forward kernel keeps its
  state dense across the lanes, ``flash._fwd_step``).
* **hole masking** — table holes carry the out-of-bounds sentinel
  (``num_blocks``); the index_map clamps them onto the last real block
  (exactly what ``jnp.take(mode="clip")`` does in the gather path) and the
  *in-kernel* position mask zeroes every clamped lane, so correctness
  never depends on a post-hoc ``-1e30`` pass over a gathered copy.
  Blocks entirely past a sequence's length skip their MXU work outright.
* **chunked prefill** — the same kernel shape with a ``[C, H, Dh]`` query
  tile per sequence and the mask evaluated at *absolute*
  positions (query ``starts[b] + row`` vs key ``j*block_tokens + col``)
  through ``causal_mask``, the one function shared with the training
  flash kernels (``MASK_NONE``/``MASK_CAUSAL``/``MASK_STRICT``,
  ``parallel/flash.py``; positions on a column and a row) —
  the engine scatters the chunk's K/V into the pool before the call, so
  intra-chunk causality falls out of the positional mask exactly as in
  the gather path.
* **quantized KV blocks** — int8 (and fp8 ``float8_e4m3fn`` where the
  jax build has it) block storage with scale rows stored per (block slot,
  position, head): dequantization is fused into the kernel's block load
  (one multiply in VMEM), and the scale pools ride the same
  table-indexed BlockSpecs.  Scales are per *position* within the block
  rather than one per block because blocks fill incrementally (a decode
  appends one token into an existing block); a single per-block scale
  would need a lossy requantization of every already-written token on
  each append, while per-position rows are written once, append-only,
  exactly like the K/V they describe.  At ``float16`` scales the
  overhead is ``2/Dh`` of the int8 payload (~3% at Dh=64).

Numerics: all accumulation is f32, like both the gather path and the
flash kernels.  The online softmax is mathematically identical to the
gather path's ``softmax(mask(QK^T))V`` but associates the reductions
blockwise, so kernel-vs-gather parity is exact at the *token stream*
level (greedy argmax; pinned by tests across mask modes, block sizes and
pool geometries) and ~1e-7-tight at the attention-output level — the
same contract the flash kernels pin against their dense reference.

Everything runs under the Pallas interpreter off-TPU (CPU tier-1 tests
and the hermetic bench), and compiles through Mosaic on TPU, where the
float32 dots go through the MXU as one bf16 pass — the precision XLA's
default gives the gather path too (chip_smoke.py measures both).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..parallel.flash import (LANES, MASK_CAUSAL, MASK_NONE, MASK_STRICT,
                              NEG_INF, block_contributes, causal_mask,
                              online_softmax_block, online_softmax_flush)

__all__ = [
    "MASK_NONE", "MASK_CAUSAL", "MASK_STRICT",
    "KV_DTYPES", "kv_bytes_per_token", "quantize_kv", "dequantize_kv",
    "paged_decode_attention", "paged_prefill_attention",
    "paged_attention_reference",
]


# ---------------------------------------------------------------------------
# Quantized block storage
# ---------------------------------------------------------------------------

#: Scale rows are stored per (block slot, position, head) in this dtype;
#: f16's 10-bit mantissa keeps the scale's own rounding (~5e-4 relative)
#: far under int8's quantization step (~4e-3 relative at amax).
SCALE_DTYPE = jnp.float16


def _fp8_dtype():
    return getattr(jnp, "float8_e4m3fn", None)


def _kv_dtypes():
    out = {
        # name -> (storage dtype or None for "store at compute dtype",
        #          max representable magnitude for the quantizer)
        "native": (None, None),
        "int8": (jnp.int8, 127.0),
    }
    if _fp8_dtype() is not None:
        out["fp8"] = (_fp8_dtype(), 448.0)
    return out


#: Supported ``HVD_SERVE_KV_DTYPE`` values on this jax build.
KV_DTYPES = tuple(_kv_dtypes())


def kv_bytes_per_token(kv_dtype: str, head_dim: int, native_dtype) -> int:
    """HBM bytes one token position of one head's K *or* V costs under
    ``kv_dtype`` storage (payload + its share of the scale row) — the
    unit the BlockManager's bytes-per-block accounting is built from."""
    storage, _ = _kv_dtypes()[kv_dtype]
    if storage is None:
        return head_dim * jnp.dtype(native_dtype).itemsize
    return (head_dim * jnp.dtype(storage).itemsize
            + jnp.dtype(SCALE_DTYPE).itemsize)


def quantize_kv(x, kv_dtype: str):
    """Quantize K/V ``[..., H, Dh]`` to ``(values, scales)`` with one
    symmetric-absmax scale per ``[..., H]`` row (per token position, per
    head).  Written at append time; rows are immutable afterwards."""
    storage, qmax = _kv_dtypes()[kv_dtype]
    if storage is None:
        raise ValueError(f"kv_dtype {kv_dtype!r} is not quantized")
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax / qmax, 1e-8)
    q = x32 / scale[..., None]
    if storage == jnp.int8:
        q = jnp.clip(jnp.round(q), -127.0, 127.0)
    else:  # fp8: clamp before the saturating cast (inf on overflow)
        q = jnp.clip(q, -qmax, qmax)
    return q.astype(storage), scale.astype(SCALE_DTYPE)


def dequantize_kv(values, scales):
    """Inverse of :func:`quantize_kv` (f32 out): ``values [..., H, Dh]``
    times the broadcast ``scales [..., H]`` row."""
    return values.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, mask_mode: int, block_tokens: int,
                  num_blocks: int, quantized: bool):
    """Shared decode/prefill kernel body.

    ``q_ref`` is ``[1, C, H, Dh]`` (C = 1 for decode); ``k_ref``/``v_ref``
    are one physical pool block ``[1, BT, H, Dh]`` selected by the
    BlockSpec index_map from the scalar-prefetched table — every block
    takes all heads, so its last two dimensions are whole array
    dimensions, which the TPU lowering requires of a block that is not a
    multiple of the (8, 128) tile; the heads are looped over in here.
    ``rest`` is ``(k_scale_ref, v_scale_ref, o_ref, acc, m, l)`` when
    quantized else ``(o_ref, acc, m, l)``, the scratch carrying one
    online-softmax state per head.  ``pos_ref[b]`` is the highest key
    position this row's queries may see (decode: the token's own
    position; prefill: the chunk's start — each query row adds its offset
    via the mask-mode machinery).
    """
    if quantized:
        k_scale_ref, v_scale_ref, o_ref, acc, m, l = rest
    else:
        o_ref, acc, m, l = rest
    b, j = pl.program_id(0), pl.program_id(1)
    C, H = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    q_lo = pos_ref[b]
    # Highest position any query row of this tile can attend; a key block
    # starting past it contributes nothing — skip its MXU work (the DMA
    # of the clamped block is already in flight; acceptable overfetch,
    # identical to the flash kernels' mask-skip policy).  Hole sentinels
    # (table entry >= num_blocks) are skipped in EVERY mask mode — a
    # hole is never a real key, and under MASK_NONE the positional mask
    # alone would let the clamped block's garbage attend.
    contributes = block_contributes(mask_mode, q_lo, q_lo + C - 1,
                                    j * block_tokens) \
        & (tables_ref[b, j] < num_blocks)

    @pl.when(contributes)
    def _step():
        for h in range(H):
            q = q_ref[0, :, h, :].astype(jnp.float32) * scale   # [C, Dh]
            k = k_ref[0, :, h, :].astype(jnp.float32)           # [BT, Dh]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                k = k * k_scale_ref[0, :, h:h + 1]
                v = v * v_scale_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [C, BT]
            # Absolute-position mask: queries at q_lo + row vs keys at
            # j*BT + col.  This is what zeroes hole blocks (their clamped
            # physical block holds positions past the sequence) — the
            # kernel masks CONTRIBUTIONS, never trusting gathered values.
            s = causal_mask(s, q_lo, j * block_tokens, mask_mode)
            online_softmax_block(s, v, m.at[h], l.at[h], acc.at[h])

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        for h in range(H):
            out, _ = online_softmax_flush(m.at[h], l.at[h], acc.at[h])
            o_ref[0, :, h, :] = out.astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, tables, positions, k_scale, v_scale,
                scale, mask_mode, interpret):
    B, C, H, Dh = q.shape
    NB, BT = k_pool.shape[0], k_pool.shape[1]
    MB = tables.shape[1]
    quantized = k_scale is not None

    def pool_map(*trailing):
        # Physical block = the scalar-prefetched table entry, clamped onto
        # the last real block for hole sentinels exactly like
        # ``jnp.take(mode="clip")`` (the in-kernel masking skips/zeroes
        # the clamped lanes).
        def index_map(b, j, tables, pos):
            return (jnp.minimum(tables[b, j], NB - 1),) + trailing
        return index_map

    def q_map(b, j, tables, pos):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, C, H, Dh), q_map),
        pl.BlockSpec((1, BT, H, Dh), pool_map(0, 0, 0)),
        pl.BlockSpec((1, BT, H, Dh), pool_map(0, 0, 0)),
    ]
    args = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, BT, H), pool_map(0, 0)),
                     pl.BlockSpec((1, BT, H), pool_map(0, 0))]
        # Mosaic has no float16 vector loads; the widening is exact.
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C, H, Dh), q_map),
        scratch_shapes=[pltpu.VMEM((H, C, Dh), jnp.float32),
                        pltpu.VMEM((H, C, LANES), jnp.float32),
                        pltpu.VMEM((H, C, LANES), jnp.float32)],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, mask_mode=mask_mode, block_tokens=BT,
        num_blocks=NB, quantized=quantized)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, Dh), jnp.float32),
        compiler_params=compiler_params,
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(positions, jnp.int32),
      *args)


def _resolve_interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def paged_decode_attention(q, k_pool, v_pool, tables, positions, *,
                           k_scale=None, v_scale=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """One decode step of paged attention, straight off the block pool.

    ``q`` [B, H, Dh] (the step's single query per sequence); ``k_pool`` /
    ``v_pool`` [NB, BT, H, Dh] (one layer's pool; int8/fp8 storage passes
    the matching ``k_scale``/``v_scale`` [NB, BT, H] rows); ``tables``
    [B, MB] block tables with the hole sentinel ``NB``; ``positions`` [B]
    = each row's current token position (keys at index <= position
    attend, exactly the gather path's validity mask).  Returns
    [B, H, Dh] f32.
    """
    B, H, Dh = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    out = _paged_call(q[:, None], k_pool, v_pool, tables, positions,
                      k_scale, v_scale, scale, MASK_CAUSAL,
                      _resolve_interpret(interpret))
    return out[:, 0]


def paged_prefill_attention(q, k_pool, v_pool, tables, starts, *,
                            mask_mode: int = MASK_CAUSAL,
                            k_scale=None, v_scale=None,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None):
    """Chunked-prefill paged attention: ``q`` [B, C, H, Dh] is one prompt
    chunk per sequence whose row 0 sits at absolute position
    ``starts[b]`` (the engine scatters the chunk's K/V into the pool
    before this call, so intra-chunk causality falls out of the
    positional ``mask_mode`` — MASK_CAUSAL for standard decode-parity
    prefill, MASK_STRICT/MASK_NONE for ring-style consumers).  Returns
    [B, C, H, Dh] f32."""
    B, C, H, Dh = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    return _paged_call(q, k_pool, v_pool, tables, starts,
                       k_scale, v_scale, scale, mask_mode,
                       _resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# Gather reference (the exactness baseline, shared with tests/bench)
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_pool, v_pool, tables, positions, *,
                              mask_mode: int = MASK_CAUSAL,
                              k_scale=None, v_scale=None,
                              scale: Optional[float] = None):
    """The engine's gather-based paged attention as a free function (take
    over the block table + post-hoc mask + dense softmax), accepting both
    decode ([B, H, Dh]) and prefill ([B, C, H, Dh]) query shapes — the
    baseline the kernels are pinned against and the dequantizing gather
    the engine's ``attn_impl="gather"`` path uses for quantized pools."""
    decode = q.ndim == 3
    if decode:
        q = q[:, None]
    B, C, H, Dh = q.shape
    NB, BT = k_pool.shape[0], k_pool.shape[1]
    MB = tables.shape[1]
    S = MB * BT
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    kk = jnp.take(k_pool, tables, axis=0, mode="clip").reshape(B, S, H, Dh)
    vv = jnp.take(v_pool, tables, axis=0, mode="clip").reshape(B, S, H, Dh)
    if k_scale is not None:
        ks = jnp.take(k_scale, tables, axis=0, mode="clip").reshape(B, S, H)
        vs = jnp.take(v_scale, tables, axis=0, mode="clip").reshape(B, S, H)
        kk = dequantize_kv(kk, ks)
        vv = dequantize_kv(vv, vs)
    s = jnp.einsum("bqhe,bkhe->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    q_pos = positions[:, None, None, None] \
        + jnp.arange(C)[None, None, :, None]
    k_pos = jnp.arange(S)[None, None, None, :]
    if mask_mode == MASK_CAUSAL:
        keep = k_pos <= q_pos
    elif mask_mode == MASK_STRICT:
        keep = k_pos < q_pos
    else:
        keep = jnp.ones_like(k_pos <= q_pos)
    # Hole sentinels are never real keys, whatever the mask mode — the
    # kernel skips them at the block level; mask their positions here so
    # MASK_NONE can't attend the clamped block's garbage.  (Under
    # CAUSAL/STRICT with engine-shaped tables this is a no-op: hole
    # positions always exceed every query position.)
    hole = jnp.repeat(tables >= NB, BT, axis=1)          # [B, S]
    keep = keep & ~hole[:, None, None, :]
    s = jnp.where(keep, s, jnp.float32(NEG_INF))
    p = jax.nn.softmax(s, axis=-1)
    # A row with EVERY key masked contributes nothing (the kernels'
    # floored online softmax gives it exactly 0) — softmax alone would
    # spread weight 1/S over the masked garbage instead.  No-op for any
    # row with a real key: its masked lanes already carry exactly 0.
    p = jnp.where(jnp.any(keep, axis=-1, keepdims=True), p, 0.0)
    out = jnp.einsum("bhqk,bkhe->bqhe", p, vv.astype(jnp.float32))
    return out[:, 0] if decode else out
