"""Flash attention as differentiable Pallas TPU kernels.

The local-attention compute inside sequence parallelism (the per-step block
math of ring attention, or the full-sequence-per-head-subset attention of
Ulysses) and the dense encoder attention of BERT/GPT are the hot loops this
kernel serves.  FlashAttention-2 structure, mapped onto the Mosaic pipeline:

* **Forward** — grid ``(B*H, q_blocks, k_blocks)`` with the K/V block index
  as an ``arbitrary`` (sequential) grid dimension.  Each K/V block is a
  grid-indexed ``BlockSpec``, so Mosaic double-buffers the HBM→VMEM DMA of
  block *i+1* against the MXU compute of block *i* automatically — the
  whole online-softmax state (running max / sum / accumulator) lives in
  VMEM scratch that persists across the sequential dimension.  The [S, S]
  score matrix never touches HBM.  Emits the per-row logsumexp as a
  residual for the backward pass.
* **Backward** — two kernels of the same shape (FlashAttention-2 split):
  one accumulates dQ streaming over K/V blocks, one accumulates dK/dV
  streaming over Q blocks; both recompute the probabilities from the saved
  logsumexp instead of materializing them.
* ``jax.custom_vjp`` ties them together, so the kernel drops into
  ``jax.grad`` training steps (the BERT/GPT benches) directly.

Parity note: the reference has no attention kernels at all (it is a
communication library); this is part of the TPU build's "beat the baseline"
surface (SURVEY.md §5.8).  Numerics (forward AND gradients) are validated
against the dense reference implementation in tests (CPU interpret mode),
the kernels are compiled for the chip in tests/test_tpu_compile.py and run
against the same reference on it by chip_smoke.py.

Layout: [B, S, H, D] public API; internally [B*H, S, D], per-row
statistics [B*H, 1, S].  Block sizes default to 128 (MXU tile) and clamp
to the sequence length.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # VMEM lane width: (block_q, LANES) scratch keeps m/l aligned

# Static mask modes (ring attention's per-hop block masks compile one
# kernel per mode): NONE = full attend; CAUSAL = q >= k on local indices;
# STRICT = q > k (the striped ring's off-diagonal rule).
MASK_NONE, MASK_CAUSAL, MASK_STRICT = 0, 1, 2


def causal_mask(s, q_offset, k_offset, mode):
    """Apply a mask mode to one ``[Bq, Bk]`` score tile whose queries sit
    at global positions ``q_offset + row`` and keys at ``k_offset + col``.
    Offsets may be static ints (the dense flash kernels pass block-index
    multiples) or traced scalars (the paged serving kernels pass each
    sequence's absolute chunk start / block-table slot).  Shared by the
    training flash kernels and serve/paged_attention."""
    if mode == MASK_NONE:
        return s
    bq, bk = s.shape
    qg = q_offset + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kg = k_offset + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = qg >= kg if mode == MASK_CAUSAL else qg > kg
    return jnp.where(keep, s, NEG_INF)


def block_contributes(mode, q_lo, q_hi, k_lo):
    """Whether a key block starting at global position ``k_lo`` can
    contribute to queries spanning ``[q_lo, q_hi]`` under ``mode`` — the
    compute-skip predicate for blocks entirely outside the mask (their
    DMA is already in flight; acceptable overfetch).  Static or traced
    positions, same contract as :func:`causal_mask`."""
    if mode == MASK_NONE:
        return True
    if mode == MASK_CAUSAL:
        return k_lo <= q_hi
    return k_lo < q_hi  # STRICT


def online_softmax_block(s, v, m_ref, l_ref, acc_ref):
    """One FlashAttention-2 online-softmax accumulation step: fold score
    tile ``s`` [Bq, Bk] and value block ``v`` [Bk, D] into the running
    (max ``m_ref``, sum ``l_ref``, accumulator ``acc_ref``) VMEM scratch
    carried across the sequential K-block grid dimension.  Shared by the
    training flash kernels and serve/paged_attention.

    The running max is floored at ``NEG_INF / 2`` so a row with EVERY
    key masked contributes ``p = exp(NEG_INF - NEG_INF/2) = 0`` instead
    of ``exp(NEG_INF - NEG_INF) = 1`` per masked key — without the floor
    such a row accumulates weight-1 garbage that nothing ever corrects
    (reachable via MASK_STRICT's first row, and via paged tables whose
    clamped hole blocks sit entirely past the sequence).  Rows that see
    at least one unmasked key anywhere are bit-identical either way: the
    first real key's ``corr = exp(floor - max)`` underflows to exactly
    0.0, the same wash-out the unfloored state got from
    ``exp(NEG_INF - max)``."""
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True)),
                        NEG_INF / 2)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def online_softmax_flush(m_ref, l_ref, acc_ref):
    """Finalize the online softmax: returns ``(out [Bq, D], lse
    [Bq, LANES])`` from the scratch state after the last contributing
    block; the logsumexp stays lane-broadcast like the state it is made
    from."""
    l_final = jnp.maximum(l_ref[...], 1e-30)
    return acc_ref[...] / l_final[:, :1], m_ref[...] + jnp.log(l_final)


def _col_to_row(x):
    """``[Bq, LANES]`` lane-broadcast per-row statistic → ``[1, Bq]`` row,
    the layout it is stored in (see ``_flash_fwd``)."""
    return x.T[:1]


def _row_to_col(row):
    """``[1, Bq]`` stored statistic → ``[Bq, 1]`` column that broadcasts
    against a ``[Bq, Bk]`` score tile."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l, *,
                scale: float, mask_mode: int, block_q: int, block_k: int,
                num_kb: int):
    qi, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    contributes = block_contributes(mask_mode, qi * block_q,
                                    qi * block_q + block_q - 1,
                                    kb * block_k)

    @pl.when(contributes)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # [Bq, D]
        k = k_ref[0].astype(jnp.float32)              # [Bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Bq, Bk]
        s = causal_mask(s, qi * block_q, kb * block_k, mask_mode)
        online_softmax_block(s, v, m, l, acc)

    @pl.when(kb == num_kb - 1)
    def _flush():
        out, lse = online_softmax_flush(m, l, acc)
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0] = _col_to_row(lse)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale: float, mask_mode: int, block_q: int,
                   block_k: int, num_kb: int):
    qi, kb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    contributes = block_contributes(mask_mode, qi * block_q,
                                    qi * block_q + block_q - 1,
                                    kb * block_k)

    @pl.when(contributes)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = causal_mask(s, qi * block_q, kb * block_k, mask_mode)
        p = jnp.exp(s - _row_to_col(lse_ref[0]))      # [Bq, Bk]
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _row_to_col(delta_ref[0]))
        dq_acc[...] += jax.lax.dot_general(
            ds, k, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    mask_mode: int, block_q: int, block_k: int,
                    num_qb: int):
    kb, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    contributes = block_contributes(mask_mode, qi * block_q,
                                    qi * block_q + block_q - 1,
                                    kb * block_k)

    @pl.when(contributes)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = causal_mask(s, qi * block_q, kb * block_k, mask_mode)
        p = jnp.exp(s - _row_to_col(lse_ref[0]))      # [Bq, Bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Bk, D]
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _row_to_col(delta_ref[0]))
        dk_acc[...] += jax.lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Bk, D]

    @pl.when(qi == num_qb - 1)
    def _flush():
        # q was pre-scaled, so dk_acc already carries the scale factor.
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _out_struct(shape, dtype, like):
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _stat_spec(block_q, index_map):
    """BlockSpec of a per-row statistic (logsumexp, delta).  They are kept
    as ``[BH, 1, S]`` rows so that a block's last two dimensions are the
    whole unit dimension and a lane-dense ``block_q`` — a ``(1, block_q)``
    block of a ``[BH, S]`` array is refused by the TPU lowering."""
    return pl.BlockSpec((1, 1, block_q), index_map)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, mask_mode, scale, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, mask_mode, scale, block_q, block_k,
                        interpret)
    return out


def _flash_fwd(q, k, v, mask_mode, scale, block_q, block_k, interpret):
    BH, S, D = q.shape
    num_qb, num_kb = S // block_q, S // block_k
    kernel = functools.partial(_fwd_kernel, scale=scale,
                               mask_mode=mask_mode,
                               block_q=block_q, block_k=block_k,
                               num_kb=num_kb)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[_out_struct((BH, S, D), q.dtype, q),
                   _out_struct((BH, 1, S), jnp.float32, q)],
        grid=(BH, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kb: (bh, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kb: (bh, qi, 0)),
            _stat_spec(block_q, lambda bh, qi, kb: (bh, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)
    return out, (q, k, v, out, lse.reshape(BH, S))


def _flash_bwd(mask_mode, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass; let XLA fuse it
    # in f32.  dO itself stays in its original dtype (the kernels upcast
    # per-block in VMEM; a host-side astype would double bf16 DMA traffic).
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # [BH, S]
    return _run_bwd_kernels(q, k, v, g, lse, delta, mask_mode, scale,
                            block_q, block_k, interpret)


def _run_bwd_kernels(q, k, v, do, lse, delta, mask_mode, scale,
                     block_q, block_k, interpret):
    """The two FlashAttention-2 backward kernels, shared by the plain and
    the lse-exposing vjps (the latter folds the lse cotangent into
    ``delta``; see ``_flash_lse_bwd``)."""
    BH, S, D = q.shape
    num_qb, num_kb = S // block_q, S // block_k
    lse, delta = lse.reshape(BH, 1, S), delta.reshape(BH, 1, S)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, mask_mode=mask_mode,
                          block_q=block_q, block_k=block_k, num_kb=num_kb),
        out_shape=_out_struct((BH, S, D), q.dtype, q),
        grid=(BH, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kb: (bh, qi, 0)),
            _stat_spec(block_q, lambda bh, qi, kb: (bh, 0, qi)),
            _stat_spec(block_q, lambda bh, qi, kb: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, kb: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale,
                          mask_mode=mask_mode,
                          block_q=block_q, block_k=block_k, num_qb=num_qb),
        out_shape=[_out_struct((BH, S, D), k.dtype, k),
                   _out_struct((BH, S, D), v.dtype, v)],
        grid=(BH, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, kb, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, kb, qi: (bh, qi, 0)),
            _stat_spec(block_q, lambda bh, kb, qi: (bh, 0, qi)),
            _stat_spec(block_q, lambda bh, kb, qi: (bh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kb, qi: (bh, kb, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, mask_mode, scale, block_q, block_k, interpret,
               out_dtype):
    """Like ``_flash`` but returns (out, lse) and is differentiable in
    BOTH outputs — the building block ring attention's cross-hop
    logsumexp merge needs (the merge weights are functions of lse, so a
    nonzero lse cotangent flows back into q/k).  ``out_dtype`` lets the
    merge receive f32 partials (one quantization at the END of the ring,
    not one per hop)."""
    (out, lse), _ = _flash_lse_fwd(q, k, v, mask_mode, scale, block_q,
                                   block_k, interpret, out_dtype)
    return out, lse


def _flash_lse_fwd(q, k, v, mask_mode, scale, block_q, block_k, interpret,
                   out_dtype):
    qd = q if out_dtype is None else q.astype(out_dtype)
    out, res = _flash_fwd(qd, k, v, mask_mode, scale, block_q, block_k,
                          interpret)
    return (out, res[4]), (q, k, v, out, res[4])


def _flash_lse_bwd(mask_mode, scale, block_q, block_k, interpret,
                   out_dtype, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    # ds_ij = p_ij (dp_ij - delta_i + g_lse_i): the lse cotangent enters
    # the softmax backward exactly like -delta (dL/ds_ij = p_ij), so it
    # folds into the delta operand and the kernels run unchanged.
    delta = jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1) - g_lse.astype(jnp.float32)       # [BH, S]
    return _run_bwd_kernels(q, k, v, g_out, lse, delta, mask_mode, scale,
                            block_q, block_k, interpret)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable flash attention over [B, S, H, D] (full local seq).

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU so the
    same call works in the CPU-mesh test environment.  In interpret mode
    under shard_map, pass ``check_vma=False`` to the shard_map (the
    interpreter inlines the kernel, mixing invariant loop indices with
    varying data); the compiled TPU path needs no such escape hatch."""
    B, S, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(
            f"flash_attention requires seq len {S} divisible by block sizes "
            f"({block_q}, {block_k})")

    def reshape_in(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    mode = MASK_CAUSAL if causal else MASK_NONE
    out = _flash(reshape_in(q), reshape_in(k), reshape_in(v),
                 mode, scale, block_q, block_k, interpret)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        *,
                        mask_mode: int = MASK_NONE,
                        scale: Optional[float] = None,
                        block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None,
                        out_dtype=None):
    """Flash attention returning ``(out [B,S,H,D], lse [B,H,S])``, both
    differentiable — the per-hop building block of ring_flash_attention
    (the cross-hop merge weights depend on lse, so its cotangent is
    nonzero).  ``mask_mode`` is one of MASK_NONE / MASK_CAUSAL /
    MASK_STRICT applied on LOCAL block indices (ring hops pick the mode
    per hop from the block owner)."""
    B, S, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(
            f"flash_attention_lse requires seq len {S} divisible by block "
            f"sizes ({block_q}, {block_k})")

    def reshape_in(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    out, lse = _flash_lse(reshape_in(q), reshape_in(k), reshape_in(v),
                          mask_mode, scale, block_q, block_k, interpret,
                          out_dtype)
    return (out.reshape(B, H, S, D).transpose(0, 2, 1, 3),
            lse.reshape(B, H, S))
