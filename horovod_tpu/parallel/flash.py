"""Flash attention as differentiable Pallas TPU kernels.

The local-attention compute inside sequence parallelism (the per-step block
math of ring attention, or the full-sequence-per-head-subset attention of
Ulysses) and the dense encoder attention of BERT/GPT are the hot loops this
kernel serves.  FlashAttention-2 structure, mapped onto the Mosaic pipeline:

* **Forward** — grid ``(B*H, t)`` where ``t``, an ``arbitrary``
  (sequential) grid dimension, walks one flat list of exactly the (query
  tile, key tile) pairs the mask keeps, a query tile's key tiles one after
  the other.  The list is static (``tile_lists``: a numpy table made from
  ``block_contributes`` and ``block_full``, handed to the kernel as a
  scalar-prefetch operand that its ``BlockSpec`` index maps read): for
  every step the query tile, the key tile, whether the tile crosses the
  mask's edge, and whether it is the first or the last of its query tile.
  So a tile wholly outside the mask costs no product, no copy and no grid
  step, and a tile wholly inside skips the mask's arithmetic.  The output
  block stays resident while consecutive steps name the same query tile
  and is written when the tile changes; the scaled query tile and the
  online-softmax state (running max / sum / accumulator) live in VMEM
  scratch, set up on a query tile's first step and flushed on its last.
  The state is dense across the lanes, a row's maximum and sum the same in
  every lane of the row, so a step pays its two lane reductions and no
  lane broadcast.
  Each K/V block is a grid-indexed ``BlockSpec``, so Mosaic double-buffers
  the HBM→VMEM DMA of step *t+1* against the MXU compute of step *t*
  automatically.  The [S, S] score matrix never touches HBM.  Emits the
  per-row logsumexp as a residual for the backward pass.
* **Backward** — two kernels (FlashAttention-2 split): one accumulates dQ
  over the same list as the forward pass, one accumulates dK/dV over the
  list's transpose (a key tile's query tiles one after the other); both
  recompute the probabilities from the saved logsumexp instead of
  materializing them.
* **Grouped key/value heads** — ``k`` and ``v`` may have fewer heads than
  ``q``: the index maps send query head ``h`` to key/value head ``h //
  group``, and the dK/dV kernel's list walks, for a key tile, the group's
  query heads and for each its query tiles, so K and V are never repeated
  in HBM and dK/dV are summed over the group in VMEM.
* **Two widths** — queries and keys are ``Dqk`` wide, values ``Dv``
  (``v.shape[-1]``; latent attention attends with keys of 192, 128 without
  positions and 64 rotary, and values of 128).  ``q``, ``k``, dQ and dK and
  their blocks and scratch are ``Dqk`` wide; ``v``, the output, its
  accumulator, dO and dV ``Dv``; the softmax scale defaults to ``1 /
  sqrt(Dqk)``.  One product over the whole key: a key of one and a half
  lane widths costs the MXU the two passes that 256 would, and so would a
  second product over its 64 rotary dimensions alone, so a rotary key that
  all heads share is broadcast by the caller (``models/joyai_flash.py``)
  and its gradient is the sum of dK's rotary columns over the heads.  With
  ``Dv == Dqk`` the kernels are the programs they were.
* **Masks** — static modes, a kernel per mode: ``MASK_NONE``,
  ``MASK_CAUSAL``, ``MASK_STRICT``, the causal window of
  :func:`window_mask` (``0 <= q - k < window``: a row walks at most
  ``window / block_k + 1`` key tiles, the diagonal one and the one the
  window's far side cuts with the mask, those between without) and the
  block-diffusion mask of :func:`block_diffusion_mask`.  One predicate pair
  (``block_contributes``, ``block_full``) decides for every mode which tiles
  the lists hold and which of them need the mask's arithmetic.  That
  arithmetic (:func:`causal_mask`) runs on the tile's positions as a
  ``[Bq, 1]`` column and a ``[1, Bk]`` row, 512 values each and not
  262,144: an edge tile pays one or two comparisons of the column with the
  row, their conjunction and a select.
* Products run in the operands' dtype (bf16 stays bf16 on the MXU) with
  float32 accumulation; softmax statistics are float32.
* ``jax.custom_vjp`` ties them together, so the kernel drops into
  ``jax.grad`` training steps (the BERT/GPT benches) directly.

Parity note: the reference has no attention kernels at all (it is a
communication library); this is part of the TPU build's "beat the baseline"
surface (SURVEY.md §5.8).  Numerics (forward AND gradients) are validated
against the dense reference implementation in tests (CPU interpret mode),
the kernels are compiled for the chip in tests/test_tpu_compile.py and run
against the same reference on it by chip_smoke.py.

Layout: [B, S, H, D] public API (``v``: [B, S, Hkv, Dv]); internally
[B*H, S, D], per-row statistics [B*H, 1, S], which
:func:`flash_attention_heads_first` takes and returns as they are (a
caller that makes its operands head-major itself: ``qk_rope.py``).  Block
sizes default to 128 (MXU tile) and clamp to the sequence length.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SAVED = ("hvd_flash_out", "hvd_flash_lse")  # checkpoint names, _flash_fwd
LANES = 128  # VMEM lane width: per-row statistics are kept this wide

# Static mask modes (ring attention's per-hop block masks compile one
# kernel per mode): NONE = full attend; CAUSAL = q >= k on local indices;
# STRICT = q > k (the striped ring's off-diagonal rule).
# BLOCK_DIFFUSION is the training mask of block diffusion (BD3-LM,
# arXiv:2503.09573): the sequence is the noised copy ``[0, L)`` followed by
# the clean copy ``[L, 2L)`` of ``L`` tokens cut into blocks; a noised query
# reads the noised keys of its own block and the clean keys of earlier
# blocks, a clean query reads the clean keys of its own and earlier blocks,
# and never a noised key.  It needs its block length and ``L``, so the mode
# is the tuple :func:`block_diffusion_mask` makes.
# WINDOW is the causal sliding window: a query reads itself and the
# ``window - 1`` keys before it, ``0 <= q - k < window``; the mode is the
# tuple :func:`window_mask` makes.
MASK_NONE, MASK_CAUSAL, MASK_STRICT, MASK_BLOCK_DIFFUSION, MASK_WINDOW = \
    0, 1, 2, 3, 4


def block_diffusion_mask(block_length: int, length: int):
    """The mask mode for ``[noised ; clean]`` copies of ``length`` tokens in
    blocks of ``block_length`` (static, hashable: a kernel per value)."""
    return (MASK_BLOCK_DIFFUSION, int(block_length), int(length))


def window_mask(window: int):
    """The mask mode of a causal window: pair ``(q, k)`` kept iff ``0 <= q -
    k < window`` (static, hashable: a kernel per value).  At or over the
    sequence it is ``MASK_CAUSAL``, tile lists and results bit for bit."""
    if window < 1:
        raise ValueError(f"a window of {window} positions keeps nothing")
    return (MASK_WINDOW, int(window))


def _half_block(pos, block_length, length):
    """``(noised?, block index)`` of global positions under block
    diffusion; arrays or Python ints.  Positions are never negative, so an
    array's division is a shift where the block length is a power of two
    and truncates where it is not."""
    noised = pos < length
    if isinstance(pos, int):
        return noised, (pos if noised else pos - length) // block_length
    local = jnp.where(noised, pos, pos - length)
    if block_length & (block_length - 1) == 0:
        return noised, jax.lax.shift_right_logical(
            local, jnp.int32(block_length.bit_length() - 1))
    return noised, jax.lax.div(local, jnp.int32(block_length))


_NEVER = np.iinfo(np.int32).max  # above every position and every rank


def causal_mask(s, q_offset, k_offset, mode):
    """Apply a mask mode to one ``[Bq, Bk]`` score tile whose queries sit
    at global positions ``q_offset + row`` and keys at ``k_offset + col``.
    Offsets may be static ints (the dense flash kernels pass block-index
    multiples) or traced scalars (the paged serving kernels pass each
    sequence's absolute chunk start / block-table slot).  Shared by the
    training flash kernels and serve/paged_attention.

    What depends on a query's position alone is computed on a ``[Bq, 1]``
    column and what depends on a key's alone on a ``[1, Bk]`` row: the
    positions, the window's far side, block diffusion's halves, block
    indices (its one division) and ranks.  The tile sees the column
    compared with the row (once under ``MASK_CAUSAL`` and ``MASK_STRICT``,
    twice and the two results' conjunction under the window and block
    diffusion) and the one select: no arithmetic of the tile's shape."""
    if mode == MASK_NONE:
        return s
    bq, bk = s.shape
    qg = q_offset + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    kg = k_offset + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    if isinstance(mode, tuple) and mode[0] == MASK_WINDOW:
        keep = (kg <= qg) & (kg > qg - mode[1])
    elif isinstance(mode, tuple):
        _, block_length, length = mode
        q_noised, q_block = _half_block(qg, block_length, length)
        k_noised, k_block = _half_block(kg, block_length, length)
        # Rank the blocks of both copies in one order, noised block b at
        # 2b and clean block b at 2b + 1.  A clean key is kept iff its
        # rank is at most the query's (a noised query's own clean block
        # ranks one above it), a noised key iff its rank is the query's
        # (only a noised query of its block has it).  Comparisons and
        # logical operations only: Mosaic has no select over booleans.
        q_rank = 2 * q_block + jnp.where(q_noised, 0, 1)
        k_rank = 2 * k_block + jnp.where(k_noised, 0, 1)
        keep = (k_rank <= q_rank) \
            & (q_rank <= jnp.where(k_noised, k_rank, _NEVER))
    else:
        keep = qg >= kg if mode == MASK_CAUSAL else qg > kg
    return jnp.where(keep, s, NEG_INF)


def block_contributes(mode, q_lo, q_hi, k_lo, k_hi=None):
    """Whether a key block spanning global positions ``[k_lo, k_hi]`` can
    contribute to queries spanning ``[q_lo, q_hi]`` under ``mode`` — the
    compute-skip predicate for blocks entirely outside the mask.  Static
    or traced positions, same contract as :func:`causal_mask`; the window
    and block diffusion read ``k_hi`` too, and block diffusion takes static
    positions only."""
    if mode == MASK_NONE:
        return True
    if mode == MASK_CAUSAL:
        return k_lo <= q_hi
    if mode == MASK_STRICT:
        return k_lo < q_hi
    if mode[0] == MASK_WINDOW:
        return (k_lo <= q_hi) & (q_lo - k_hi < mode[1])
    # Block diffusion: static positions only (the kernels walk lists made
    # from this, ``tile_lists``).  The noised and the clean part of each
    # span, as block indices:
    _, block_length, length = mode
    q_has_noised, q_has_clean = q_lo < length, q_hi >= length
    k_has_noised, k_has_clean = k_lo < length, k_hi >= length
    q_noised = (q_lo // block_length, min(q_hi, length - 1) // block_length)
    k_noised = (k_lo // block_length, min(k_hi, length - 1) // block_length)
    q_last_clean = (q_hi - length) // block_length
    k_first_clean = (max(k_lo, length) - length) // block_length
    same_block = q_has_noised and k_has_noised and \
        k_noised[0] <= q_noised[1] and q_noised[0] <= k_noised[1]
    earlier_clean = q_has_noised and k_has_clean and \
        k_first_clean < q_noised[1]
    block_causal = q_has_clean and k_has_clean and \
        k_first_clean <= q_last_clean
    return same_block or earlier_clean or block_causal


def block_full(mode, q_lo, q_hi, k_lo, k_hi):
    """Whether every pair of the tile is kept, so that the kernels need
    not apply the mask to it.  Static positions only (the tile lists)."""
    if mode == MASK_NONE:
        return True
    if mode == MASK_CAUSAL:
        return k_hi <= q_lo
    if mode == MASK_STRICT:
        return k_hi < q_lo
    if mode[0] == MASK_WINDOW:
        return k_hi <= q_lo and q_hi - k_lo < mode[1]
    _, block_length, length = mode
    if (q_lo < length) != (q_hi < length) or \
            (k_lo < length) != (k_hi < length):
        return False                      # a span over both copies
    q_noised, q_first = _half_block(q_lo, block_length, length)
    _, q_last = _half_block(q_hi, block_length, length)
    k_noised, k_first = _half_block(k_lo, block_length, length)
    _, k_last = _half_block(k_hi, block_length, length)
    if q_noised and k_noised:
        return q_first == q_last == k_first == k_last
    if q_noised:
        return k_last < q_first
    return not k_noised and k_last <= q_first


#: Rows of a tile list (``tile_lists``); a column is one grid step.
ROW, TILE, EDGE, FIRST, LAST, HEAD = range(6)


@functools.lru_cache(maxsize=None)
def _mask_tiles(mode, seq: int, block_q: int, block_k: int):
    """``(kept, edge)``, boolean ``[nq, nk]``: the tiles that hold a pair
    the mask keeps, and those of them the mask cuts through.  A query or
    key tile the mask leaves nothing (``MASK_STRICT`` on tiles of one
    position) keeps its first tile, on the edge: every pair of it is
    masked, and its output is written as zeros."""
    nq, nk = seq // block_q, seq // block_k
    kept, edge = np.zeros((nq, nk), bool), np.zeros((nq, nk), bool)
    for qi in range(nq):
        q_lo, q_hi = qi * block_q, (qi + 1) * block_q - 1
        for ki in range(nk):
            k_lo, k_hi = ki * block_k, (ki + 1) * block_k - 1
            if block_contributes(mode, q_lo, q_hi, k_lo, k_hi):
                kept[qi, ki] = True
                edge[qi, ki] = not block_full(mode, q_lo, q_hi, k_lo, k_hi)
    empty = np.zeros_like(kept)
    empty[~kept.any(axis=1), 0] = empty[0, ~kept.any(axis=0)] = True
    return kept | empty, edge | empty


@functools.lru_cache(maxsize=None)
def tile_lists(mode, seq: int, block_q: int, block_k: int, group: int = 1):
    """What the kernels walk: ``(by_query, by_key)``, two ``int32`` numpy
    tables with one column for every grid step and the rows ``ROW``,
    ``TILE``, ``EDGE``, ``FIRST``, ``LAST``, ``HEAD``.

    ``by_query`` (forward and dQ) lists every tile the mask keeps once, in
    the order of the query tiles: ``ROW`` is the query tile, ``TILE`` the
    key tile it reads, ``EDGE`` 1 where the mask has to be applied and 0
    where the tile lies wholly inside it, ``FIRST`` and ``LAST`` 1 on the
    first and the last step of the query tile; ``HEAD`` is 0.  ``by_key``
    (dK/dV) is its transpose for ``group`` query heads a key/value head:
    ``ROW`` is the key tile, and its steps are the heads of the group
    (``HEAD``) one after the other, for each the query tiles (``TILE``)
    that read the key tile.  Tiles outside the mask are in neither list:
    they cost no product, no copy and no grid step."""
    kept, edge = _mask_tiles(mode, seq, block_q, block_k)

    def walk(kept, edge, heads):
        steps = []
        for row in range(kept.shape[0]):
            visits = [(head, tile) for head in range(heads)
                      for tile in np.flatnonzero(kept[row])]
            steps += [(row, tile, edge[row, tile], n == 0,
                       n == len(visits) - 1, head)
                      for n, (head, tile) in enumerate(visits)]
        return np.asarray(steps, np.int32).T

    return walk(kept, edge, 1), walk(kept.T, edge.T, group)


def grid_steps(mode, seq: int, block_q: int, block_k: int, heads: int,
               kv_heads: int):
    """``(steps, tiles)`` of one sequence through the three kernels: the
    grid steps they launch along their sequential dimension (the lengths
    of the lists their grids are sized from) and the tiles they compute
    (those the mask keeps, once in each kernel for every query head)."""
    by_query, by_key = tile_lists(mode, seq, block_q, block_k,
                                  heads // kv_heads)
    kept, _ = _mask_tiles(mode, seq, block_q, block_k)
    return (2 * heads * by_query.shape[1] + kv_heads * by_key.shape[1],
            3 * heads * int(kept.sum()))


def online_softmax_block(s, v, m_ref, l_ref, acc_ref):
    """One FlashAttention-2 online-softmax accumulation step: fold score
    tile ``s`` [Bq, Bk] and value block ``v`` [Bk, D] into the running
    (max ``m_ref``, sum ``l_ref``, accumulator ``acc_ref``) VMEM scratch
    carried across the sequential K-block grid dimension.  For
    serve/paged_attention alone, whose score tiles are one block of the
    pool wide, a fraction of a lane group: the statistics are ``[:, :1]``
    columns broadcast over the lanes.  The training forward kernel has its own step
    (``_fwd_step``), which keeps them dense over tiles of whole lane
    groups.

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
        p.astype(v.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def online_softmax_flush(m_ref, l_ref, acc_ref):
    """Finalize the online softmax: returns ``(out [Bq, D], lse
    [Bq, LANES])`` from the scratch state after the last contributing
    block; the logsumexp stays lane-broadcast like the state it is made
    from.  :func:`online_softmax_block`'s flush, for
    serve/paged_attention alone."""
    l_final = jnp.maximum(l_ref[...], 1e-30)
    return acc_ref[...] / l_final[:, :1], m_ref[...] + jnp.log(l_final)


def _col_to_row(x):
    """``[Bq, LANES]`` lane-broadcast per-row statistic → ``[1, Bq]`` row,
    the layout it is stored in (see ``_flash_fwd``)."""
    return x.T[:1]


def _row_to_lanes(row):
    """``[1, Bq]`` stored statistic → ``[Bq, LANES]``, lane-broadcast;
    its ``[:, :1]`` is the column that broadcasts against a ``[Bq, Bk]``
    score tile."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _scaled(q_ref, scale):
    """The query tile times ``scale``, computed in float32 and handed to
    the MXU in the tile's own dtype (bf16 stays bf16)."""
    return (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)


def _scores(q, k):
    return jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [Bq, Bk]


def _on_tile(edge, step):
    """Run ``step(masked)`` for the tile of this grid step: with the mask
    where the tile crosses its edge, without where it lies wholly
    inside."""
    pl.when(edge == 1)(functools.partial(step, True))
    pl.when(edge == 0)(functools.partial(step, False))


def _state_lanes(block_k):
    """Width of the forward kernel's per-row statistics: the lane width
    where the key tile is whole lane groups, else the key tile's own."""
    return LANES if block_k % LANES == 0 else block_k


def _at_width(x, width):
    """A ``[Bq, lanes]`` statistic that is the same in every lane, at
    ``width`` lanes (the accumulator's head size)."""
    if width <= x.shape[1]:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _fwd_step(s, v, m_ref, l_ref, acc_ref):
    """One online-softmax step of the forward kernel: fold score tile ``s``
    [Bq, Bk] and value block ``v`` [Bk, D] into the running state, all of it
    float32 VMEM scratch ``_state_lanes(Bk)`` wide and dense across the
    lanes from load to store: ``m_ref`` holds a row's maximum and ``l_ref``
    its sum in every lane.  The maximum (the sum) is the elementwise
    maximum (sum) of the tile's lane groups, then one lane reduction whose
    result is in every lane, so the exponent's argument, the correction and
    the stores are register for register: no column, no lane broadcast.

    The running maximum starts at the floor ``NEG_INF / 2``
    (``_fwd_kernel``), so a row with EVERY key masked contributes
    ``p = exp(NEG_INF - NEG_INF/2) = 0`` and its output is written as
    zeros; see :func:`online_softmax_block`, whose results these are, bit
    for bit."""
    lanes = m_ref.shape[1]
    groups = [s[:, c:c + lanes] for c in range(0, s.shape[1], lanes)]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(
        functools.reduce(jnp.maximum, groups), axis=1, keepdims=True))
    p = [jnp.exp(group - m_new) for group in groups]
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(
        functools.reduce(jnp.add, p), axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * _at_width(corr, acc_ref.shape[1]) \
        + jax.lax.dot_general(
            jnp.concatenate(p, axis=1).astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fwd_flush(m_ref, l_ref, acc_ref):
    """``(out [Bq, D], lse [Bq, lanes])`` from ``_fwd_step``'s state after
    a query tile's last key tile; the (natural) logsumexp is lane-broadcast
    like the state it is made from."""
    l_final = jnp.maximum(l_ref[...], 1e-30)
    return (acc_ref[...] / _at_width(l_final, acc_ref.shape[1]),
            m_ref[...] + jnp.log(l_final))


def _fwd_kernel(tiles_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, qs, acc, m,
                l, *, scale: float, mask_mode, block_q: int, block_k: int):
    t = pl.program_id(1)

    @pl.when(tiles_ref[FIRST, t] == 1)
    def _init():
        qs[...] = _scaled(q_ref, scale)
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF / 2)
        l[...] = jnp.zeros_like(l)

    def _step(masked):
        s = _scores(qs[...], k_ref[0])
        if masked:
            s = causal_mask(s, tiles_ref[ROW, t] * block_q,
                            tiles_ref[TILE, t] * block_k, mask_mode)
        _fwd_step(s, v_ref[0], m, l, acc)

    _on_tile(tiles_ref[EDGE, t], _step)

    @pl.when(tiles_ref[LAST, t] == 1)
    def _flush():
        out, lse = _fwd_flush(m, l, acc)
        o_ref[0] = out.astype(o_ref.dtype)
        lse_ref[0] = _col_to_row(lse)


def _probs_and_ds(q, k_ref, v_ref, do_ref, lse, delta, q_offset, k_offset,
                  mask_mode, masked):
    """``(p, ds)`` of one tile, both ``[Bq, Bk]`` float32, recomputed from
    the saved logsumexp; ``lse`` and ``delta`` are ``[Bq, 1]`` columns."""
    s = _scores(q, k_ref[0])
    if masked:
        s = causal_mask(s, q_offset, k_offset, mask_mode)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _bwd_dq_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, qs, lse_lanes, delta_lanes, dq_acc, *,
                   scale: float, mask_mode, block_q: int, block_k: int):
    t = pl.program_id(1)

    # What is constant along a query tile's key tiles is made on its
    # first step: the scaled queries and the two statistics as columns.
    @pl.when(tiles_ref[FIRST, t] == 1)
    def _init():
        qs[...] = _scaled(q_ref, scale)
        lse_lanes[...] = _row_to_lanes(lse_ref[0])
        delta_lanes[...] = _row_to_lanes(delta_ref[0])
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _step(masked):
        _, ds = _probs_and_ds(
            qs[...], k_ref, v_ref, do_ref, lse_lanes[:, :1],
            delta_lanes[:, :1], tiles_ref[ROW, t] * block_q,
            tiles_ref[TILE, t] * block_k, mask_mode, masked)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _on_tile(tiles_ref[EDGE, t], _step)

    @pl.when(tiles_ref[LAST, t] == 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(tiles_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, mask_mode, block_q: int, block_k: int):
    # A key tile's steps walk the group's query heads and, for each, the
    # query tiles that read the key tile: dK and dV of a key/value head
    # are summed over its query heads here, in VMEM.
    t = pl.program_id(1)

    @pl.when(tiles_ref[FIRST, t] == 1)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _step(masked):
        q = _scaled(q_ref, scale)
        p, ds = _probs_and_ds(
            q, k_ref, v_ref, do_ref, _row_to_lanes(lse_ref[0])[:, :1],
            _row_to_lanes(delta_ref[0])[:, :1],
            tiles_ref[TILE, t] * block_q, tiles_ref[ROW, t] * block_k,
            mask_mode, masked)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Bk, Dv]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [Bk, D]

    _on_tile(tiles_ref[EDGE, t], _step)

    @pl.when(tiles_ref[LAST, t] == 1)
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
        dimension_semantics=("parallel", "arbitrary"))


def _stat_spec(block_q, index_map):
    """BlockSpec of a per-row statistic (logsumexp, delta).  They are kept
    as ``[BH, 1, S]`` rows so that a block's last two dimensions are the
    whole unit dimension and a lane-dense ``block_q`` — a ``(1, block_q)``
    block of a ``[BH, S]`` array is refused by the TPU lowering."""
    return pl.BlockSpec((1, 1, block_q), index_map)


def vary_like(x, like):
    """``x`` typed as varying over every mesh axis ``like`` varies over
    (inside ``shard_map``): Pallas asks it of every operand of a call, and
    a ``custom_vjp`` of every cotangent against its primal.  The cast's own
    transpose is the sum over the axis, which is what a replicated
    operand's gradient needs."""
    x = jnp.asarray(x)
    missing = tuple(a for a in jax.typeof(like).vma
                    if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _by_query_maps(group):
    """Index maps of a kernel that walks ``tile_lists``' ``by_query``:
    ``(query tile, key/value tile, statistic)`` of grid step ``t``."""
    return (lambda bh, t, tiles: (bh, tiles[ROW, t], 0),
            lambda bh, t, tiles: (bh // group, tiles[TILE, t], 0),
            lambda bh, t, tiles: (bh, 0, tiles[ROW, t]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, mask_mode, scale, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, mask_mode, scale, block_q, block_k,
                        interpret)
    return out


def _flash_fwd(q, k, v, mask_mode, scale, block_q, block_k, interpret):
    """``q`` is ``[B*H, S, D]``, ``k`` ``[B*Hkv, S, D]`` and ``v`` ``[B*Hkv,
    S, Dv]`` with ``Hkv`` dividing ``H``: query head ``h`` reads key/value
    head ``h // (H / Hkv)`` through the index maps, no copy.  The output
    and its accumulator are ``Dv`` wide."""
    BH, S, D = q.shape
    Dv = v.shape[2]
    group = BH // k.shape[0]
    tiles, _ = tile_lists(mask_mode, S, block_q, block_k, group)
    q_map, kv_map, stat_map = _by_query_maps(group)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, mask_mode=mask_mode,
                          block_q=block_q, block_k=block_k),
        out_shape=[_out_struct((BH, S, Dv), q.dtype, q),
                   _out_struct((BH, 1, S), jnp.float32, q)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, tiles.shape[1]),
            in_specs=[
                pl.BlockSpec((1, block_q, D), q_map),
                pl.BlockSpec((1, block_k, D), kv_map),
                pl.BlockSpec((1, block_k, Dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dv), q_map),
                _stat_spec(block_q, stat_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), q.dtype),
                pltpu.VMEM((block_q, Dv), jnp.float32),
                pltpu.VMEM((block_q, _state_lanes(block_k)), jnp.float32),
                pltpu.VMEM((block_q, _state_lanes(block_k)), jnp.float32),
            ]),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="hvd_flash_fwd",
    )(vary_like(tiles, q), q, k, v)
    # Named for ``jax.checkpoint`` policies: a caller that recomputes a
    # layer in its backward pass can keep these two (``save_only_these_
    # names(*SAVED)``) and spare the forward kernel's second run.
    out = checkpoint_name(out, SAVED[0])
    lse = checkpoint_name(lse.reshape(BH, S), SAVED[1])
    return out, (q, k, v, out, lse)


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
    Dv = v.shape[2]
    group = BH // k.shape[0]
    by_query, by_key = tile_lists(mask_mode, S, block_q, block_k, group)
    lse, delta = lse.reshape(BH, 1, S), delta.reshape(BH, 1, S)

    q_map, kv_map, stat_map = _by_query_maps(group)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, mask_mode=mask_mode,
                          block_q=block_q, block_k=block_k),
        out_shape=_out_struct((BH, S, D), q.dtype, q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, by_query.shape[1]),
            in_specs=[
                pl.BlockSpec((1, block_q, D), q_map),
                pl.BlockSpec((1, block_k, D), kv_map),
                pl.BlockSpec((1, block_k, Dv), kv_map),
                pl.BlockSpec((1, block_q, Dv), q_map),
                _stat_spec(block_q, stat_map),
                _stat_spec(block_q, stat_map),
            ],
            out_specs=pl.BlockSpec((1, block_q, D), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, D), q.dtype),
                            pltpu.VMEM((block_q, LANES), jnp.float32),
                            pltpu.VMEM((block_q, LANES), jnp.float32),
                            pltpu.VMEM((block_q, D), jnp.float32)]),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="hvd_flash_bwd_dq",
    )(vary_like(by_query, q), q, k, v, do, lse, delta)

    # One program per key/value head; the sequential dimension is (key
    # tile) x (query head of the group) x (query tile that reads it).
    q_of = lambda bkv, t, tiles: (
        bkv * group + tiles[HEAD, t], tiles[TILE, t], 0)
    kv_of = lambda bkv, t, tiles: (bkv, tiles[ROW, t], 0)
    stat_of = lambda bkv, t, tiles: (
        bkv * group + tiles[HEAD, t], 0, tiles[TILE, t])
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale,
                          mask_mode=mask_mode, block_q=block_q,
                          block_k=block_k),
        out_shape=[_out_struct(k.shape, k.dtype, k),
                   _out_struct(v.shape, v.dtype, v)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k.shape[0], by_key.shape[1]),
            in_specs=[
                pl.BlockSpec((1, block_q, D), q_of),
                pl.BlockSpec((1, block_k, D), kv_of),
                pl.BlockSpec((1, block_k, Dv), kv_of),
                pl.BlockSpec((1, block_q, Dv), q_of),
                _stat_spec(block_q, stat_of),
                _stat_spec(block_q, stat_of),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, D), kv_of),
                pl.BlockSpec((1, block_k, Dv), kv_of),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, Dv), jnp.float32)]),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="hvd_flash_bwd_dkv",
    )(vary_like(by_key, q), q, k, v, do, lse, delta)
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


def _heads_first(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _all_heads_first(name, q, k, v):
    """``q``, ``k``, ``v`` ``[B, S, heads, D]`` head-major, for the calls
    that take that layout."""
    if not q.shape[0] == k.shape[0] == v.shape[0]:
        raise ValueError(f"{name}: queries, keys and values {q.shape}, "
                         f"{k.shape}, {v.shape} of different batches")
    return _heads_first(q), _heads_first(k), _heads_first(v)


def _checked(name, q, k, v, scale, block_q, block_k, interpret):
    """Defaults filled in and shapes checked for the public calls, on
    head-major ``q [B*H, S, D]``, ``k [B*Hkv, S, D]``, ``v [B*Hkv, S,
    Dv]``."""
    BH, S, D = q.shape
    if k.shape[0] != v.shape[0] or k.shape[1:] != (S, D) \
            or v.shape[1] != S or BH % k.shape[0]:
        raise ValueError(
            f"{name}: keys and values {k.shape}, {v.shape} do not fit "
            f"queries {q.shape}: keys as wide as the queries, as many value "
            f"heads as key heads, and their number must divide the query "
            f"heads")
    block_q, block_k = min(block_q, S), min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(
            f"{name} requires seq len {S} divisible by block sizes "
            f"({block_q}, {block_k})")
    return (scale if scale is not None else 1.0 / math.sqrt(D),
            block_q, block_k,
            jax.default_backend() != "tpu" if interpret is None
            else interpret)


def flash_attention_heads_first(q: jax.Array, k: jax.Array, v: jax.Array,
                                *,
                                causal: bool = False,
                                mask_mode=None,
                                scale: Optional[float] = None,
                                block_q: int = 128,
                                block_k: int = 128,
                                interpret: Optional[bool] = None
                                ) -> jax.Array:
    """:func:`flash_attention` in the kernels' own layout: ``q [B*H, S,
    D]``, ``k [B*Hkv, S, D]``, ``v [B*Hkv, S, Dv]`` (a batch's heads
    together, ``Hkv`` dividing ``H``) to ``[B*H, S, Dv]``, for a caller
    that makes its operands head-major itself (``qk_rope.py``) and so
    spares the transposes."""
    scale, block_q, block_k, interpret = _checked(
        "flash_attention_heads_first", q, k, v, scale, block_q, block_k,
        interpret)
    if mask_mode is None:
        mask_mode = MASK_CAUSAL if causal else MASK_NONE
    return _flash(q, k, v, mask_mode, scale, block_q, block_k, interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *,
                    causal: bool = False,
                    mask_mode=None,
                    scale: Optional[float] = None,
                    block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable flash attention over [B, S, H, D] (full local seq).

    ``v`` may be narrower or wider than ``q`` and ``k`` (``[B, S, Hkv,
    Dv]``): the output is ``[B, S, H, Dv]``.
    ``k`` and ``v`` may have fewer heads than ``q`` (``[B, S, Hkv, D]``,
    ``Hkv`` dividing ``H``): query head ``h`` reads key/value head
    ``h // (H / Hkv)``, the kernels address it through their index maps
    and sum dK and dV over the group, so K and V are never repeated in
    HBM.  ``mask_mode`` names the mask where ``causal`` cannot: one of the
    ``MASK_*`` modes, :func:`window_mask` or :func:`block_diffusion_mask`;
    tiles wholly outside it cost no product, no copy and no grid step.

    ``interpret=None`` auto-selects the Pallas interpreter off-TPU so the
    same call works in the CPU-mesh test environment.  In interpret mode
    under shard_map, pass ``check_vma=False`` to the shard_map (the
    interpreter inlines the kernel, mixing invariant loop indices with
    varying data); the compiled TPU path needs no such escape hatch."""
    B, S, H, _ = q.shape
    out = flash_attention_heads_first(
        *_all_heads_first("flash_attention", q, k, v), causal=causal,
        mask_mode=mask_mode, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out.reshape(B, H, S, v.shape[3]).transpose(0, 2, 1, 3)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        *,
                        mask_mode=MASK_NONE,
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
    per hop from the block owner), :func:`window_mask` or
    :func:`block_diffusion_mask`."""
    B, S, H, _ = q.shape
    heads_first = _all_heads_first("flash_attention_lse", q, k, v)
    scale, block_q, block_k, interpret = _checked(
        "flash_attention_lse", *heads_first, scale, block_q, block_k,
        interpret)
    out, lse = _flash_lse(*heads_first, mask_mode, scale, block_q, block_k,
                          interpret, out_dtype)
    return (out.reshape(B, H, S, v.shape[3]).transpose(0, 2, 1, 3),
            lse.reshape(B, H, S))
