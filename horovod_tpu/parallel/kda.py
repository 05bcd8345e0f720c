"""Kimi Delta Attention's recurrence as differentiable Pallas TPU kernels: a
gated delta rule with a decay for every channel, in its chunked form.

A head carries a state ``S [d, d]`` (keys by values) over the positions of
its sequence.  With ``q_t, k_t, v_t [d]``, ``g_t [d] <= 0`` the log of the
decay of every key channel and ``beta_t`` a scalar (``S_0 = 0``):

    S'_t = Diag(exp g_t) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - k_t^T S'_t)^T
    o_t  = S_t^T q_t

:func:`kda_recurrence` is that, a token at a time (the definition; the
tests and ``chip_smoke.py`` hold the kernels to it).  :func:`kda_scan` is
the chunked form (arXiv:2510.26692, section 3; the WY representation of the
delta rule with the decay folded into the keys).  Within a chunk of ``C``
positions that starts from the state ``S``, with ``G_i = sum_{t <= i} g_t``
counted from the chunk's start and ``e(i, j) = exp(G_i - G_j)`` a channel:

    P_ij = sum_c k_ic k_jc e(i, j)_c   for j < i,   A = Diag(beta) P
    M_ij = sum_c q_ic k_jc e(i, j)_c   for j <= i
    T    = (I + A)^-1
    w    = T (beta k exp G),   u = T (beta v),   u' = u - w S
    o    = (q exp G) S + M u'
    S   <- Diag(exp G_C) S + (k exp(G_C - G))^T u'

* **No exponent is above 0.**  ``e(i, j)`` is a product of a factor of row
  ``i`` and one of row ``j`` only against a reference row ``m`` between
  them, ``exp(G_i - G_m) exp(G_m - G_j)``, both at or under 1 whatever the
  decays (a decay of 100 a step, which wipes a channel's state, underflows
  to the zero it is).  One reference serves all pairs it parts, so the
  chunk is halved again and again: at the level of half-length ``s`` a
  block of ``2 s`` rows has the last row of its lower half as reference,
  its upper rows on the query side and its lower rows on the key side, and
  the level's product ``[2C, d] x [d, C]`` is kept where row and column lie
  in one block.  Every pair ``j < i`` is parted at exactly one of the
  ``log2 C`` levels; ``M``'s diagonal is ``q_i . k_i``.  All exponents, and
  ``G`` itself, are ONE product of a 0/1 matrix with ``g`` (``_sums``);
  ``exp G`` and ``exp(G_C - G)`` are at or under 1 by themselves.
* **The triangular system on the MXU, by blocks.**  ``A`` is strictly lower
  triangular; ``(I + A)^-1`` is built from single rows up, ``[[T11, 0],
  [-T22 A21 T11, T22]]`` a level, ``log2 C`` levels of two ``C x C``
  products (``_tri_inverse`` says why not the nilpotent product).
* **Precision.**  ``G``, the state, ``A``, ``T``, ``w`` and ``u`` are
  float32.  The 0/1 matrix of the exponents is bf16, which holds it
  exactly, and its products (``g`` forward, the exponents' gradient
  backward) are three bf16 passes, one for each of the float32 operand's
  exact bf16 pieces (``_exact_sums_product``): float32's precision at half
  the passes ``Precision.HIGHEST`` takes, which splits both operands.
  Every other product takes its operands in the dtype of ``q`` (bf16) and
  accumulates in float32.
* **Forward** (``hvd_kda_fwd``) — grid ``(heads, S / block)``, the second
  dimension sequential: a grid step holds ``block`` rows of one head's 128
  lanes of ``q, k, v, g [S, heads x d]`` (no head-major copy: a head is a
  lane block), walks their chunks with the state, transposed (values by
  keys, so that the decay scales its lanes), in VMEM scratch, and writes
  ``o`` and the state at the start of every chunk (``[heads, S / C, d,
  d]`` float32: the residual the backward pass reads), both named
  (``SAVED``) for a caller's ``jax.checkpoint`` policy to keep.
* **Backward** (``hvd_kda_bwd``) — the same grid walked in reverse: a chunk
  recomputes its ``G, M, P, T, w, u, u'`` from its operands and its saved
  starting state, takes ``dO`` and the gradient of the state it hands on,
  and returns ``dq, dk, dv, dg, dbeta`` and the gradient of its starting
  state.  The gradient through ``T`` is ``dA = -(T^T dw) w^T - (T^T du)
  u^T``; ``dg`` is the reverse cumulative sum of ``dG`` within the chunk
  (``G`` starts anew in every chunk: the decay between chunks goes through
  ``G_C``).
* **A grid step walks the chunks of its block** (``BLOCK`` rows, 8 chunks)
  and the compiler schedules ``UNROLL`` of them as one block of
  instructions: a chunk is a chain of some thirty dependent products, and
  most of the next chunk's (its decays, pairs and triangular system need no
  state) fills the gaps.  On the v5e a chunk alone takes 2.33 us forward
  and 3.38 backward with all 8 unrolled (``chip_smoke.py --phase kda``;
  2.58 and 3.66 with the 0/1 matrix's products at ``Precision.HIGHEST``,
  2.80 and 3.55 at one chunk a loop iteration before that; blocks of 256
  or 1,024 rows read the same within 2 %).
* ``beta`` and ``dbeta`` go in and out as rows ``[heads, 1, S]`` and are
  turned to columns a block at a time, as ``flash.py`` keeps its
  logsumexp.

:func:`chunks` counts what the kernels launch, for the benchmark
(``flash.grid_steps``' kind).  ``jax.custom_vjp`` ties the two kernels
together; ``interpret=None`` picks the Pallas interpreter off the TPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import LANES, _col_to_row, _out_struct, _row_to_lanes

CHUNK = 64      # positions a chunk, a power of two
BLOCK = 512     # rows a grid step
UNROLL = 8      # chunks of a block the compiler schedules as one
SAVED = ("hvd_kda_out", "hvd_kda_states")  # checkpoint names, _kda_fwd


def kda_recurrence(q, k, v, g, beta, state=None):
    """The definition, a token at a time under ``lax.scan``, float32: ``q,
    k, v, g [S, heads, d]``, ``beta [S, heads]`` -> ``(o [S, heads, d],
    final state [heads, d, d])``."""
    f32 = lambda x: x.astype(jnp.float32)
    _, heads, d = q.shape
    if state is None:
        state = jnp.zeros((heads, d, d), jnp.float32)

    def step(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hk,hkv->hv", k_t, state,
                          precision=lax.Precision.HIGHEST)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - read)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision=lax.Precision.HIGHEST)

    state, o = lax.scan(step, state, (f32(q), f32(k), f32(v), f32(g),
                                      f32(beta)))
    return o, state


def chunks(seq: int, chunk: int = CHUNK, heads: int = 1,
           block: int = BLOCK):
    """``(steps, chunks)`` of one sequence through one scan kernel, the
    forward and the backward kernel alike: the grid steps it launches along
    its sequential dimension over all heads, and the chunks it computes."""
    return heads * (seq // _block_rows(seq, chunk, block)), \
        heads * (seq // chunk)


def _block_rows(seq, chunk, block):
    """Rows a grid step: ``block`` where it divides the sequence, else the
    whole sequence (the small sizes of the tests)."""
    if seq % chunk:
        raise ValueError(f"kda_scan requires seq len {seq} divisible by the "
                         f"chunk of {chunk}")
    block = max(block - block % chunk, chunk)
    return block if seq % block == 0 else seq


# -- one chunk -----------------------------------------------------------------

def _dot(a, b, contract=(1, 0), dtype=None):
    if dtype is not None:
        a, b = a.astype(dtype), b.astype(dtype)
    return lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


_NT, _TN = (1, 1), (0, 0)   # a b^T, a^T b


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _tri_inverse(a):
    """``(I + a)^-1`` of a strictly lower triangular ``a [C, C]``, by
    blocks: with ``T`` the inverse of the diagonal blocks of ``s`` rows, the
    blocks of ``2 s`` have ``[[T11, 0], [-T22 a21 T11, T22]]``; from single
    rows (``T = I``) up, ``log2 C`` levels of two products, every level's
    ``a21`` blocks at once (``T`` is block diagonal, so ``T (a * mask) T``
    lands each where it belongs).  Forward substitution in another order:
    it multiplies entries of the inverse, which stay bounded, where powers
    of ``a`` (the nilpotent product ``(I - a)(I + a^2)(I + a^4) ...``) grow
    combinatorially once the keys of a chunk resemble each other and lose
    every digit to cancellation."""
    size = a.shape[0]
    i, j = _iota(a.shape, 0), _iota(a.shape, 1)
    inverse = (i == j).astype(jnp.float32)
    for s in reversed(_levels(size)):
        below = (i - (i & (2 * s - 1)) == j - (j & (2 * s - 1))) \
            & ((i & s) != 0) & ((j & s) == 0)
        inverse = inverse - _dot(
            _dot(inverse, jnp.where(below, a, 0.0)), inverse)
    return inverse


def _levels(size):
    """Half-lengths of the blocks a chunk of ``size`` rows is halved into,
    down to single rows: 32, 16, 8, 4, 2, 1 for 64."""
    return [size >> n for n in range(1, size.bit_length())]


def _sums(size):
    """The 0/1 matrix ``[(1 + levels) x C, C]`` whose product with ``g`` is,
    block by block of ``C`` rows: ``G`` (row ``i`` sums ``t <= i``), and for
    every level of half-length ``s`` the exponent of row ``i`` against its
    reference row ``m``, the last row of the lower half of the block of
    ``2 s`` rows that holds ``i``: ``G_i - G_m`` (``m < t <= i``) for a row
    of the upper half, ``G_m - G_i`` (``i < t <= m``) for one of the lower.
    Sums of ``g <= 0`` only: no exponent is above 0.  In bf16, which holds
    0 and 1 exactly: its products with float32 operands are three passes
    of their exact pieces (:func:`_exact_sums_product`)."""
    i, t = _iota((size, size), 0), _iota((size, size), 1)
    blocks = [t <= i]
    for s in _levels(size):
        m = i - (i & (2 * s - 1)) + (s - 1)
        blocks.append((t > jnp.minimum(i, m)) & (t <= jnp.maximum(i, m)))
    return jnp.concatenate([b.astype(jnp.bfloat16) for b in blocks], 0)


def _split(x):
    """``(hi, mid, lo)``, three bf16 pieces of the float32 ``x`` whose sum
    is ``x`` exactly: each takes the leading 8 bits of what the ones before
    left, and 3 x 8 bits hold float32's 24."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _exact_sums_product(sums, x, contract=(1, 0)):
    """The product of the 0/1 matrix ``sums`` (bf16, which holds it
    exactly) with the float32 ``x`` to float32's precision: three bf16
    passes, one a piece of ``x`` (:func:`_split`), summed smallest first.
    ``Precision.HIGHEST`` would split both operands and run six."""
    hi, mid, lo = (_dot(sums, piece, contract) for piece in _split(x))
    return hi + (mid + lo)


def _chunk_parts(q, k, v, g, beta, state, dtype):
    """What forward and backward share of a chunk: ``q, k, v [C, d]``,
    ``g [C, d]`` float32, ``beta [C, 1]``, ``state [d, d]`` transposed
    (values by keys); a dict of the intermediates named as above."""
    size, d = g.shape
    f32 = jnp.float32
    row = _iota((size, 1), 0)
    # Row and column of a pair, for the queries' rows and then the keys'.
    rows = _iota((2 * size, size), 0) & (size - 1)
    cols = _iota((2 * size, size), 1)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    sums = _sums(size)
    exponents = _exact_sums_product(sums, g)
    cumulative = exponents[:size]
    total = cumulative[size - 1][None, :]
    z = dict(qf=qf, kf=kf, vf=vf, sums=sums, levels=[],
             decay=jnp.exp(cumulative), total=jnp.exp(total),
             to_end=jnp.exp(total - cumulative))
    # A pair (i, j < i) is in exactly one level: the one whose halving parts
    # them, i above and j below its reference row.
    qk = jnp.concatenate([qf, kf], 0)
    pairs = jnp.zeros((2 * size, size), f32)
    for n, s in enumerate(_levels(size)):
        factor = jnp.exp(exponents[(n + 1) * size:(n + 2) * size])
        above = jnp.where((row & s) != 0, factor, 0.0)
        below = factor - above
        sides = (qk * jnp.concatenate([above, above], 0)).astype(dtype)
        keys = (kf * below).astype(dtype)
        together = (rows - (rows & (2 * s - 1))) \
            == (cols - (cols & (2 * s - 1)))
        pairs = pairs + jnp.where(together, _dot(sides, keys, _NT), 0.0)
        z["levels"].append((above, below, sides, keys, together))
    z["diagonal"] = (rows == cols)[:size]
    z["m"] = pairs[:size] + jnp.where(
        z["diagonal"], jnp.sum(qf * kf, axis=1, keepdims=True), 0.0)
    z["p"] = pairs[size:]
    z["t"] = _tri_inverse(beta * z["p"])
    z["bw"], z["bu"] = beta * kf * z["decay"], beta * vf
    z["wu"] = _dot(z["t"], jnp.concatenate([z["bw"], z["bu"]], 1))
    z["w"], u = z["wu"][:, :d], z["wu"][:, d:]
    z["qg"] = qf * z["decay"]
    through = _dot(jnp.concatenate([z["qg"], z["w"]], 0), state, _NT,
                   dtype)                                 # [2C, d]
    z["from_state"] = through[:size]
    z["u2"] = u - through[size:]
    z["kd"] = kf * z["to_end"]
    return z


def _chunk_forward(q, k, v, g, beta, state, dtype):
    """``(o [C, d] float32, the state after the chunk)``."""
    z = _chunk_parts(q, k, v, g, beta, state, dtype)
    o = z["from_state"] + _dot(z["m"], z["u2"], dtype=dtype)
    return o, z["total"] * state + _dot(z["u2"], z["kd"], _TN, dtype)


def _chunk_backward(q, k, v, g, beta, state, do, dstate, dtype):
    """``(dq, dk, dv, dg [C, d], dbeta [C, 1], gradient of the chunk's
    starting state)`` from ``do [C, d]`` and the gradient ``dstate`` of the
    state the chunk hands on, all float32."""
    z = _chunk_parts(q, k, v, g, beta, state, dtype)
    size, d = g.shape
    qf, kf = z["qf"], z["kf"]
    do = do.astype(jnp.float32)
    du2 = _dot(z["m"], do, _TN, dtype) + _dot(z["kd"], dstate, _NT, dtype)
    dm = _dot(do, z["u2"], _NT, dtype)
    dqg = _dot(do, state, dtype=dtype)
    dkd = _dot(z["u2"], dstate, dtype=dtype)
    dw = -_dot(du2, state, dtype=dtype)
    dstate0 = z["total"] * dstate + _dot(do, z["qg"], _TN, dtype) \
        - _dot(du2, z["w"], _TN, dtype)
    dtotal = z["total"] * jnp.sum(state * dstate, axis=0, keepdims=True) \
        + jnp.sum(dkd * z["kd"], axis=0, keepdims=True)
    db = _dot(z["t"], jnp.concatenate([dw, du2], 1), _TN)     # [C, 2d]
    dbw, dbu = db[:, :d], db[:, d:]
    da = -_dot(db, z["wu"], _NT, dtype)
    dbeta = jnp.sum(da * z["p"], axis=1, keepdims=True) + jnp.sum(
        dbw * kf * z["decay"] + dbu * z["vf"], axis=1, keepdims=True)
    # What of dm and da lies outside a level's pairs meets a zero factor.
    dpairs = jnp.concatenate([dm, beta * da], 0)                # [2C, C]
    on_diagonal = jnp.sum(jnp.where(z["diagonal"], dm, 0.0), axis=1,
                          keepdims=True)
    dq = dqg * z["decay"] + on_diagonal * kf
    dk = beta * dbw * z["decay"] + dkd * z["to_end"] + on_diagonal * qf
    dcumulative = dqg * z["qg"] + dbw * z["bw"] - dkd * z["kd"]
    dexponents = [dcumulative + jnp.where(
        _iota((size, 1), 0) == size - 1, dtotal, 0.0)]
    for above, below, sides, keys, together in z["levels"]:
        mine = jnp.where(together, dpairs, 0.0).astype(dtype)
        dsides = _dot(mine, keys) * jnp.concatenate([above, above], 0)
        dkeys = _dot(mine, sides, _TN) * below
        dq = dq + dsides[:size]
        dk = dk + dsides[size:] + dkeys
        dexponents.append(qf * dsides[:size] + kf * (dsides[size:] + dkeys))
    dg = _exact_sums_product(z["sums"], jnp.concatenate(dexponents, 0), _TN)
    return dq, dk, beta * dbu, dg, dbeta, dstate0


# -- the kernels ---------------------------------------------------------------

def _fwd_chunk(q_ref, k_ref, v_ref, g_ref, o_ref, states_ref, state,
               beta_col, chunk, c):
    rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    states_ref[0, c] = state[...]
    o, state[...] = _chunk_forward(
        q_ref[rows, :], k_ref[rows, :], v_ref[rows, :], g_ref[rows, :],
        beta_col[rows, :][:, :1], state[...], q_ref.dtype)
    o_ref[rows, :] = o.astype(o_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                state, beta_col, *, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    beta_col[...] = _row_to_lanes(beta_ref[0])
    _walk(q_ref.shape[0] // chunk, functools.partial(
        _fwd_chunk, q_ref, k_ref, v_ref, g_ref, o_ref, states_ref, state,
        beta_col, chunk), reverse=False)


def _walk(count, one, reverse):
    """``one(c)`` for the ``count`` chunks of a block in order (or in
    reverse), ``UNROLL`` of them a loop iteration: the compiler schedules
    an iteration as one block of instructions, so what chunk ``c + 1`` does
    before it needs the state (its decays, pairs, triangular system) fills
    the gaps of chunk ``c``'s chain of dependent products."""
    unroll = math.gcd(count, UNROLL)

    def several(n, carry):
        for j in range(unroll):
            c = n * unroll + j
            one(count - 1 - c if reverse else c)
        return carry

    lax.fori_loop(0, count // unroll, several, 0)


def _bwd_chunk(q_ref, k_ref, v_ref, g_ref, states_ref, do_ref, dq_ref, dk_ref,
               dv_ref, dg_ref, dstate, beta_col, dbeta_col, chunk, c):
    rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    dq, dk, dv, dg, dbeta, dstate[...] = _chunk_backward(
        q_ref[rows, :], k_ref[rows, :], v_ref[rows, :], g_ref[rows, :],
        beta_col[rows, :][:, :1], states_ref[0, c], do_ref[rows, :],
        dstate[...], q_ref.dtype)
    dq_ref[rows, :] = dq.astype(dq_ref.dtype)
    dk_ref[rows, :] = dk.astype(dk_ref.dtype)
    dv_ref[rows, :] = dv.astype(dv_ref.dtype)
    dg_ref[rows, :] = dg
    dbeta_col[rows, :] = jnp.broadcast_to(dbeta, (chunk, LANES))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, beta_col,
                dbeta_col, *, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    beta_col[...] = _row_to_lanes(beta_ref[0])
    _walk(q_ref.shape[0] // chunk, functools.partial(
        _bwd_chunk, q_ref, k_ref, v_ref, g_ref, states_ref, do_ref, dq_ref,
        dk_ref, dv_ref, dg_ref, dstate, beta_col, dbeta_col, chunk),
        reverse=True)
    dbeta_ref[0] = _col_to_row(dbeta_col[...])


def _compiler_params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _specs(d, rows, chunk, blocks, reverse):
    """``(lane block of a head, beta's row, the chunks' states)`` of grid
    step ``(head, i)``, the blocks of the sequence first to last or, for
    the backward kernel, last to first."""
    at = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    return (pl.BlockSpec((rows, d), lambda h, i: (at(i), h)),
            pl.BlockSpec((1, 1, rows), lambda h, i: (h, 0, at(i))),
            pl.BlockSpec((1, rows // chunk, d, d),
                         lambda h, i: (h, at(i), 0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, chunk, block, interpret):
    return _kda_fwd(q, k, v, g, beta, chunk, block, interpret)[0]


def _kda_fwd(q, k, v, g, beta, chunk, block, interpret):
    """``q, k, v, g [S, heads x d]``, ``beta [heads, 1, S]``."""
    seq, width = q.shape
    heads = beta.shape[0]
    d = width // heads
    rows = _block_rows(seq, chunk, block)
    blocks = seq // rows
    lane_block, beta_row, states = _specs(d, rows, chunk, blocks, False)
    o, saved = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        out_shape=[_out_struct((seq, width), q.dtype, q),
                   _out_struct((heads, seq // chunk, d, d), jnp.float32, q)],
        grid=(heads, blocks),
        in_specs=[lane_block] * 4 + [beta_row],
        out_specs=[lane_block, states],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="hvd_kda_fwd",
    )(q, k, v, g, beta)
    # Named for ``jax.checkpoint`` policies: a caller that recomputes a
    # layer in its backward pass can keep these two (``save_only_these_
    # names(*SAVED)``: ``o`` for what reads it there, the states for the
    # backward kernel) and spare the forward kernel's second run.
    o = checkpoint_name(o, SAVED[0])
    saved = checkpoint_name(saved, SAVED[1])
    return o, (q, k, v, g, beta, saved)


def _kda_bwd(chunk, block, interpret, res, do):
    q, k, v, g, beta, saved = res
    seq, width = q.shape
    heads = beta.shape[0]
    d = width // heads
    rows = _block_rows(seq, chunk, block)
    blocks = seq // rows
    lane_block, beta_row, states = _specs(d, rows, chunk, blocks, True)
    like = lambda x: _out_struct(x.shape, x.dtype, q)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        grid=(heads, blocks),
        in_specs=[lane_block] * 4 + [beta_row, states, lane_block],
        out_specs=[lane_block] * 4 + [beta_row],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="hvd_kda_bwd",
    )(q, k, v, g, beta, saved, do.astype(q.dtype))
    return dq, dk, dv, dg, dbeta


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, *, chunk: int = CHUNK, block: int = BLOCK,
             interpret: Optional[bool] = None) -> jax.Array:
    """``o [S, heads, d]`` of one sequence through the recurrence at the
    head of this module, from a zero state: ``q, k, v [S, heads, d]`` (one
    dtype, which the products run in), ``g [S, heads, d]`` the log of the
    decay (float32, at or under 0), ``beta [S, heads]``.  Differentiable in
    all five.  ``S`` is a multiple of ``chunk``, a power of two; ``d`` is
    the lane width, 128."""
    seq, heads, d = q.shape
    if not (k.shape == v.shape == g.shape == q.shape
            and beta.shape == (seq, heads)):
        raise ValueError(
            f"kda_scan: q, k, v, g {q.shape}, {k.shape}, {v.shape}, "
            f"{g.shape} must be one shape [S, heads, d] and beta "
            f"{beta.shape} its [S, heads]")
    if chunk & (chunk - 1) or not k.dtype == v.dtype == q.dtype:
        raise ValueError(f"kda_scan: a chunk of {chunk} is no power of two, "
                         f"or q, k, v differ in dtype")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    flat = lambda x: x.reshape(seq, heads * d)
    o = _kda(flat(q), flat(k), flat(v), flat(g.astype(jnp.float32)),
             beta.astype(jnp.float32).T[:, None, :], chunk, block,
             interpret)
    return o.reshape(seq, heads, d)
