"""Grouped matrix products as differentiable Pallas TPU kernels.

The expert products of a dropless mixture-of-experts layer
(``parallel/moe.py: dropless_expert_ffn``): the rows of ``lhs`` are sorted
by group (expert), ``group_sizes[g]`` rows belong to group ``g``, and each
group is multiplied by its own matrix of ``rhs``.  The shapes are static
and the work is not: the grid's sequential dimension is as long as the
row tiles the groups touch (a traced number, as in
``jax.experimental.pallas.ops.tpu.megablox``, whose layout of the tile
metadata this follows), so rows beyond the last group cost nothing and
their output is left unwritten.  Callers read only rows inside a group.

* ``gmm(lhs [m, k], rhs [G, k, n], group_sizes [G]) -> [m, n]``
* its gradients: ``gmm`` against the transposed matrices for ``lhs``, and
  ``tgmm`` (``lhs[rows of g]^T @ dout[rows of g]`` for every group) for
  ``rhs``.

Products run in the operands' dtype with float32 accumulation.  Unlike
megablox the calls carry their varying-axes type, so they run inside
``shard_map(check_vma=True)``, which ``hvd.shard_step`` and
``DistributedOptimizer`` rely on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import _out_struct, vary_like

#: Row, contraction and column tile: targets, cut to a divisor of the
#: dimension.  512 rows of 1,024 bf16 and a 1,024 x 1,024 block of weights,
#: double-buffered, with a float32 accumulator: about 9 MiB of VMEM.
TILES = (512, 1024, 1024)


def _tile(dim: int, target: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``target``; ``dim`` itself where it is smaller or no such tile is."""
    if dim <= target:
        return dim
    for tile in range(target - target % 128, 0, -128):
        if dim % tile == 0:
            return tile
    return dim


def row_tile(rows: int) -> int:
    """The row tile ``gmm`` uses for a buffer of at most ``rows`` rows: a
    caller pads its buffer to a multiple of this."""
    return min(TILES[0], -(-rows // 8) * 8)


def group_tiles(group_sizes, m: int, tm: int):
    """``(starts, ends, group_ids, tile_ids, count)``: the first and one
    past the last row of every group, and for each step of the sequential
    grid dimension the group and the row tile it works on.  A tile that
    holds rows of two groups is visited once for each, one after the
    other; an empty group is not visited.  ``count`` is the number of
    steps in use, at most ``m // tm + G - 1``."""
    num_groups = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    steps = m // tm + num_groups - 1
    group_ids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32), tiles,
                           total_repeat_length=steps)
    before = jnp.cumsum(tiles) - tiles
    tile_ids = first[group_ids] + jnp.arange(steps, dtype=jnp.int32) \
        - before[group_ids]
    return (starts, ends, group_ids, jnp.clip(tile_ids, 0, m // tm - 1),
            tiles.sum())


def _rows_of_group(starts, ends, group_ids, tile_ids, step, tm, width):
    """``[tm, width]`` mask of the tile's rows that belong to the step's
    group."""
    group = group_ids[step]
    rows = tile_ids[step] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, width), 0)
    return jnp.logical_and(rows >= starts[group], rows < ends[group])


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = row_tile(m), _tile(k, TILES[1]), _tile(n, TILES[2])
    if m % tm:
        raise ValueError(f"gmm: {m} rows are no multiple of the row tile "
                         f"{tm} (pad to row_tile(rows))")
    *meta, count = group_tiles(group_sizes, m, tm)
    tiles_k = k // tk
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else \
        (((1,), (0,)), ((), ()))

    def kernel(starts, ends, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref,
               acc):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], contract,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            mine = _rows_of_group(starts, ends, group_ids, tile_ids, step,
                                  tm, tn)
            out_ref[...] = jnp.where(
                mine, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)

    def rhs_map(n_i, step, k_i, starts, ends, group_ids, tile_ids):
        return (group_ids[step],) + ((n_i, k_i) if transpose_rhs
                                     else (k_i, n_i))

    return pl.pallas_call(
        kernel,
        out_shape=_out_struct((m, n), lhs.dtype, lhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, count, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, step, k_i, starts, ends,
                             group_ids, tile_ids: (tile_ids[step], k_i)),
                pl.BlockSpec(rhs_block, rhs_map),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, step, k_i, starts, ends, group_ids,
                tile_ids: (tile_ids[step], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=_params(interpret),
        interpret=interpret,
        name="hvd_gmm",
    )(*(vary_like(x, lhs) for x in meta), lhs, rhs)


def _tgmm_call(lhs, dout, group_sizes, interpret):
    """``out[g] = lhs[rows of g]^T @ dout[rows of g]``: ``[G, k, n]`` in
    ``lhs``'s dtype; an empty group's matrix is zero."""
    m, k = lhs.shape
    n = dout.shape[1]
    num_groups = group_sizes.shape[0]
    tm, tk, tn = row_tile(m), _tile(k, TILES[1]), _tile(n, TILES[2])
    *meta, count = group_tiles(group_sizes, m, tm)

    def kernel(starts, ends, group_ids, tile_ids, steps, lhs_ref, dout_ref,
               out_ref, acc):
        step = pl.program_id(2)
        group = group_ids[step]
        first = jnp.logical_or(
            step == 0, group_ids[jnp.maximum(step - 1, 0)] != group)
        last = jnp.logical_or(
            step == steps[0] - 1,
            group_ids[jnp.minimum(step + 1, steps[0] - 1)] != group)

        @pl.when(first)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        # Rows of another group, and rows beyond the last group, which
        # nothing ever wrote, count for nothing: zero them on both sides
        # (0 x NaN is NaN).
        def mine(ref):
            keep = _rows_of_group(starts, ends, group_ids, tile_ids, step,
                                  tm, ref.shape[1])
            return jnp.where(keep, ref[...], jnp.zeros_like(ref[...]))

        acc[...] += jax.lax.dot_general(
            mine(lhs_ref), mine(dout_ref), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    out = pl.pallas_call(
        kernel,
        out_shape=_out_struct((num_groups, k, n), lhs.dtype, lhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tk, n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k_i, n_i, step, starts, ends,
                             group_ids, tile_ids, steps: (tile_ids[step], k_i)),
                pl.BlockSpec((tm, tn), lambda k_i, n_i, step, starts, ends,
                             group_ids, tile_ids, steps: (tile_ids[step], n_i)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda k_i, n_i, step, starts, ends,
                group_ids, tile_ids, steps: (group_ids[step], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="hvd_tgmm",
    )(*(vary_like(x, lhs) for x in meta + [count[None]]), lhs, dout)
    return jnp.where((group_sizes > 0)[:, None, None], out,
                     jnp.zeros_like(out))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    return _gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, transpose_rhs, interpret):
    return (_gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(transpose_rhs, interpret, res, dout):
    lhs, rhs, group_sizes = res
    dlhs = _gmm_call(dout, rhs, group_sizes, not transpose_rhs, interpret)
    drhs = _tgmm_call(lhs, dout, group_sizes, interpret)
    return dlhs, (drhs.swapaxes(1, 2) if transpose_rhs else drhs), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
        transpose_rhs: bool = False, interpret=None) -> jax.Array:
    """``out[rows of g] = lhs[rows of g] @ rhs[g]`` (``@ rhs[g]^T`` with
    ``transpose_rhs``) for every group ``g``; differentiable in ``lhs``
    and ``rhs``.  ``lhs`` is ``[m, k]`` with ``m`` a multiple of
    ``row_tile(m)``, sorted by group; ``sum(group_sizes) <= m``, and rows
    beyond it are neither read nor written."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"gmm: lhs is {lhs.dtype}, rhs is {rhs.dtype}")
    return _gmm(lhs, vary_like(rhs, lhs), vary_like(group_sizes, lhs),
                transpose_rhs, interpret)
