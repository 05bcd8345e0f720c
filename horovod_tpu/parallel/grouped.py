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

Dtypes: ``rhs`` comes as it is stored (a model's float32 parameters) and
is never converted outside the kernel.  The products run in ``lhs``'s
dtype with float32 accumulation: a block of ``rhs`` is cast to it in VMEM,
the rounding ``rhs.astype(lhs.dtype)`` would make.  ``out`` and the
gradient of ``lhs`` come in ``lhs``'s dtype, the gradient of ``rhs`` in
``rhs``'s, straight from ``tgmm``'s float32 sums.

One fetch a group: where a group's whole ``(k, tn)`` slab of ``rhs`` is at
most ``SLAB_BYTES``, it is the block, ``tn`` as wide as that allows.  Its
index then follows the step's group alone, consecutive visits of a group
reuse the copy Pallas holds, and the cast is made once a group into a
scratch buffer.  A wider slab is walked in tiles of the contraction,
fetched and cast again at every visit.  The choice follows ``k``, ``n`` and
``rhs``'s dtype; ``fetches`` counts what it comes to.  ``tgmm``'s block of
a group's gradient follows the same rule, and a float32 one is summed in
place.

Unlike megablox the calls carry their varying-axes type, so they run
inside ``shard_map(check_vma=True)``, which ``hvd.shard_step`` and
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
#: dimension.  The contraction's is for a slab beyond ``SLAB_BYTES`` only.
TILES = (512, 1024, 1024)

#: The largest ``(k, tn)`` slab of one group's matrix, in bytes of ``rhs``'s
#: dtype, that ``gmm`` takes as one block: 2,048 x 1,024 float32.
SLAB_BYTES = 8 * 2 ** 20

#: What a call may use of VMEM (a v5e core has 128 MiB, the compiler's
#: default is 16): a float32 slab double-buffered (16 MiB) and its cast
#: copy (4), 512 rows of 2,048 bf16 in and out, double-buffered (8), the
#: product in float32 (4), and 24 for the compiler's own.
VMEM_LIMIT_BYTES = 56 * 2 ** 20


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


def group_tiles(group_sizes, m: int, tm: int, visit_empty: bool = False):
    """``(starts, ends, group_ids, tile_ids, count)``: the first and one
    past the last row of every group, and for each step of the sequential
    grid dimension the group and the row tile it works on.  A tile that
    holds rows of two groups is visited once for each, one after the
    other; an empty group is not visited, or with ``visit_empty`` once, on
    a tile none of whose rows are its own.  ``count`` is the number of
    steps in use, at most ``m // tm + G - 1``."""
    num_groups = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1,
                      int(visit_empty))
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


def _params(interpret, *semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _gmm_tiles(m: int, k: int, n: int, rhs_dtype):
    """``(tm, tk, tn)`` of a product of ``[m, k]`` rows with ``[k, n]``
    matrices of ``rhs_dtype``: the column tile as wide as a slab of at
    most ``SLAB_BYTES`` allows, and ``tk == k``, the group's slab whole,
    where the slab is no larger."""
    size = jnp.dtype(rhs_dtype).itemsize
    tn = _tile(n, max(TILES[2], SLAB_BYTES // (k * size)))
    return (row_tile(m),
            k if k * tn * size <= SLAB_BYTES else _tile(k, TILES[1]), tn)


def fetches(group_sizes, m: int, k: int, n: int, rhs_dtype):
    """``(visits, fetches)`` of one ``gmm`` call at these sizes: the grid
    steps along its sequential dimension (a row tile once for every group
    with rows in it) and the blocks of ``rhs`` it copies into VMEM.  A
    slab is fetched once for every group with rows and column tile, ``n //
    tn`` times a group; a matrix walked in tiles ``k // tk`` times a visit
    and column tile.  A count: the same on any backend."""
    tm, tk, tn = _gmm_tiles(m, k, n, rhs_dtype)
    group_sizes = jnp.asarray(group_sizes)
    visits = int(group_tiles(group_sizes, m, tm)[-1])
    groups = int((group_sizes > 0).sum())
    return visits, n // tn * (groups if tk == k else visits * (k // tk))


def _new_group(group_ids, step):
    """Whether the step's group is another than the step's before."""
    return jnp.logical_or(
        step == 0, group_ids[jnp.maximum(step - 1, 0)] != group_ids[step])


def _gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = _gmm_tiles(m, k, n, rhs.dtype)
    if m % tm:
        raise ValueError(f"gmm: {m} rows are no multiple of the row tile "
                         f"{tm} (pad to row_tile(rows))")
    *meta, count = group_tiles(group_sizes, m, tm)
    tiles_k = k // tk
    contract = (((1,), (1,)), ((), ())) if transpose_rhs else \
        (((1,), (0,)), ((), ()))
    rhs_block = (tn, tk) if transpose_rhs else (tk, tn)
    # The slab's cast copy lives from a group's first visit to its last;
    # a tile of the contraction is cast as it is used.
    cast_once = tiles_k == 1 and rhs.dtype != lhs.dtype
    scratch = ([pltpu.VMEM(rhs_block, lhs.dtype)] if cast_once else []) + \
        ([pltpu.VMEM((tm, tn), jnp.float32)] if tiles_k > 1 else [])

    def kernel(starts, ends, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref,
               *scratch):
        step, k_i = pl.program_id(1), pl.program_id(2)

        if cast_once:
            @pl.when(_new_group(group_ids, step))
            def _cast():
                scratch[0][...] = rhs_ref[...].astype(lhs_ref.dtype)

            matrix = scratch[0][...]
        else:
            matrix = rhs_ref[...].astype(lhs_ref.dtype)
        part = jax.lax.dot_general(lhs_ref[...], matrix, contract,
                                   preferred_element_type=jnp.float32)

        def among(total):
            """``total`` on the group's rows, the block as it is elsewhere."""
            mine = _rows_of_group(starts, ends, group_ids, tile_ids, step,
                                  tm, tn)
            return jnp.where(mine, total, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)

        if tiles_k == 1:
            out_ref[...] = among(part)
            return
        acc = scratch[-1]

        @pl.when(k_i == 0)
        def _first():
            acc[...] = part

        @pl.when(k_i > 0)
        def _add():
            acc[...] += part

        @pl.when(k_i == tiles_k - 1)
        def _store():
            out_ref[...] = among(acc[...])

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
                pl.BlockSpec((None,) + rhs_block, rhs_map),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, step, k_i, starts, ends, group_ids,
                tile_ids: (tile_ids[step], n_i)),
            scratch_shapes=scratch),
        compiler_params=_params(interpret, "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret,
        name="hvd_gmm",
    )(*(vary_like(x, lhs) for x in meta), lhs, rhs)


def _tgmm_call(lhs, dout, group_sizes, dtype, interpret):
    """``out[g] = lhs[rows of g]^T @ dout[rows of g]``: ``[G, k, n]`` in
    ``dtype`` from float32 sums.  An empty group is visited once, with no
    row of its own, so that its matrix is written too, as zeros.  The
    block of ``out`` follows ``gmm``'s rule for a matrix of ``dtype``, a
    group's slab whole where that fits; a float32 block is summed in
    place."""
    m, k = lhs.shape
    n = dout.shape[1]
    num_groups = group_sizes.shape[0]
    tm, tk, tn = _gmm_tiles(m, k, n, dtype)
    *meta, count = group_tiles(group_sizes, m, tm, visit_empty=True)
    in_place = jnp.dtype(dtype) == jnp.float32

    def kernel(starts, ends, group_ids, tile_ids, steps, lhs_ref, dout_ref,
               out_ref, *scratch):
        acc = out_ref if in_place else scratch[0]
        step = pl.program_id(2)

        @pl.when(_new_group(group_ids, step))
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

        if not in_place:
            last = jnp.logical_or(
                step == steps[0] - 1,
                group_ids[jnp.minimum(step + 1, steps[0] - 1)]
                != group_ids[step])

            @pl.when(last)
            def _store():
                out_ref[...] = acc[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=_out_struct((num_groups, k, n), dtype, lhs),
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
            scratch_shapes=[] if in_place else
            [pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params(interpret, "parallel", "parallel",
                                "arbitrary"),
        interpret=interpret,
        name="hvd_tgmm",
    )(*(vary_like(x, lhs) for x in meta + [count[None]]), lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    return _gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, transpose_rhs, interpret):
    return (_gmm_call(lhs, rhs, group_sizes, transpose_rhs, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(transpose_rhs, interpret, res, dout):
    lhs, rhs, group_sizes = res
    dlhs = _gmm_call(dout, rhs, group_sizes, not transpose_rhs, interpret)
    drhs = _tgmm_call(lhs, dout, group_sizes, rhs.dtype, interpret)
    return dlhs, (drhs.swapaxes(1, 2) if transpose_rhs else drhs), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
        transpose_rhs: bool = False, interpret=None) -> jax.Array:
    """``out[rows of g] = lhs[rows of g] @ rhs[g]`` (``@ rhs[g]^T`` with
    ``transpose_rhs``) for every group ``g``; differentiable in ``lhs``
    and ``rhs``.  ``lhs`` is ``[m, k]`` with ``m`` a multiple of
    ``row_tile(m)``, sorted by group; ``sum(group_sizes) <= m``, and rows
    beyond it are neither read nor written.  ``rhs`` is taken in the dtype
    it is stored in and cast to ``lhs``'s inside the kernel: the result and
    ``lhs``'s gradient have ``lhs``'s dtype, ``rhs``'s gradient ``rhs``'s
    (the module's text has the rule of the slab)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gmm(lhs, vary_like(rhs, lhs), vary_like(group_sizes, lhs),
                transpose_rhs, interpret)
