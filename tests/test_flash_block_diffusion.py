"""The block-diffusion mask and grouped key/value heads of the flash
kernels, in interpret mode on the CPU, against dense masked softmax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import flash
from horovod_tpu.parallel.flash import (MASK_CAUSAL, MASK_NONE, MASK_STRICT,
                                        MASK_WINDOW, block_diffusion_mask,
                                        flash_attention, window_mask)

B, H, HKV, D = 2, 8, 2, 16


def dense_mask(mode, seq):
    """The mask of ``mode`` as a boolean ``[seq, seq]`` array, written
    from the rule and not from ``causal_mask``."""
    q = np.arange(seq)[:, None]
    k = np.arange(seq)[None, :]
    if mode == MASK_NONE:
        return np.ones((seq, seq), bool)
    if mode == MASK_CAUSAL:
        return q >= k
    if mode == MASK_STRICT:
        return q > k
    if mode[0] == MASK_WINDOW:
        return (q >= k) & (q - k < mode[1])
    _, block, length = mode
    qn, kn = q < length, k < length
    qb, kb = (q % length) // block, (k % length) // block
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def dense_attention(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def qkv(seed, seq, kv_heads=HKV):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(B, seq, h, D).astype(np.float32))
    return mk(H), mk(kv_heads), mk(kv_heads)


# (clean tokens L, block length, tile): L = 48 is no multiple of the tile,
# so one tile holds the end of the noised copy and the start of the clean.
CASES = [(48, 4, 32), (64, 4, 32), (64, 8, 16), (32, 32, 16)]


@pytest.mark.parametrize("length,block,tile", CASES)
def test_block_diffusion_forward_matches_dense(length, block, tile):
    mode = block_diffusion_mask(block, length)
    q, k, v = qkv(0, 2 * length)
    out = flash_attention(q, k, v, mask_mode=mode, block_q=tile,
                          block_k=tile)
    want = dense_attention(q, k, v, dense_mask(mode, 2 * length))
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("length,block,tile", CASES[:2])
def test_block_diffusion_gradients_match_dense(length, block, tile, wrt):
    mode = block_diffusion_mask(block, length)
    q, k, v = qkv(1, 2 * length)
    weight = jnp.asarray(np.random.RandomState(2).randn(
        B, 2 * length, H, D).astype(np.float32))
    got = jax.grad(lambda *a: (flash_attention(
        *a, mask_mode=mode, block_q=tile, block_k=tile) * weight).sum(),
        argnums=wrt)(q, k, v)
    want = jax.grad(lambda *a: (dense_attention(
        *a, dense_mask(mode, 2 * length)) * weight).sum(),
        argnums=wrt)(q, k, v)
    assert got.shape == (q, k, v)[wrt].shape      # dK, dV: 2 heads, summed
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_under_the_old_masks(causal):
    q, k, v = qkv(3, 64)
    mode = MASK_CAUSAL if causal else MASK_NONE
    got = jax.grad(lambda *a: flash_attention(
        *a, causal=causal, block_q=32, block_k=16).sum() ** 2,
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: dense_attention(
        *a, dense_mask(mode, 64)).sum() ** 2, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)


def test_heads_that_do_not_divide_are_refused():
    q, k, v = qkv(4, 32, kv_heads=3)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("mode", [
    block_diffusion_mask(4, 64), MASK_CAUSAL], ids=["bd4x64", "causal"])
@pytest.mark.parametrize("block_q,block_k", [(32, 16), (16, 32)])
def test_eight_heads_a_key_value_head_on_rectangular_tiles(mode, block_q,
                                                           block_k):
    """The dK/dV walk crosses the group's eight query heads inside every
    key tile: forward and all three gradients against the dense
    reference."""
    q, k, v = qkv(5, 128, kv_heads=1)
    weight = jnp.asarray(np.random.RandomState(6).randn(
        B, 128, H, D).astype(np.float32))
    mask = dense_mask(mode, 128)

    def outputs_and_gradients(attend):
        def loss(*a):
            out = attend(*a)
            return (out * weight).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    out, got = outputs_and_gradients(lambda *a: flash_attention(
        *a, mask_mode=mode, block_q=block_q, block_k=block_k))
    want_out, want = outputs_and_gradients(
        lambda *a: dense_attention(*a, mask))
    np.testing.assert_allclose(out, want_out, rtol=2e-4, atol=2e-5)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


MODES = [MASK_NONE, MASK_CAUSAL, MASK_STRICT, block_diffusion_mask(4, 48),
         block_diffusion_mask(4, 64), block_diffusion_mask(16, 64)]
MODE_IDS = ["none", "causal", "strict", "bd4x48", "bd4x64", "bd16x64"]


def seq_of(mode):
    return 2 * mode[2] if isinstance(mode, tuple) else 96


@pytest.mark.parametrize("mode", MODES, ids=MODE_IDS)
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (32, 16), (16, 32)])
def test_tile_lists_hold_the_tiles_the_mask_touches(mode, block_q, block_k):
    """A tile is in each list exactly once iff the dense mask keeps a pair
    of it, on the edge iff it does not keep all; a list's rows are
    contiguous, in order, their tiles ascending, their first and last steps
    marked; the dK/dV list is the transpose of the other, its rows walked
    once for every head of the group."""
    seq, group = seq_of(mode), 3
    mask = dense_mask(mode, seq)
    nq, nk = seq // block_q, seq // block_k
    want = np.zeros((nq, nk), int)             # 0 out, 1 edge, 2 full
    for qi in range(nq):
        for ki in range(nk):
            tile = mask[qi * block_q:(qi + 1) * block_q,
                        ki * block_k:(ki + 1) * block_k]
            want[qi, ki] = 2 if tile.all() else 1 if tile.any() else 0
    by_query, by_key = flash.tile_lists(mode, seq, block_q, block_k, group)
    assert by_query.dtype == by_key.dtype == np.int32

    def check(steps, want, heads):
        row, tile, edge, first, last, head = steps
        # Every step in the order (row, head, tile), none twice.
        order = list(zip(row, head, tile))
        assert order == sorted(set(order))
        assert set(edge) <= {0, 1}
        seen = np.zeros((heads,) + want.shape, int)
        seen[head, row, tile] = 2 - edge
        for h in range(heads):
            np.testing.assert_array_equal(seen[h], want)
        # First and last mark the ends of a row's run of steps.
        starts = np.flatnonzero(np.diff(row, prepend=-1))
        np.testing.assert_array_equal(np.flatnonzero(first), starts)
        np.testing.assert_array_equal(
            np.flatnonzero(last),
            np.append(starts[1:], len(row)) - 1)
        assert sorted(set(row)) == list(range(want.shape[0]))

    check(by_query, want, 1)
    check(by_key, want.T, group)
    assert by_query.shape == (6, (want > 0).sum())
    assert by_key.shape == (6, group * (want > 0).sum())


@pytest.mark.parametrize("mode", MODES, ids=MODE_IDS)
def test_grid_steps_are_the_lists_lengths_and_the_tiles(mode):
    """The exported count: the kernels' sequential grid dimensions are the
    lists' lengths, and no step is left that computes nothing."""
    seq, heads, kv_heads = seq_of(mode), 8, 2
    by_query, by_key = flash.tile_lists(mode, seq, 32, 16,
                                        heads // kv_heads)
    kept = sum(dense_mask(mode, seq)[qi:qi + 32, ki:ki + 16].any()
               for qi in range(0, seq, 32) for ki in range(0, seq, 16))
    steps, tiles = flash.grid_steps(mode, seq, 32, 16, heads, kv_heads)
    assert steps == 2 * heads * by_query.shape[1] \
        + kv_heads * by_key.shape[1]
    assert tiles == 3 * heads * kept == steps
    if mode == MASK_NONE:                  # the full rectangle, as before
        assert by_query.shape[1] == (seq // 32) * (seq // 16)


def test_a_row_the_mask_leaves_empty_is_written_as_zeros():
    """``MASK_STRICT`` on tiles of one position: the first query reads no
    key and the last key is read by no query; each keeps one masked tile
    (of query 0), so that the row's output is written."""
    by_query, by_key = flash.tile_lists(MASK_STRICT, 4, 1, 1)
    assert by_query.shape == by_key.shape == (6, 6 + 2)
    np.testing.assert_array_equal(
        by_query[:, :2].T, [[0, 0, 1, 1, 0, 0], [0, 3, 1, 0, 1, 0]])
    q, k, v = qkv(7, 4)
    out = flash_attention(q, k, v, mask_mode=MASK_STRICT, block_q=1,
                          block_k=1)
    want = dense_attention(q[:, 1:], k, v, dense_mask(MASK_STRICT, 4)[1:])
    np.testing.assert_array_equal(out[:, 0], 0)
    np.testing.assert_allclose(out[:, 1:], want, rtol=2e-4, atol=2e-5)


def test_block_diffusion_keeps_a_quarter_of_the_tiles():
    """At the benchmark's sizes: 2L = 8,192 positions, blocks of 4, tiles
    of 512: 80 of 256 tiles hold a kept pair, 24 of them on the edge, and
    every kernel takes 80 steps a query head: 2.27 a tile went into the
    rectangles padded to the longest row ((16, 9) and (16, 16))."""
    mode = block_diffusion_mask(4, 4096)
    by_query, by_key = flash.tile_lists(mode, 8192, 512, 512, 8)
    assert by_query.shape[1] == 80 and by_query[flash.EDGE].sum() == 24
    assert by_key.shape[1] == 8 * 80
    assert flash.grid_steps(mode, 8192, 512, 512, 32, 4) == (7680, 7680)
    per_row = np.bincount(by_query[flash.ROW])
    np.testing.assert_array_equal(
        per_row, list(range(2, 10)) + list(range(1, 9)))


# -- the forward kernel's state over whole lane groups ------------------------
# Key tiles of one, two and four lane groups of 128: a row's running maximum
# and sum are kept in every lane of the row, made from the elementwise
# maximum and sum of the groups (``flash._fwd_step``, ``flash._fwd_flush``).

WIDE_SEQ, WIDE_H, WIDE_HKV, WIDE_D = 512, 2, 1, 32
WIDE_MODES = [MASK_NONE, MASK_CAUSAL, MASK_STRICT,
              block_diffusion_mask(4, WIDE_SEQ // 2)]
WIDE_IDS = ["none", "causal", "strict", "bd4x256"]


def wide_qkv(seed, dtype):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(1, WIDE_SEQ, h, WIDE_D), dtype)
                 for h in (WIDE_H, WIDE_HKV, WIDE_HKV))


def dense_out_and_lse(q, k, v, mask):
    """Float32 ``(out [B,S,H,D], lse [B,H,S])`` of masked softmax attention;
    a row with no key reads zeros and ``-inf``."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = (jnp.repeat(t, q.shape[2] // k.shape[2], axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(mask[None, None], jnp.exp(s - jnp.where(
        jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest"), lse


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("block_k", [128, 256, 512])
@pytest.mark.parametrize("mode", WIDE_MODES, ids=WIDE_IDS)
def test_forward_and_logsumexp_over_lane_groups(mode, block_k, dtype):
    q, k, v = wide_qkv(11, dtype)
    out, lse = flash.flash_attention_lse(q, k, v, mask_mode=mode,
                                         block_q=128, block_k=block_k)
    want, want_lse = dense_out_and_lse(q, k, v, dense_mask(mode, WIDE_SEQ))
    assert out.dtype == dtype and lse.dtype == jnp.float32
    assert lse.shape == (1, WIDE_H, WIDE_SEQ)
    reads = np.asarray(jnp.isfinite(want_lse))[0, 0]       # rows with a key
    assert reads.sum() == WIDE_SEQ - (mode == MASK_STRICT)
    # A row with every key masked: zeros, and a logsumexp that is finite.
    np.testing.assert_array_equal(np.asarray(out, np.float32)[:, ~reads], 0)
    assert np.isfinite(np.asarray(lse)).all()
    # bf16: the scaled queries, the probabilities and the output are
    # rounded to bf16 in the kernel and not in the reference.
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(lse)[..., reads],
                               np.asarray(want_lse)[..., reads],
                               rtol=0, atol=tol)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=0, atol=tol)


@pytest.mark.parametrize("block_k", [128, 256, 512])
@pytest.mark.parametrize("mode", WIDE_MODES, ids=WIDE_IDS)
def test_gradients_recomputed_from_the_lane_groups_logsumexp(mode, block_k):
    """The backward kernels recompute the probabilities from the forward
    kernel's logsumexp; the loss reads the logsumexp too, as ring
    attention's merge does."""
    q, k, v = wide_qkv(12, jnp.float32)
    mask = dense_mask(mode, WIDE_SEQ)
    reads = jnp.asarray(mask.any(axis=1))
    weight = jnp.asarray(np.random.RandomState(13).randn(
        1, WIDE_SEQ, WIDE_H, WIDE_D).astype(np.float32))

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            return (out * weight).sum() + jnp.where(reads, lse, 0.0).sum()
        return f

    got = jax.grad(loss(lambda q, k, v: flash.flash_attention_lse(
        q, k, v, mask_mode=mode, block_q=128, block_k=block_k)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: dense_out_and_lse(q, k, v, mask)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)


# -- the mask of one tile: positions on a column and a row --------------------
# ``causal_mask`` computes what a query's position decides on a [Bq, 1]
# column and what a key's decides on a [1, Bk] row; the tile sees them
# compared, the results' conjunction and the select.

# (block length, clean tokens L, query tile, key tile): the cell's sizes,
# then block lengths that are no power of two with L no multiple of the
# tile, so that a tile spans the end of the noised copy and the start of
# the clean one.
TILE_CASES = [(4, 4096, 512, 512), (3, 60, 8, 8), (6, 60, 24, 24),
              (6, 60, 8, 24), (3, 48, 32, 16)]


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("block,length,block_q,block_k", TILE_CASES)
def test_causal_mask_is_the_rule_on_every_edge_tile(block, length, block_q,
                                                    block_k, traced):
    mode = block_diffusion_mask(block, length)
    seq = 2 * length
    want = dense_mask(mode, seq)
    _, edge = flash._mask_tiles(mode, seq, block_q, block_k)
    if length % block_q:           # the tiles that span both copies
        assert edge[length // block_q].any()
        assert edge[:, length // block_k].any()
    # Off the edge the kernels skip the mask; it is right there too (the
    # paged kernel runs it on every block): every tile at the small sizes.
    tiles = np.argwhere(edge if seq > 1024 else np.ones_like(edge))
    assert seq <= 1024 or len(tiles) == 24

    def kept(q_offset, k_offset):
        return flash.causal_mask(jnp.zeros((block_q, block_k)), q_offset,
                                 k_offset, mode) == 0

    got = jax.jit(kept) if traced else kept
    for qi, ki in tiles:
        q_lo, k_lo = int(qi * block_q), int(ki * block_k)
        np.testing.assert_array_equal(
            got(q_lo, k_lo),
            want[q_lo:q_lo + block_q, k_lo:k_lo + block_k],
            err_msg=f"tile ({qi}, {ki})")


def tile_shaped(jaxpr, shape):
    """The primitives of the equations of ``jaxpr``, and of those inside
    them, whose output has ``shape``, in order."""
    found = []
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        if inner:
            for sub in inner:
                found += tile_shaped(getattr(sub, "jaxpr", sub), shape)
        elif any(getattr(v.aval, "shape", None) == shape
                 for v in eqn.outvars):
            found.append(eqn.primitive.name)
    return found


COMPARISONS = {"lt", "le", "gt", "ge", "eq", "ne"}


@pytest.mark.parametrize("mode,comparisons", [
    (block_diffusion_mask(4, 4096), 2), (window_mask(2048), 2),
    (MASK_CAUSAL, 1), (MASK_STRICT, 1)],
    ids=["block_diffusion", "window", "causal", "strict"])
def test_the_tile_sees_comparisons_and_one_select(mode, comparisons):
    """The CPU's reading of what ``tools/flash_bundles.py`` counts: on a
    [512, 512] edge tile no division, remainder, subtraction, iota or
    select has an output of the tile's shape but the final select; the
    tile-shaped equations are the comparisons of the column with the row,
    their conjunction, the masked value's broadcast and that select."""
    tile = (512, 512)
    jaxpr = jax.make_jaxpr(
        lambda s, q_offset, k_offset: flash.causal_mask(
            s, q_offset, k_offset, mode))(jnp.zeros(tile), 3584, 3584)
    found = tile_shaped(jaxpr.jaxpr, tile)
    assert found[-1] == "select_n" and found.count("select_n") == 1
    assert sum(name in COMPARISONS for name in found) == comparisons
    assert set(found) <= COMPARISONS | {"broadcast_in_dim", "and",
                                        "select_n"}
    assert found.count("and") == comparisons - 1


# The five masks where a block-diffusion tile spans both copies and the
# block length is no power of two: 120 positions, L = 60.
SPAN_SEQ = 120
SPAN_MODES = [MASK_NONE, MASK_CAUSAL, MASK_STRICT,
              block_diffusion_mask(6, SPAN_SEQ // 2), window_mask(20)]
SPAN_IDS = ["none", "causal", "strict", "bd6x60", "window20"]


@pytest.mark.parametrize("block_q,block_k", [(24, 8), (8, 24)])
@pytest.mark.parametrize("mode", SPAN_MODES, ids=SPAN_IDS)
def test_outputs_logsumexp_and_gradients_under_the_five_masks(
        mode, block_q, block_k):
    q, k, v = qkv(21, SPAN_SEQ)
    mask = dense_mask(mode, SPAN_SEQ)
    reads = jnp.asarray(mask.any(axis=1))
    weight = jnp.asarray(np.random.RandomState(22).randn(
        B, SPAN_SEQ, H, D).astype(np.float32))

    def everything(attend):
        def loss(q, k, v):
            out, lse = attend(q, k, v)
            return ((out * weight).sum()
                    + jnp.where(reads, lse, 0.0).sum()), (out, lse)
        (_, outs), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return outs, grads

    (out, lse), got = everything(lambda q, k, v: flash.flash_attention_lse(
        q, k, v, mask_mode=mode, block_q=block_q, block_k=block_k))
    (want_out, want_lse), want = everything(
        lambda q, k, v: dense_out_and_lse(q, k, v, mask))
    np.testing.assert_allclose(out, want_out, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse)[..., np.asarray(reads)],
                               np.asarray(want_lse)[..., np.asarray(reads)],
                               rtol=0, atol=1e-5)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
