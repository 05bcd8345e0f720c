"""The causal window of the flash kernels (``flash.window_mask``), in
interpret mode on the CPU, against dense masked softmax: pair ``(q, k)`` is
kept iff ``0 <= q - k < window``."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import flash
from horovod_tpu.parallel.flash import (MASK_CAUSAL, MASK_NONE, MASK_STRICT,
                                        block_diffusion_mask, flash_attention,
                                        flash_attention_lse, window_mask)

B, H, HKV, D = 2, 8, 2, 16


def dense_mask(seq, window):
    ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
    return (ahead >= 0) & (ahead < window)


def dense_out_and_lse(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


def qkv(seed, seq):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(B, seq, h, D).astype(np.float32))
    return mk(H), mk(HKV), mk(HKV)


# (positions, window, query tile, key tile): windows of whole tiles, of a
# tile and a half, narrower than a tile, and of one position (a query reads
# itself alone).
CASES = [(128, 64, 32, 32), (128, 48, 32, 32), (96, 8, 32, 16),
         (128, 64, 16, 32), (64, 1, 16, 16)]


@pytest.mark.parametrize("seq,window,block_q,block_k", CASES)
def test_window_output_and_logsumexp_match_dense(seq, window, block_q,
                                                 block_k):
    q, k, v = qkv(0, seq)
    out, lse = flash_attention_lse(q, k, v, mask_mode=window_mask(window),
                                   block_q=block_q, block_k=block_k)
    want, want_lse = dense_out_and_lse(q, k, v, dense_mask(seq, window))
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("seq,window,block_q,block_k", CASES[:4])
def test_window_gradients_match_dense(seq, window, block_q, block_k, wrt):
    """All three kernels, four query heads a key/value head: dK and dV are
    summed over the group."""
    q, k, v = qkv(1, seq)
    weight = jnp.asarray(np.random.RandomState(2).randn(
        B, seq, H, D).astype(np.float32))
    got = jax.grad(lambda *a: (flash_attention(
        *a, mask_mode=window_mask(window), block_q=block_q,
        block_k=block_k) * weight).sum(), argnums=wrt)(q, k, v)
    want = jax.grad(lambda *a: (dense_out_and_lse(
        *a, dense_mask(seq, window))[0] * weight).sum(),
        argnums=wrt)(q, k, v)
    assert got.shape == (q, k, v)[wrt].shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seq,window,block_q,block_k", CASES)
def test_tile_lists_hold_exactly_the_band(seq, window, block_q, block_k):
    """A tile is in each list once iff the dense mask keeps a pair of it,
    on the edge iff it does not keep all of it."""
    mask = dense_mask(seq, window)
    nq, nk = seq // block_q, seq // block_k
    want = np.zeros((nq, nk), int)             # 0 out, 1 edge, 2 full
    for qi in range(nq):
        for ki in range(nk):
            tile = mask[qi * block_q:(qi + 1) * block_q,
                        ki * block_k:(ki + 1) * block_k]
            want[qi, ki] = 2 if tile.all() else 1 if tile.any() else 0
    group = 3
    by_query, by_key = flash.tile_lists(window_mask(window), seq, block_q,
                                        block_k, group)
    seen = np.zeros_like(want)
    seen[by_query[flash.ROW], by_query[flash.TILE]] = 2 - by_query[flash.EDGE]
    np.testing.assert_array_equal(seen, want)
    assert by_query.shape[1] == (want > 0).sum()
    for head in range(group):
        steps = by_key[:, by_key[flash.HEAD] == head]
        seen = np.zeros_like(want)
        seen[steps[flash.TILE], steps[flash.ROW]] = 2 - steps[flash.EDGE]
        np.testing.assert_array_equal(seen, want)
    assert by_key.shape[1] == group * (want > 0).sum()
    # A row walks at most window / block_k + 1 key tiles (one more where
    # the tiles are rectangular and the band starts inside a key tile).
    assert np.bincount(by_query[flash.ROW]).max() <= \
        -(-(window + block_q - 1) // block_k) + 1


def test_the_cells_band_five_tiles_a_row_two_of_them_edges():
    """8,192 positions, a window of 2,048, tiles of 512: a row holds the
    diagonal tile, three full ones and the tile the window's far side
    cuts; 70 of 256 tiles, 28 on an edge, where ``MASK_CAUSAL`` keeps 136
    with 16; no grid step computes nothing."""
    mode = window_mask(2048)
    by_query, by_key = flash.tile_lists(mode, 8192, 512, 512, 8)
    assert by_query.shape[1] == 70 and by_query[flash.EDGE].sum() == 28
    assert by_key.shape[1] == 8 * 70
    np.testing.assert_array_equal(np.bincount(by_query[flash.ROW]),
                                  [1, 2, 3, 4] + [5] * 12)
    np.testing.assert_array_equal(np.bincount(by_key[flash.ROW]),
                                  [8 * 5] * 12 + [8 * 4, 8 * 3, 8 * 2, 8])
    for row in range(4, 16):
        steps = by_query[:, by_query[flash.ROW] == row]
        np.testing.assert_array_equal(steps[flash.TILE],
                                      range(row - 4, row + 1))
        np.testing.assert_array_equal(steps[flash.EDGE], [1, 0, 0, 0, 1])
    steps, tiles = flash.grid_steps(mode, 8192, 512, 512, 32, 4)
    assert steps == tiles == 3 * 32 * 70
    full = flash.grid_steps(MASK_CAUSAL, 8192, 512, 512, 32, 4)[1]
    assert full == 3 * 32 * 136 and tiles / full == pytest.approx(0.5147,
                                                                   abs=1e-4)


@pytest.mark.parametrize("window", [128, 4096])
def test_a_window_over_the_sequence_is_causal_bit_for_bit(window):
    seq, tile = 128, 32
    for mine, theirs in zip(
            flash.tile_lists(window_mask(window), seq, tile, tile, 4),
            flash.tile_lists(MASK_CAUSAL, seq, tile, tile, 4)):
        np.testing.assert_array_equal(mine, theirs)
    q, k, v = qkv(3, seq)
    weight = jnp.asarray(np.random.RandomState(4).randn(
        B, seq, H, D).astype(np.float32))

    def everything(mode):
        def loss(q, k, v):
            out, lse = flash_attention_lse(q, k, v, mask_mode=mode,
                                           block_q=tile, block_k=tile)
            return (out * weight).sum() + lse.sum(), (out, lse)
        (_, outs), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return outs + grads

    for mine, theirs in zip(everything(window_mask(window)),
                            everything(MASK_CAUSAL)):
        np.testing.assert_array_equal(mine, theirs)


# CRC-32 of ``tile_lists(mode, 8192, 512, 512, 8)`` (by_query, by_key) at
# the commit before the window mode came: the lists of the four older modes
# are what they were.
OLD_LISTS = {
    "none": (MASK_NONE, 256, 382009306, 3585320237),
    "causal": (MASK_CAUSAL, 136, 2745083319, 1554566314),
    "strict": (MASK_STRICT, 136, 2745083319, 1554566314),
    "block_diffusion": (block_diffusion_mask(4, 4096), 80, 893943739,
                        1126428745),
}


@pytest.mark.parametrize("name", sorted(OLD_LISTS))
def test_the_older_modes_lists_are_unchanged(name):
    mode, tiles, crc_query, crc_key = OLD_LISTS[name]
    by_query, by_key = flash.tile_lists(mode, 8192, 512, 512, 8)
    assert by_query.shape == (6, tiles) and by_key.shape == (6, 8 * tiles)
    assert zlib.crc32(by_query.tobytes()) == crc_query
    assert zlib.crc32(by_key.tobytes()) == crc_key


def test_a_window_that_keeps_nothing_is_refused():
    with pytest.raises(ValueError, match="keeps nothing"):
        window_mask(0)


def test_traced_offsets_take_the_window_too():
    """``causal_mask`` and ``block_contributes`` with traced offsets, as the
    serving kernels call them."""
    mode = window_mask(5)

    @jax.jit
    def masked(q_offset, k_offset):
        return (flash.causal_mask(jnp.zeros((8, 8)), q_offset, k_offset,
                                  mode),
                flash.block_contributes(mode, q_offset, q_offset + 7,
                                        k_offset, k_offset + 7))

    kept, contributes = masked(16, 8)
    np.testing.assert_array_equal(np.asarray(kept) == 0,
                                  dense_mask(24, 5)[16:24, 8:16])
    assert bool(contributes)
    assert not bool(masked(24, 8)[1]) and not bool(masked(8, 16)[1])


# (positions, window, query tile, key tile): a window narrower than the
# tile, the tile's own width, a tile and a half, and the cell's sizes.
TILE_CASES = [(128, 8, 32, 32), (128, 32, 32, 32), (128, 48, 32, 32),
              (96, 20, 32, 16), (8192, 2048, 512, 512)]


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("seq,window,block_q,block_k", TILE_CASES)
def test_causal_mask_is_the_band_on_every_edge_tile(seq, window, block_q,
                                                    block_k, traced):
    """``causal_mask`` on a tile against the dense band, with the offsets
    as Python ints and traced: every tile at the small sizes (off the edge
    too, where the kernels skip the mask), the 28 edge tiles at the
    cell's."""
    mode = window_mask(window)
    want = dense_mask(seq, window)
    _, edge = flash._mask_tiles(mode, seq, block_q, block_k)
    tiles = np.argwhere(edge if seq > 1024 else np.ones_like(edge))
    assert seq <= 1024 or len(tiles) == 28

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
