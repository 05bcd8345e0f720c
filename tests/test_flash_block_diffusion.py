"""The block-diffusion mask and grouped key/value heads of the flash
kernels, in interpret mode on the CPU, against dense masked softmax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import flash
from horovod_tpu.parallel.flash import (MASK_CAUSAL, MASK_NONE, MASK_STRICT,
                                        block_diffusion_mask,
                                        flash_attention)

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
    MASK_NONE, MASK_CAUSAL, MASK_STRICT, block_diffusion_mask(4, 48),
    block_diffusion_mask(4, 64), block_diffusion_mask(16, 64)],
    ids=["none", "causal", "strict", "bd4x48", "bd4x64", "bd16x64"])
@pytest.mark.parametrize("block_q,block_k", [(32, 32), (32, 16), (16, 32)])
def test_tile_tables_hold_the_tiles_the_mask_touches(mode, block_q, block_k):
    """A tile is in the tables iff the dense mask keeps a pair of it, is
    flagged full iff it keeps all, and the two tables are each other's
    transpose."""
    seq = 2 * mode[2] if isinstance(mode, tuple) else 96
    mask = dense_mask(mode, seq)
    kidx, kflag, qidx, qflag = flash.tile_tables(mode, seq, block_q,
                                                 block_k)
    seen = np.zeros((seq // block_q, seq // block_k), int)
    for qi in range(seq // block_q):
        for j in range(kidx.shape[1]):
            if kflag[qi, j]:
                seen[qi, kidx[qi, j]] = kflag[qi, j]
    seen_t = np.zeros_like(seen)
    for ki in range(seq // block_k):
        for j in range(qidx.shape[1]):
            if qflag[ki, j]:
                seen_t[qidx[ki, j], ki] = qflag[ki, j]
    np.testing.assert_array_equal(seen, seen_t)
    for qi in range(seq // block_q):
        for ki in range(seq // block_k):
            tile = mask[qi * block_q:(qi + 1) * block_q,
                        ki * block_k:(ki + 1) * block_k]
            want = 2 if tile.all() else 1 if tile.any() else 0
            assert seen[qi, ki] == want, (qi, ki)


def test_block_diffusion_keeps_a_quarter_of_the_tiles():
    """At the benchmark's sizes: 2L = 8,192 positions, blocks of 4, tiles
    of 512: 80 of 256 tiles hold a kept pair, 24 of them on the edge."""
    _, kflag, _, _ = flash.tile_tables(block_diffusion_mask(4, 4096), 8192,
                                       512, 512)
    assert (kflag > 0).sum() == 80 and (kflag == 1).sum() == 24
    assert kflag.shape == (16, 9)
