"""The flash kernels with keys wider than values (``Dqk != Dv``: latent
attention attends with keys of 192 and values of 128), in interpret mode on
the CPU against dense float32 attention: output, logsumexp and the three
gradients under every kind of mask, with grouped key/value heads, and with
one rotary key shared by all heads as ``models/joyai_flash.py`` hands it
over (broadcast by the caller, its gradient the sum over the heads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel.flash import (MASK_CAUSAL, MASK_NONE,
                                        block_diffusion_mask, flash_attention,
                                        flash_attention_lse, window_mask)

B, S, H, DQK, DV, TILE = 2, 64, 4, 24, 16, 16
LENGTH, BLOCK, WINDOW = 32, 4, 24


def dense_masks():
    pos = np.arange(S)
    ahead = pos[:, None] - pos[None, :]
    noised, blk = pos < LENGTH, (pos % LENGTH) // BLOCK
    qn, kn, qb, kb = noised[:, None], noised[None, :], blk[:, None], \
        blk[None, :]
    return {
        "none": (MASK_NONE, np.ones((S, S), bool)),
        "causal": (MASK_CAUSAL, ahead >= 0),
        "window": (window_mask(WINDOW), (ahead >= 0) & (ahead < WINDOW)),
        "block_diffusion": (
            block_diffusion_mask(BLOCK, LENGTH),
            (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))}


MASKS = dense_masks()


def dense(q, k, v, mask):
    """``(out, lse)`` of dense masked softmax attention, float32."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(mask[None, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


def qkv(seed, kv_heads=H, dqk=DQK, dv=DV):
    rng = np.random.RandomState(seed)
    mk = lambda h, d: jnp.asarray(rng.randn(B, S, h, d).astype(np.float32))
    return mk(H, dqk), mk(kv_heads, dqk), mk(kv_heads, dv)


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("kv_heads", [H, 2], ids=["heads4", "grouped2"])
def test_output_and_logsumexp_match_dense(mask, kv_heads):
    mode, keep = MASKS[mask]
    q, k, v = qkv(0, kv_heads)
    out, lse = flash_attention_lse(q, k, v, mask_mode=mode, block_q=TILE,
                                   block_k=TILE)
    want, want_lse = dense(q, k, v, keep)
    assert out.shape == (B, S, H, DV) and lse.shape == (B, H, S)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("kv_heads", [H, 2], ids=["heads4", "grouped2"])
def test_gradients_match_dense(mask, kv_heads, wrt):
    """All three kernels: dQ and dK as wide as the keys, dV as the values,
    dK and dV summed over a group's query heads."""
    mode, keep = MASKS[mask]
    q, k, v = qkv(1, kv_heads)
    weight = jnp.asarray(np.random.RandomState(2).randn(
        B, S, H, DV).astype(np.float32))
    got = jax.grad(lambda *a: (flash_attention(
        *a, mask_mode=mode, block_q=TILE, block_k=TILE) * weight).sum(),
        argnums=wrt)(q, k, v)
    want = jax.grad(lambda *a: (dense(*a, keep)[0] * weight).sum(),
                    argnums=wrt)(q, k, v)
    assert got.shape == (q, k, v)[wrt].shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_logsumexp_takes_a_cotangent_at_two_widths():
    """``flash_attention_lse`` is differentiable in both outputs (ring
    attention's merge): the lse's cotangent folds into ``delta`` whatever
    the widths."""
    mode, keep = MASKS["causal"]
    q, k, v = qkv(3)
    loss = lambda fn: lambda *a: (lambda out, lse: out.sum()
                                  + (lse ** 2).sum())(*fn(*a))
    got = jax.grad(loss(lambda *a: flash_attention_lse(
        *a, mask_mode=mode, block_q=TILE, block_k=TILE)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: dense(*a, keep)), argnums=(0, 1, 2))(
        q, k, v)
    for mine, theirs in zip(got, want):
        np.testing.assert_allclose(mine, theirs, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("mask", ["causal", "none"])
def test_one_rotary_key_shared_by_all_heads(mask):
    """The score as latent attention writes it, ``q_nope . k_nope + q_rope .
    k_rope`` with ``k_rope`` ONE head, against the kernels' one product over
    the whole key with ``k_rope`` broadcast by the caller: output and the
    gradient of every part, the shared key's summed over the heads."""
    mode, keep = MASKS[mask]
    nope, rope = DV, DQK - DV
    rng = np.random.RandomState(4)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))
    parts = (mk(B, S, H, nope), mk(B, S, H, rope), mk(B, S, H, nope),
             mk(B, S, rope), mk(B, S, H, DV))
    weight = mk(B, S, H, DV)

    def by_kernels(q_nope, q_rope, k_nope, k_rope, v):
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], (B, S, H, rope))], axis=-1)
        return flash_attention(jnp.concatenate([q_nope, q_rope], axis=-1),
                               k, v, mask_mode=mode, block_q=TILE,
                               block_k=TILE)

    def by_two_products(q_nope, q_rope, k_nope, k_rope, v):
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) \
            / np.sqrt(nope + rope)
        s = jnp.where(keep[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    np.testing.assert_allclose(by_kernels(*parts), by_two_products(*parts),
                               rtol=2e-4, atol=2e-5)
    got, want = (jax.grad(lambda *a: (fn(*a) * weight).sum(),
                          argnums=tuple(range(5)))(*parts)
                 for fn in (by_kernels, by_two_products))
    for mine, theirs, part in zip(got, want, parts):
        assert mine.shape == part.shape
        np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-4)


def test_values_as_wide_as_keys_and_narrower_are_one_path():
    """``Dv == Dqk`` gives in its first columns what the same call gives
    with the values cut to those columns (to rounding: the CPU's product
    sums a narrower matrix in another order)."""
    q, k, _ = qkv(5)
    v = jnp.asarray(np.random.RandomState(6).randn(B, S, H, DQK),
                    jnp.float32)
    same = flash_attention(q, k, v, causal=True, block_q=TILE, block_k=TILE)
    narrow = flash_attention(q, k, v[..., :DV], causal=True, block_q=TILE,
                             block_k=TILE)
    np.testing.assert_allclose(same[..., :DV], narrow, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("shapes,match", [
    (((B, S, H, DQK), (B, S, H, DV), (B, S, H, DV)), "as wide as"),
    (((B, S, H, DQK), (B, S, 2, DQK), (B, S, 4, DV)), "value"),
    (((B, S, H, DQK), (B, S, 3, DQK), (B, S, 3, DV)), "divide")])
def test_shapes_that_do_not_fit_are_refused(shapes, match):
    q, k, v = (jnp.zeros(shape, jnp.float32) for shape in shapes)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, v)
