"""Pallas flash attention: exactness vs dense reference (CPU interpret mode)
and integration with Ulysses sequence parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel.flash import flash_attention
from horovod_tpu.parallel.ring import ring_attention_reference
from horovod_tpu.parallel.ulysses import ulysses_attention

B, S, H, D = 2, 128, 4, 32


def _qkv(seed):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32) * 0.3)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(hvd8, causal):
    q, k, v = _qkv(0)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    expected = ring_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_flash_uneven_block_sizes(hvd8):
    q, k, v = _qkv(1)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=32)
    expected = ring_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_flash_indivisible_seq_rejected(hvd8):
    q = jnp.ones((1, 100, 2, 16))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, q, q, block_q=64, block_k=64)


def test_flash_bf16(hvd8):
    q, k, v = _qkv(2)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=False, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    expected = ring_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               rtol=0.1, atol=0.05)


def test_flash_inside_ulysses(hvd8):
    """Ulysses with the Pallas kernel as the local attention backend."""
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(rng.randn(2, 64, 8, 32).astype(np.float32) * 0.3)
    q, k, v = mk(), mk(), mk()
    mesh = hvd8.mesh()

    def body(a, b, c):
        return ulysses_attention(
            a, b, c, causal=True,
            attention_fn=lambda *t, **kw: flash_attention(
                *t, block_q=32, block_k=32, **kw))

    # check_vma=False: the Pallas *interpreter* inlines the kernel into the
    # jaxpr where loop indices (invariant) mix with data (varying); the real
    # TPU lowering is a single opaque primitive and needs no escape hatch.
    out = jax.jit(jax.shard_map(body, mesh=mesh,
                                in_specs=(P(None, "hvd"),) * 3,
                                out_specs=P(None, "hvd"),
                                check_vma=False))(q, k, v)
    expected = ring_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_transformer_flash_impl_matches_dense(hvd8):
    import dataclasses
    from horovod_tpu.models import Transformer, TransformerConfig
    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            d_model=32, d_ff=64, max_len=64, causal=True,
                            dtype=jnp.float32)
    cfg_f = dataclasses.replace(cfg, attention_impl="flash")
    toks = jnp.asarray(np.random.RandomState(4).randint(0, 64, (2, 64)))
    params = Transformer(cfg).init(jax.random.PRNGKey(0), toks)
    a = Transformer(cfg).apply(params, toks)
    b = Transformer(cfg_f).apply(params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(hvd8, causal):
    """custom_vjp backward kernels vs autodiff through the dense reference."""
    q, k, v = _qkv(5)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(o * jnp.cos(o))

    def loss_dense(q, k, v):
        o = ring_attention_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=name)


def test_flash_gradients_uneven_blocks(hvd8):
    q, k, v = _qkv(6)
    f = lambda *t: jnp.sum(flash_attention(*t, causal=True, block_q=64,
                                           block_k=32) ** 2)
    d = lambda *t: jnp.sum(ring_attention_reference(*t, causal=True) ** 2)
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_transformer_flash_training_step(hvd8):
    """attention_impl='flash' must train (grad through the Pallas VJP)."""
    import dataclasses
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.models.transformer import lm_loss
    cfg = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                            d_model=32, d_ff=64, max_len=64, causal=False,
                            dtype=jnp.float32, attention_impl="flash")
    toks = jnp.asarray(np.random.RandomState(7).randint(0, 64, (2, 64)))
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0), toks)
    g = jax.grad(lambda p: lm_loss(model.apply(p, toks), toks))(params)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(x)).all() for x in flat)
    assert any(float(jnp.max(jnp.abs(x))) > 0 for x in flat)
