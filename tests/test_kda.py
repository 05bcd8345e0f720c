"""The chunked scan of Kimi Delta Attention (``parallel/kda.py``) in
interpret mode against the recurrence it stands for, a token at a time:
output and all five gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import kda

HEADS, D = 2, 32
CHUNK = 64


def operands(seq, seed=0, decay=1.0, beta=None, dtype=jnp.float32,
             heads=HEADS, d=D):
    """Unit keys, queries of ``d^-1/2``, values of SiLU's range, log-decays
    of ``-decay x softplus(n - 2)`` (0.13 x ``decay`` a step in the mean,
    single steps several times that), ``beta`` a sigmoid or the constant
    given."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (seq, heads, d)
    q = unit(jax.random.normal(keys[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.nn.silu(jax.random.normal(keys[2], shape))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], shape) - 2.0)
    b = jax.nn.sigmoid(jax.random.normal(keys[4], (seq, heads))) \
        if beta is None else jnp.full((seq, heads), beta, jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, b


def both(args, chunk=CHUNK, block=kda.BLOCK, seed=9):
    """``((o, gradients) of the kernels, (o, gradients) of the
    recurrence)`` under one random cotangent."""
    weight = jax.random.normal(jax.random.PRNGKey(seed), args[0].shape)

    def run(scan):
        def loss(*a):
            o = scan(*a).astype(jnp.float32)
            return jnp.sum(o * weight), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return o, grads

    return run(lambda *a: kda.kda_scan(*a, chunk=chunk, block=block)), \
        run(lambda *a: kda.kda_recurrence(*a)[0])


def off(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("decay", [0.05, 1.0, 15.0, 200.0],
                         ids=["weak", "seeded", "strong", "wiping"])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_output_and_all_five_gradients_equal_the_recurrence(chunks, decay):
    """1, 2 and 5 chunks (the last no multiple of a block: one grid step
    walks them all), from decays that forget nothing in a chunk to ones
    whose cumulative exponent passes 100 inside one (``exp(100)`` is no
    float32) and on to decays of 25 a step sustained, hundreds in single
    steps, which wipe a channel's state between neighbours: no exponent is
    taken above 0, so none overflows and none is clamped."""
    args = operands(chunks * CHUNK, seed=chunks, decay=decay)
    (o, grads), (want, want_grads) = both(args)
    if decay >= 15.0:
        assert float(args[3].sum(0).min()) < -100.0 * chunks
    assert np.isfinite(np.asarray(o)).all()
    assert off(o, want) < 2e-5
    for name, mine, theirs in zip("q k v g beta".split(), grads,
                                  want_grads):
        assert np.isfinite(np.asarray(mine)).all(), name
        assert off(mine, theirs) < 1e-4, name


def test_keys_that_resemble_each_other_do_not_lose_the_triangular_system():
    """Where the positions of a sequence share a direction (every seeded
    decoder's deeper layers do) a chunk's keys are nearly one vector and
    ``A`` nearly ``beta`` times a triangle of ones: its powers grow
    combinatorially (the nilpotent product of the inverse is off by 10 in
    float32 here and by 1e5 in the MXU's bf16 passes), its inverse by
    blocks does not."""
    q, k, v, g, b = operands(2 * CHUNK, seed=12, decay=0.02)
    shared = jax.random.normal(jax.random.PRNGKey(1), (1, HEADS, D))
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    k = unit(shared + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                               k.shape))
    assert float(jnp.einsum("shd,thd->hst", k, k).min()) > 0.9
    (o, grads), (want, want_grads) = both((q, k, v, g, b))
    assert off(o, want) < 1e-4
    for name, mine, theirs in zip("q k v g beta".split(), grads,
                                  want_grads):
        assert off(mine, theirs) < 1e-3, name


@pytest.mark.parametrize("beta", [0.0, 1.0])
def test_beta_0_writes_nothing_and_beta_1_writes_the_whole_error(beta):
    args = operands(2 * CHUNK, seed=4, beta=beta)
    (o, grads), (want, want_grads) = both(args)
    if beta == 0.0:
        # Nothing is ever written: the state stays zero and so does o; only
        # beta's own gradient is alive.
        assert float(jnp.abs(o).max()) == 0.0
        assert float(jnp.abs(grads[4]).max()) > 0
        np.testing.assert_allclose(grads[4], want_grads[4], rtol=1e-4,
                                   atol=1e-6)
        return
    assert off(o, want) < 2e-5
    for name, mine, theirs in zip("q k v g beta".split(), grads,
                                  want_grads):
        assert off(mine, theirs) < 1e-4, name


def test_a_state_written_in_one_chunk_is_read_in_the_next():
    """One head writes in the first chunk alone (``beta`` 0 afterwards) and
    does not decay: what the later chunks and the later grid steps read is
    the state that crossed their boundaries."""
    seq = 4 * CHUNK
    q, k, v, g, b = operands(seq, seed=6)
    early = (jnp.arange(seq) < CHUNK)[:, None]
    b = jnp.where(early, b, 0.0)
    g = jnp.zeros_like(g)
    args = (q, k, v, g, b)
    # Two grid steps of two chunks each.
    (o, grads), (want, want_grads) = both(args, block=2 * CHUNK)
    assert float(jnp.abs(want[CHUNK:]).max()) > 1e-3
    assert off(o[CHUNK:], want[CHUNK:]) < 2e-5
    # With no decay and no later write the state is constant after the
    # first chunk: o_t = S^T q_t with the first chunk's final state.
    _, state = kda.kda_recurrence(*(x[:CHUNK] for x in args))
    np.testing.assert_allclose(
        o[CHUNK:], jnp.einsum("shk,hkv->shv", q[CHUNK:], state),
        rtol=1e-4, atol=1e-6)
    # The loss on the later chunks reaches the first chunk's keys and
    # values through the boundary alone.
    for name, mine, theirs in zip("q k v g beta".split(), grads,
                                  want_grads):
        assert off(mine, theirs) < 1e-4, name
    assert float(jnp.abs(grads[2][:CHUNK]).max()) > 0
    assert float(jnp.abs(grads[2][CHUNK:]).max()) == 0.0


def test_blocks_of_any_size_give_the_same_result():
    args = operands(4 * CHUNK, seed=8)
    (o, grads), _ = both(args, block=CHUNK)
    (o2, grads2), _ = both(args, block=4 * CHUNK)
    np.testing.assert_allclose(o, o2, rtol=1e-5, atol=1e-7)
    for mine, theirs in zip(grads, grads2):
        np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-6)


def test_bf16_operands_at_the_cells_head_size():
    """Heads of 128 in chunks of 64 with bf16 operands, as the cell runs
    them: against the recurrence in float32 on the same rounded operands
    the products' bf16 shows, a few parts in a thousand."""
    args = operands(2 * 64, seed=3, dtype=jnp.bfloat16, heads=1, d=128)
    (o, grads), (want, want_grads) = both(args, chunk=64)
    assert o.dtype == jnp.float32 and grads[0].dtype == jnp.bfloat16
    assert grads[3].dtype == jnp.float32 and grads[4].dtype == jnp.float32
    assert off(o, want) < 1e-2
    for name, mine, theirs in zip("q k v g beta".split(), grads,
                                  want_grads):
        assert off(mine, theirs) < 2e-2, name


def test_a_term_of_the_delta_rule_left_out_is_seen():
    """The tolerance of these tests tells the delta rule from plain gated
    linear attention (no ``k^T S`` taken off the value)."""
    args = operands(2 * CHUNK, seed=2)
    (o, _), _ = both(args)
    q, k, v, g, b = args

    def no_delta(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = jnp.exp(g_t)[:, :, None] * state \
            + (b_t[:, None] * k_t)[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, plain = jax.lax.scan(no_delta, jnp.zeros((HEADS, D, D)), args)
    assert off(o, plain) > 1e-2


@pytest.mark.parametrize("sign", [1.0, -1.0], ids=["positive", "negative"])
@pytest.mark.parametrize("magnitude", [1e-6, 1e-3, 1.0, 1e2, 1e4])
def test_the_three_bf16_pieces_of_a_float32_sum_to_it_exactly(magnitude,
                                                             sign):
    """``hi + mid + lo == x`` bit for bit, eagerly and jitted (a compiler
    that took ``x - f32(bf16(x))`` for 0 would leave one piece of 8 bits),
    for every mantissa over a decade of magnitudes."""
    x = sign * magnitude * jax.random.uniform(
        jax.random.PRNGKey(int(np.log10(magnitude)) + 7), (64, 128),
        minval=1.0, maxval=10.0)
    for split in (kda._split, jax.jit(kda._split)):
        pieces = split(x)
        assert all(p.dtype == jnp.bfloat16 for p in pieces)
        hi, mid, lo = (np.asarray(p, np.float32) for p in pieces)
        np.testing.assert_array_equal(hi + (mid + lo), np.asarray(x))
        assert np.abs(mid).max() > 0


@pytest.mark.parametrize("orientation", ["S_g", "St_d"])
@pytest.mark.parametrize("decay", [1e-3, 1.0, 25.0, 100.0])
def test_the_three_pass_product_of_the_sums_is_float32s(decay, orientation):
    """The exponents ``S g`` (forward) and their gradient ``S^T d``
    (backward) in three bf16 passes against the float64 product: within 8
    float32 ulps of the sum of the terms' magnitudes (of the result itself
    where all terms share a sign, as the decays' do)."""
    sums = kda._sums(CHUNK)
    assert sums.dtype == jnp.bfloat16
    exact = np.asarray(sums, np.float64)
    seed = int(decay * 1000) % 97
    if orientation == "S_g":
        x = operands(CHUNK, seed=seed, decay=decay, heads=1, d=128)[3][:, 0]
        contract = (1, 0)
    else:
        x = decay * jax.random.normal(jax.random.PRNGKey(seed),
                                      (sums.shape[0], 128))
        contract, exact = (0, 0), exact.T
    got = jax.jit(kda._exact_sums_product, static_argnums=2)(sums, x,
                                                             contract)
    assert got.dtype == jnp.float32
    x64 = np.asarray(x, np.float64)
    want, scale = exact @ x64, exact @ np.abs(x64)
    # Rows of no term (a row against itself as reference) are exactly 0.
    assert np.all(np.abs(np.asarray(got, np.float64) - want)
                  <= 8 * np.finfo(np.float32).eps * scale)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_no_product_of_a_chunk_runs_at_the_highest_precision(direction):
    """The 0/1 matrix's products are three bf16 passes each (one in the
    forward chunk; the same again and its transpose in the backward one),
    and no product of a chunk asks for ``Precision.HIGHEST``: that stays in
    the recurrence, the definition."""
    q, k, v, g, b = (x[:, 0] for x in operands(CHUNK, heads=1, d=128))
    state = jnp.zeros((128, 128), jnp.float32)
    if direction == "forward":
        run = lambda: kda._chunk_forward(q, k, v, g, b[:, None], state,
                                         q.dtype)
    else:
        run = lambda: kda._chunk_backward(q, k, v, g, b[:, None], state, q,
                                          state, q.dtype)
    dots = [e for e in jax.make_jaxpr(run)().eqns
            if e.primitive.name == "dot_general"]
    assert dots and all(e.params["precision"] is None for e in dots)
    rows = kda._sums(CHUNK).shape
    of_sums = [e for e in dots if e.invars[0].aval.shape == rows]
    assert len(of_sums) == (3 if direction == "forward" else 6)
    assert all(v.aval.dtype == jnp.bfloat16
               for e in of_sums for v in e.invars)
    recurrence = jax.make_jaxpr(kda.kda_recurrence)(
        *(x[:2] for x in operands(CHUNK)))
    assert "HIGHEST" in str(recurrence)


def test_chunks_counts_grid_steps_and_chunks():
    # The cell: 8,192 positions, 32 heads, chunks of 64 in blocks of 512.
    assert kda.chunks(8192, 64, 32) == (32 * 16, 32 * 128)
    assert kda.chunks(128, 32, 2) == (2, 8)     # one block: the sequence
    assert kda.chunks(1024, 64, 1, block=64) == (16, 16)


def test_shapes_it_cannot_take_are_refused():
    q, k, v, g, b = operands(CHUNK)
    with pytest.raises(ValueError, match="divisible by the chunk"):
        kda.kda_scan(q[:-8], k[:-8], v[:-8], g[:-8], b[:-8], chunk=CHUNK)
    with pytest.raises(ValueError, match="one shape"):
        kda.kda_scan(q, k, v, g, b[:, :1], chunk=CHUNK)
    with pytest.raises(ValueError, match="no power of two"):
        kda.kda_scan(q[:48], k[:48], v[:48], g[:48], b[:48], chunk=24)
