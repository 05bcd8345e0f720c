"""The dropless expert layer (``parallel/moe.py: dropless_expert_ffn``)
and its grouped products, on the CPU in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import grouped
from horovod_tpu.parallel.moe import dropless_expert_ffn

T, D, F, E, K = 48, 16, 24, 16, 4


def weights(seed, experts=E, router_scale=1.0):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))
    return {"x": mk(T, D), "router": mk(D, experts) * router_scale,
            "gate": mk(experts, D, F) * 0.3, "up": mk(experts, D, F) * 0.3,
            "down": mk(experts, F, D) * 0.3}


def per_token_loop(w, first=0, held=None, router=None):
    """The layer written token by token in numpy: softmax over all
    experts, the K largest, renormalised; only experts ``first ..
    first + held - 1`` are applied.  Returns ``(out, pairs here)``."""
    x = np.asarray(w["x"], np.float64)
    router = np.asarray(w["router"] if router is None else router,
                        np.float64)
    held = router.shape[1] - first if held is None else held
    out, here = np.zeros_like(x), 0
    for t in range(x.shape[0]):
        logits = x[t] @ router
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = np.argsort(-p, kind="stable")[:K]
        for e in top:
            if first <= e < first + held:
                here += 1
                g, u, dn = (np.asarray(w[n][e - first], np.float64)
                            for n in ("gate", "up", "down"))
                a = x[t] @ g
                out[t] += p[e] / p[top].sum() * (
                    (a / (1 + np.exp(-a)) * (x[t] @ u)) @ dn)
    return out, here


def layer(w, **kw):
    return dropless_expert_ffn(w["x"], w["router"], w["gate"], w["up"],
                               w["down"], top_k=K, **kw)


def test_whole_layer_equals_the_per_token_loop():
    w = weights(0)
    got = layer(w)
    want, here = per_token_loop(w)
    assert int(got.routed_here) == here == T * K
    np.testing.assert_allclose(got.out, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_shares_add_up_to_the_uncut_layer(held):
    """The share test: every share routes over all E experts and computes
    its own experts' part; the parts of all E / held shares add up to the
    uncut layer, and their pair counts to T x K."""
    w = weights(1)
    total, pairs = 0, 0
    for first in range(0, E, held):
        share = dict(w, **{n: w[n][first:first + held]
                           for n in ("gate", "up", "down")})
        got = layer(share, first_expert=first)
        want, here = per_token_loop(share, first, held)
        assert int(got.routed_here) == here
        np.testing.assert_allclose(got.out, want, rtol=2e-4, atol=2e-5)
        total, pairs = total + got.out, pairs + here
    assert pairs == T * K
    np.testing.assert_allclose(total, per_token_loop(w)[0], rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("skew", ["one_expert", "held_only", "elsewhere"])
def test_skewed_routing_drops_nothing(skew):
    """A share of 2 of 16 experts sized for an even load (a buffer of 48
    rows for 192 pairs): all tokens choose expert 0 first, all K choices
    of all tokens are held here (the chunked path), or none is."""
    w = weights(2)
    bias = np.zeros((D, E), np.float32)
    x = np.abs(np.asarray(w["x"])) + 0.5      # so a column's sign decides
    first, held = 0, 2
    if skew == "one_expert":
        bias[:, 0] = 0.3
    elif skew == "held_only":
        first, held = 0, 4
        bias[:, :4] = 0.3
    else:
        bias[:, 2:] = 0.3
    w = dict(w, x=jnp.asarray(x), router=w["router"] * 0.05 + bias)
    share = dict(w, **{n: w[n][first:first + held]
                       for n in ("gate", "up", "down")})
    got = layer(share, first_expert=first)
    want, here = per_token_loop(share, first, held)
    assert int(got.routed_here) == here
    assert {"one_expert": here >= T, "held_only": here == T * K,
            "elsewhere": here < T}[skew]
    np.testing.assert_allclose(got.out, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("wrt", ["x", "router", "gate", "up", "down"])
def test_gradients_equal_the_dense_layer(wrt):
    """Against every held expert applied to every token and weighted by
    the routing, differentiated by JAX."""
    w = weights(3)
    first, held = 4, 8
    share = dict(w, **{n: w[n][first:first + held]
                       for n in ("gate", "up", "down")})
    seed = jnp.asarray(np.random.RandomState(4).randn(T, D), jnp.float32)

    def dense(w):
        p = jax.nn.softmax(w["x"] @ w["router"], axis=-1)
        top_p, top = jax.lax.top_k(p, K)
        gates = jnp.zeros_like(p).at[jnp.arange(T)[:, None], top].set(
            top_p / top_p.sum(-1, keepdims=True))[:, first:first + held]
        h = jax.nn.silu(jnp.einsum("td,edf->tef", w["x"], w["gate"])) \
            * jnp.einsum("td,edf->tef", w["x"], w["up"])
        return jnp.einsum("te,tef,efd->td", gates, h, w["down"])

    got = jax.grad(lambda w: (layer(w, first_expert=first).out
                              * seed).sum())(share)[wrt]
    want = jax.grad(lambda w: (dense(w) * seed).sum())(share)[wrt]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("skewed", [False, True], ids=["even", "skewed"])
def test_over_the_mesh_equals_one_device(hvd8, skewed):
    """``axis_name``: 8 shards of 6 tokens and 2 experts each, every pair
    through all-to-all and back, equal the single-device layer; skewed,
    every pair of every shard goes to shard 0."""
    w = weights(5, router_scale=0.3)
    if skewed:
        bias = np.zeros((D, E), np.float32)
        bias[:, :2] = 0.3
        w = dict(w, x=jnp.abs(w["x"]) + 0.5, router=w["router"] * 0.05
                 + bias)
    mesh = hvd8.mesh()

    def shard(x, router, gate, up, down):
        got = dropless_expert_ffn(x, router, gate, up, down, top_k=K,
                                  axis_name="hvd")
        return got.out, got.routed_here[None]

    out, received = jax.jit(jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P("hvd"), P(), P("hvd"), P("hvd"), P("hvd")),
        out_specs=(P("hvd"), P("hvd")), check_vma=False))(
            w["x"], w["router"], w["gate"], w["up"], w["down"])
    want = layer(w)
    np.testing.assert_allclose(out, want.out, rtol=2e-4, atol=2e-5)
    assert int(received.sum()) == T * K
    want_loop, _ = per_token_loop(w)
    np.testing.assert_allclose(out, want_loop, rtol=2e-4, atol=2e-5)


def test_gradients_over_the_mesh_equal_one_device(hvd8):
    w = weights(6, router_scale=0.3)
    seed = jnp.asarray(np.random.RandomState(7).randn(T, D), jnp.float32)

    def loss_sharded(x, router, gate, up, down, seed):
        return (dropless_expert_ffn(x, router, gate, up, down, top_k=K,
                                    axis_name="hvd").out * seed).sum()[None]

    def sharded(w):
        return jax.shard_map(
            loss_sharded, mesh=hvd8.mesh(),
            in_specs=(P("hvd"), P(), P("hvd"), P("hvd"), P("hvd"),
                      P("hvd")),
            out_specs=P("hvd"), check_vma=False)(
                w["x"], w["router"], w["gate"], w["up"], w["down"],
                seed).sum()

    got = jax.jit(jax.grad(sharded))(w)
    want = jax.grad(lambda w: (layer(w).out * seed).sum())(w)
    for name in ("x", "gate", "up", "down"):
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("sizes", [[5, 3, 0, 8], [0, 0, 0, 0],
                                   [64, 0, 0, 0], [10, 20, 30, 4],
                                   [0, 1, 0, 40]])
def test_grouped_product_and_its_gradients(sizes):
    rng = np.random.RandomState(8)
    m, k, n = 64, 16, 24
    lhs = jnp.asarray(rng.randn(m, k), jnp.float32)
    rhs = jnp.asarray(rng.randn(len(sizes), k, n), jnp.float32)
    ends = np.cumsum(sizes)
    total = int(ends[-1])
    inside = jnp.asarray(np.arange(m) < total, jnp.float32)[:, None]
    seed = jnp.asarray(rng.randn(m, n), jnp.float32) * inside

    def loop(lhs, rhs):
        out = jnp.zeros((m, n))
        for g, size in enumerate(sizes):
            rows = slice(ends[g] - size, ends[g])
            out = out.at[rows].set(lhs[rows] @ rhs[g])
        return out

    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped.gmm(lhs, rhs, sizes)
    np.testing.assert_allclose(got[:total], loop(lhs, rhs)[:total],
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda l, r: (grouped.gmm(l, r, sizes) * seed).sum(),
                   (0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: (loop(l, r) * seed).sum(), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0][:total], want[0][:total], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    transposed = grouped.gmm(lhs, rhs.swapaxes(1, 2), sizes,
                             transpose_rhs=True)
    np.testing.assert_allclose(transposed[:total], loop(lhs, rhs)[:total],
                               rtol=1e-5, atol=1e-5)


def _by_group(sizes, lhs, rhs):
    """``lhs[rows of g] @ rhs[g]`` group by group in float32 numpy; rows
    beyond the last group zero."""
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    out, ends = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32), \
        np.cumsum(sizes)
    for g, size in enumerate(sizes):
        rows = slice(ends[g] - size, ends[g])
        out[rows] = lhs[rows] @ rhs[g]
    return out


@pytest.mark.parametrize("sizes", [[5, 3, 0, 8], [10, 20, 30, 4],
                                   [0, 1, 0, 40]])
def test_grouped_product_of_bf16_rows_with_stored_float32_matrices(sizes):
    """The dtype contract: ``rhs`` as it is stored, rounded to ``lhs``'s
    dtype inside the kernel; the result and ``dlhs`` in ``lhs``'s dtype,
    ``drhs`` in ``rhs``'s, from float32 sums that no bf16 rounds."""
    rng = np.random.RandomState(9)
    m, k, n = 64, 16, 24
    bf16 = jnp.bfloat16
    lhs = jnp.asarray(rng.randn(m, k), bf16)
    rhs = jnp.asarray(rng.randn(len(sizes), k, n), jnp.float32)
    total = int(np.sum(sizes))
    seed = jnp.asarray(rng.randn(m, n) * (np.arange(m) < total)[:, None],
                       bf16)
    group_sizes = jnp.asarray(sizes, jnp.int32)

    got = grouped.gmm(lhs, rhs, group_sizes)
    rounded = grouped.gmm(lhs, rhs.astype(bf16), group_sizes)
    assert got.dtype == bf16
    np.testing.assert_array_equal(np.asarray(got[:total], np.float32),
                                  np.asarray(rounded[:total], np.float32))
    np.testing.assert_allclose(
        np.asarray(got[:total], np.float32),
        _by_group(sizes, lhs, rhs.astype(bf16))[:total], rtol=1e-2,
        atol=1e-2)

    def loss(l, r):
        return (grouped.gmm(l, r, group_sizes).astype(jnp.float32)
                * seed.astype(jnp.float32)).sum()

    dlhs, drhs = jax.grad(loss, (0, 1))(lhs, rhs)
    assert dlhs.dtype == bf16 and drhs.dtype == jnp.float32
    # drhs[g] = lhs[rows of g]^T @ dout[rows of g]: exact sums of the bf16
    # inputs' products, which a result rounded to bf16 (4e-3) is not.
    rows, dout = np.asarray(lhs, np.float32), np.asarray(seed, np.float32)
    want = np.stack([rows[end - size:end].T @ dout[end - size:end]
                     for size, end in zip(sizes, np.cumsum(sizes))])
    np.testing.assert_allclose(drhs, want, rtol=1e-5, atol=1e-5)
    want_dlhs = _by_group(sizes, seed,
                          np.asarray(rhs.astype(bf16),
                                     np.float32).swapaxes(1, 2))
    np.testing.assert_allclose(np.asarray(dlhs[:total], np.float32),
                               want_dlhs[:total], rtol=1e-2, atol=2e-2)
    # A caller that stores bf16 matrices gets bf16 gradients, as before.
    assert jax.grad(loss, 1)(lhs, rhs.astype(bf16)).dtype == bf16


@pytest.mark.parametrize("rows_dtype", ["float32", "bfloat16"])
def test_a_slab_beyond_the_budget_is_walked_in_tiles(rows_dtype):
    """``k x tn`` float32 beyond ``SLAB_BYTES``: the contraction goes in
    tiles of 1,024, each fetched and cast at every visit; the transposed
    product of the gradient (``k`` and ``n`` swapped) fits a slab."""
    m, k, n, sizes = 16, 4096, 1024, [5, 9]
    assert k * n * 4 > grouped.SLAB_BYTES
    assert grouped._gmm_tiles(m, k, n, jnp.float32) == (16, 1024, 1024)
    assert grouped._gmm_tiles(m, n, k, jnp.float32) == (16, 1024, 2048)
    assert grouped._gmm_tiles(m, 2048, n, jnp.float32) == (16, 2048, 1024)
    rng = np.random.RandomState(10)
    lhs = jnp.asarray(rng.randn(m, k), rows_dtype)
    rhs = jnp.asarray(rng.randn(2, k, n) / k ** 0.5, jnp.float32)
    seed = jnp.asarray(rng.randn(m, n) * (np.arange(m) < 14)[:, None],
                       rows_dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    exact = rows_dtype == "float32"
    tol = dict(rtol=1e-4, atol=1e-4) if exact else dict(rtol=2e-2, atol=2e-2)
    matrices = rhs if exact else rhs.astype(rows_dtype)

    def loss(l, r):
        return (grouped.gmm(l, r, group_sizes).astype(jnp.float32)
                * seed.astype(jnp.float32)).sum()

    got = grouped.gmm(lhs, rhs, group_sizes)
    np.testing.assert_allclose(np.asarray(got[:14], np.float32),
                               _by_group(sizes, lhs, matrices)[:14], **tol)
    dlhs, drhs = jax.grad(loss, (0, 1))(lhs, rhs)
    np.testing.assert_allclose(
        np.asarray(dlhs[:14], np.float32),
        _by_group(sizes, seed, np.asarray(matrices, np.float32)
                  .swapaxes(1, 2))[:14], **tol)
    rows, dout = np.asarray(lhs, np.float32), np.asarray(seed, np.float32)
    np.testing.assert_allclose(
        drhs, np.stack([rows[:5].T @ dout[:5], rows[5:14].T @ dout[5:14]]),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cell,rows,pairs,width", [
    ("sdar", 16384, 512, 768), ("trinity", 16384, 512, 1024),
    ("joyai", 8192, 256, 768)])
def test_a_groups_matrix_is_fetched_once_a_column_tile(cell, rows, pairs,
                                                       width):
    """``grouped.fetches`` at the cells' sizes (a layer and sequence, 16
    held experts a little off the row tile, as a routing leaves them): a
    tile of rows is visited once for every group in it, and a group's
    float32 slab is copied ``n // tn`` times whatever its visits; walked in
    tiles of the contraction it would be copied at every visit."""
    sizes = np.full(16, pairs - 12)
    visits, fetched = grouped.fetches(sizes, rows, 2048, width, jnp.float32)
    straddling = sum(a // 512 != (b - 1) // 512 for a, b in
                     zip(np.cumsum(sizes) - sizes, np.cumsum(sizes)))
    assert visits == 16 + straddling == {512: 31, 256: 23}[pairs]
    assert fetched == 16                    # tn is the whole width
    assert grouped.fetches(sizes, rows, width, 2048,
                           jnp.float32) == (visits, 16)   # and here: 2,048
    # The same walk of a slab beyond the budget: every visit, every tile.
    assert grouped.fetches(sizes, rows, 4096, 1024,
                           jnp.float32) == (visits, visits * 4)
    sizes[3:] = 0                           # empty groups are not fetched
    assert grouped.fetches(sizes, rows, 2048, width, jnp.float32)[1] == 3


# -- the router's variants ------------------------------------------------------
# A sigmoid router with a selection bias, the chosen scores divided by their
# sum plus 1e-20 and scaled (``score_func``, ``selection_bias``,
# ``route_scale``), against the layer written token by token.

SCALE, EPS = 2.826, 1e-20
SIGMOID = dict(score_func="sigmoid", route_scale=SCALE)


def sigmoid_loop(w, first=0, held=None, bias=None, shared=None):
    """Sigmoid scores, the K largest of ``score + bias``, a chosen score
    over the chosen scores' sum times ``SCALE``; experts ``first .. first +
    held - 1`` applied, and ``shared`` (gate, up, down) once a token."""
    x = np.asarray(w["x"], np.float64)
    router = np.asarray(w["router"], np.float64)
    held = router.shape[1] - first if held is None else held
    bias = np.zeros(router.shape[1]) if bias is None else np.asarray(bias)
    ffn = lambda v, g, u, dn: ((v @ g) / (1 + np.exp(-(v @ g)))
                               * (v @ u)) @ dn
    out, here = np.zeros_like(x), 0
    for t in range(x.shape[0]):
        s = 1 / (1 + np.exp(-(x[t] @ router)))
        top = np.argsort(-(s + bias), kind="stable")[:K]
        for e in top:
            if first <= e < first + held:
                here += 1
                out[t] += SCALE * s[e] / (s[top].sum() + EPS) * ffn(
                    x[t], *(np.asarray(w[n][e - first], np.float64)
                            for n in ("gate", "up", "down")))
        if shared is not None:
            out[t] += ffn(x[t], *(np.asarray(m, np.float64)
                                  for m in shared))
    return out, here


def test_the_default_router_is_the_parents_bit_for_bit():
    """The one router's defaults against the parent's formula written out:
    softmax, top-k, the chosen probabilities over their sum floored at
    1e-9, which a top-k sum (at least ``K / E``) never reaches."""
    from horovod_tpu.parallel import moe
    logits = jnp.asarray(np.random.RandomState(20).randn(T, E) * 3,
                         jnp.float32)
    chosen, gates, scores = moe._top_k_gating(logits, K)
    want_gates, want_chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
    want_gates = want_gates / jnp.maximum(
        want_gates.sum(axis=-1, keepdims=True), 1e-9)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(gates, want_gates)
    np.testing.assert_array_equal(scores, jax.nn.softmax(logits, -1))
    w = weights(21)
    np.testing.assert_array_equal(
        layer(w).out, layer(w, score_func="softmax", selection_bias=None,
                            route_scale=1.0).out)


def test_sigmoid_scores_renormalised_and_scaled():
    w = weights(22)
    got = layer(w, **SIGMOID)
    want, here = sigmoid_loop(w)
    assert int(got.routed_here) == here == T * K
    np.testing.assert_allclose(got.out, want, rtol=2e-4, atol=2e-5)
    # The scale is a factor of the result.
    unscaled = layer(w, score_func="sigmoid")
    np.testing.assert_allclose(got.out, SCALE * unscaled.out, rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError, match="score_func"):
        layer(w, score_func="tanh")


def test_selection_bias_changes_the_choice_and_not_the_weight():
    """A bias of 10 on two experts puts them among every token's choices;
    a weight is still the plain score over the chosen plain scores."""
    w = weights(23)
    bias = np.zeros(E, np.float32)
    bias[[3, 11]] = 10.0
    got = layer(w, selection_bias=jnp.asarray(bias), **SIGMOID)
    plain = layer(w, **SIGMOID)
    assert all({3, 11} <= set(row) for row in np.asarray(got.chosen))
    assert not all({3, 11} <= set(row) for row in np.asarray(plain.chosen))
    want, _ = sigmoid_loop(w, bias=bias)
    np.testing.assert_allclose(got.out, want, rtol=2e-4, atol=2e-5)
    # No gradient reaches the bias.
    grad = jax.grad(lambda b: layer(w, selection_bias=b, **SIGMOID)
                    .out.sum())(jnp.asarray(bias))
    np.testing.assert_array_equal(grad, 0)


@pytest.mark.parametrize("wrt", ["x", "router", "gate", "up", "down"])
def test_sigmoid_router_gradients_equal_the_dense_layer(wrt):
    w = weights(24)
    first, held = 4, 8
    share = dict(w, **{n: w[n][first:first + held]
                       for n in ("gate", "up", "down")})
    seed = jnp.asarray(np.random.RandomState(25).randn(T, D), jnp.float32)

    def dense(w):
        s = jax.nn.sigmoid(w["x"] @ w["router"])
        top_s, top = jax.lax.top_k(s, K)
        gates = jnp.zeros_like(s).at[jnp.arange(T)[:, None], top].set(
            SCALE * top_s / (top_s.sum(-1, keepdims=True) + EPS)
        )[:, first:first + held]
        h = jax.nn.silu(jnp.einsum("td,edf->tef", w["x"], w["gate"])) \
            * jnp.einsum("td,edf->tef", w["x"], w["up"])
        return jnp.einsum("te,tef,efd->td", gates, h, w["down"])

    got = jax.grad(lambda w: (layer(w, first_expert=first, **SIGMOID).out
                              * seed).sum())(share)[wrt]
    want = jax.grad(lambda w: (dense(w) * seed).sum())(share)[wrt]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def test_eight_chips_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The share test of a layer with a shared expert: each of 8 chips
    routes over all 16 experts and computes its 2; their parts, with the
    shared expert (which every chip computes alike) counted ONCE, add up to
    the uncut reference layer, and their pair counts to T x K."""
    w = weights(26)
    rng = np.random.RandomState(27)
    shared = tuple(jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)
                   for shape in ((D, F), (D, F), (F, D)))
    bias = rng.randn(E).astype(np.float32) * 0.2
    held = E // 8
    total, pairs = 0, 0
    for first in range(0, E, held):
        share = dict(w, **{n: w[n][first:first + held]
                           for n in ("gate", "up", "down")})
        got = layer(share, first_expert=first,
                    selection_bias=jnp.asarray(bias), **SIGMOID)
        want, here = sigmoid_loop(share, first, held, bias=bias)
        assert int(got.routed_here) == here
        np.testing.assert_allclose(got.out, want, rtol=2e-4, atol=2e-5)
        total, pairs = total + got.out, pairs + here
    assert pairs == T * K
    gate, up, down = shared
    total = total + (jax.nn.silu(w["x"] @ gate) * (w["x"] @ up)) @ down
    uncut, _ = sigmoid_loop(w, bias=bias, shared=shared)
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-5)
    # Counted on every chip, the shared expert would be there 8 times.
    assert float(np.abs(uncut - sigmoid_loop(w, bias=bias)[0]).max()) > 0.1
