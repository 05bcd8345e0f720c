"""DistributedOptimizer / gradient layer tests (reference:
test/parallel/test_torch.py optimizer sections + gradient_aggregation tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from tests.test_collective_ops import run_spmd

N = 8

def test_distributed_optimizer_averages_gradients(hvd8):
    opt = hvd.DistributedOptimizer(optax.sgd(1.0))
    params = {"w": jnp.zeros((3,), jnp.float32)}
    per_rank_grads = jnp.asarray(
        np.random.RandomState(0).randn(N, 3).astype(np.float32))

    def body(g):
        state = opt.init(params)
        updates, _ = opt.update({"w": g}, state, params)
        return updates["w"]

    out = run_spmd(hvd8, body, per_rank_grads)
    expected = -np.mean(np.asarray(per_rank_grads), axis=0)
    for r in range(N):
        np.testing.assert_allclose(np.asarray(out[r]), expected, rtol=1e-5)


def test_distributed_optimizer_sum_and_predivide(hvd8):
    opt = hvd.DistributedOptimizer(optax.sgd(1.0),
                                   gradient_predivide_factor=2.0)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    g = jnp.asarray(np.random.RandomState(1).randn(N, 4).astype(np.float32))

    def body(gr):
        state = opt.init(params)
        updates, _ = opt.update({"w": gr}, state, params)
        return updates["w"]

    out = run_spmd(hvd8, body, g)
    # predivide 2: prescale 1/2, average, postscale 2 → same as plain average.
    expected = -np.mean(np.asarray(g), axis=0)
    np.testing.assert_allclose(np.asarray(out[0]), expected, rtol=1e-4)


def test_backward_passes_per_step_accumulates(hvd8):
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), backward_passes_per_step=2)
    params = {"w": jnp.zeros((2,), jnp.float32)}
    g1 = jnp.asarray(np.random.RandomState(2).randn(N, 2).astype(np.float32))
    g2 = jnp.asarray(np.random.RandomState(3).randn(N, 2).astype(np.float32))

    def body(a, b):
        state = opt.init(params)
        u1, state = opt.update({"w": a}, state, params)
        u2, state = opt.update({"w": b}, state, params)
        return u1["w"], u2["w"]

    u1, u2 = run_spmd(hvd8, body, g1, g2)
    # First pass: zero update (aggregation only).
    np.testing.assert_allclose(np.asarray(u1[0]), np.zeros(2), atol=1e-7)
    # Second: mean over ranks of (g1+g2)/2, negated by sgd(1.0).
    expected = -np.mean((np.asarray(g1) + np.asarray(g2)) / 2, axis=0)
    np.testing.assert_allclose(np.asarray(u2[0]), expected, rtol=1e-4)


def test_value_and_grad_wrapper(hvd8):
    per_rank_x = jnp.asarray(
        np.random.RandomState(4).randn(N, 5).astype(np.float32))

    def body(x):
        def loss(w):
            return jnp.sum(w * x)
        val, g = hvd.value_and_grad(loss)(jnp.ones((5,), jnp.float32))
        return g

    out = run_spmd(hvd8, body, per_rank_x)
    expected = np.mean(np.asarray(per_rank_x), axis=0)
    np.testing.assert_allclose(np.asarray(out[0]), expected, rtol=1e-5)


def test_grad_wrapper_sum(hvd8):
    per_rank_x = jnp.asarray(
        np.random.RandomState(5).randn(N, 3).astype(np.float32))

    def body(x):
        g = hvd.grad(lambda w: jnp.sum(w * x), op=hvd.Sum)(
            jnp.ones((3,), jnp.float32))
        return g

    out = run_spmd(hvd8, body, per_rank_x)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.sum(np.asarray(per_rank_x), 0), rtol=1e-5)


def test_adasum_delta_step_ranks_agree(hvd8):
    opt = optax.sgd(0.5)
    params = {"w": jnp.ones((4,), jnp.float32)}
    g = jnp.asarray(np.random.RandomState(6).randn(N, 4).astype(np.float32))

    def body(gr):
        state = opt.init(params)
        new_params, _ = hvd.adasum_delta_step(opt, params, {"w": gr}, state)
        return new_params["w"]

    out = np.asarray(run_spmd(hvd8, body, g))
    for r in range(1, N):
        np.testing.assert_allclose(out[r], out[0], rtol=1e-5)
    assert not np.allclose(out[0], np.ones(4))  # something happened


def test_optimizer_num_groups(hvd8):
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), num_groups=2)
    params = {"a": jnp.zeros((2,)), "b": jnp.zeros((3,)),
              "c": jnp.zeros((4,))}
    rng = np.random.RandomState(7)
    ga = jnp.asarray(rng.randn(N, 2).astype(np.float32))
    gb = jnp.asarray(rng.randn(N, 3).astype(np.float32))
    gc = jnp.asarray(rng.randn(N, 4).astype(np.float32))

    def body(a, b, c):
        state = opt.init(params)
        updates, _ = opt.update({"a": a, "b": b, "c": c}, state, params)
        return updates["a"], updates["b"], updates["c"]

    ua, ub, uc = run_spmd(hvd8, body, ga, gb, gc)
    np.testing.assert_allclose(np.asarray(ua[0]),
                               -np.mean(np.asarray(ga), 0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(uc[0]),
                               -np.mean(np.asarray(gc), 0), rtol=1e-5)


def test_broadcast_variables_tree(hvd8):
    params = {"w": jnp.full((3, 2), 5.0), "b": jnp.arange(4.0)}
    out = hvd.broadcast_variables(params, root_rank=0)
    assert out["w"].shape == (3, 2)
    np.testing.assert_allclose(out["b"], np.arange(4.0))


def test_broadcast_optimizer_state(hvd8):
    opt = optax.adam(1e-3)
    state = opt.init({"w": jnp.ones((3,))})
    out = hvd.broadcast_optimizer_state(state, root_rank=0)
    leaves = jax.tree_util.tree_leaves(out)
    assert len(leaves) == len(jax.tree_util.tree_leaves(state))


def test_broadcast_object_and_allgather_object(hvd8):
    obj = {"epoch": 3, "lr": 0.1}
    assert hvd.broadcast_object(obj) == obj  # emulated: shared process
    objs = hvd.allgather_object([{"r": r} for r in range(N)])
    assert objs == [{"r": r} for r in range(N)]
    with pytest.raises(ValueError):
        hvd.allgather_object({"not": "a list"})


def test_sync_batch_stats(hvd8):
    x = np.random.RandomState(8).randn(N, 16, 4).astype(np.float32)

    def body(xb):
        mean, var = hvd.sync_batch_stats(xb)
        return mean, var

    mean, var = run_spmd(hvd8, body, jnp.asarray(x))
    flat = x.reshape(-1, 4)
    np.testing.assert_allclose(np.asarray(mean[0]), flat.mean(0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(var[0]), flat.var(0),
                               rtol=1e-3, atol=1e-4)


def test_shard_step_helper(hvd8):
    step = hvd.parallel.shard_step(
        lambda w, xb: hvd.allreduce(jnp.sum(xb) * w, op=hvd.Sum),
        in_specs=(P(), P("hvd")), out_specs=P())
    x = jnp.ones((8, 2), jnp.float32)
    out = step(jnp.asarray(2.0), x)
    np.testing.assert_allclose(np.asarray(out), 2.0 * 16.0)


def test_shard_step_passes_no_compiler_option_on_cpu(hvd8, monkeypatch):
    """The options that cut and hide the gradient buckets are the TPU
    compiler's: on a CPU mesh, and on a mesh of one device, the wrapper
    hands ``jax.jit`` none, and the step's sums are what they were."""
    from horovod_tpu import parallel
    assert parallel._compiler_options(hvd.mesh()) == {}
    seen = []
    jit = jax.jit
    monkeypatch.setattr(
        jax, "jit", lambda f, **kw: seen.append(kw) or jit(f, **kw))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), op=hvd.Sum)

    def local_step(w, opt_state, xb):
        grads = jax.grad(lambda w: jnp.sum(xb @ w))(w)
        updates, opt_state = opt.update(grads, opt_state, w)
        return optax.apply_updates(w, updates), opt_state

    step = hvd.parallel.shard_step(local_step,
                                   in_specs=(P(), P(), P("hvd")),
                                   out_specs=(P(), P()))
    w = jnp.zeros((3,), jnp.float32)
    x = jnp.asarray(np.random.RandomState(7).randn(N * 2, 3), jnp.float32)
    new_w, _ = step(w, opt.init(w), x)
    np.testing.assert_allclose(np.asarray(new_w), -np.asarray(x).sum(0),
                               rtol=1e-5)
    assert [kw.get("compiler_options") for kw in seen] == [None]


def test_make_mesh_and_hierarchical(hvd8):
    m = hvd.parallel.make_mesh({"cross": 2, "local": 4})
    assert m.shape == {"cross": 2, "local": 4}
    with pytest.raises(ValueError):
        hvd.parallel.make_mesh({"a": 3})
    hm = hvd.parallel.hierarchical_mesh()
    assert int(np.prod(list(hm.shape.values()))) == N


def test_invariant_grads_not_double_counted(hvd8):
    """shard_map's transpose pre-sums grads of replicated params (vma
    semantics); the optimizer layer must not psum them again."""
    opt = hvd.DistributedOptimizer(optax.sgd(1.0))
    x = jnp.asarray(np.random.RandomState(9).randn(N, 5).astype(np.float32))

    def body(xr):
        params = {"w": jnp.ones((5,), jnp.float32)}  # replicated/invariant
        # grads wrt invariant params arrive already globally summed:
        # grad = sum_r x_r.  Average must yield mean_r x_r, not psum it again
        # (which would give N * sum_r x_r).
        grads = jax.grad(lambda p: jnp.sum(p["w"] * xr))(params)
        state = opt.init(params)
        updates, _ = opt.update(grads, state, params)
        return updates["w"]

    out = run_spmd(hvd8, body, x)
    expected = -np.mean(np.asarray(x), axis=0)
    np.testing.assert_allclose(np.asarray(out[0]), expected, rtol=1e-5)


def test_tape_local_grads_average_exactly(hvd8):
    x = jnp.asarray(np.random.RandomState(10).randn(N, 4).astype(np.float32))

    def body(xr):
        w = jnp.ones((4,), jnp.float32)
        val, g = hvd.value_and_grad(lambda w: jnp.sum(w * xr))(w)
        return g

    out = run_spmd(hvd8, body, x)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.mean(np.asarray(x), 0), rtol=1e-5)


def test_partial_distributed_optimizer(hvd8):
    """Parameters matched by local_filter keep their LOCAL gradients
    (PartialDistributedOptimizer, tensorflow/__init__.py:1204)."""
    opt = hvd.PartialDistributedOptimizer(
        optax.sgd(1.0),
        local_filter=lambda path, leaf: "local" in str(path[0]))
    params = {"shared": jnp.zeros((3,)), "local_emb": jnp.zeros((3,))}
    g = jnp.asarray(np.random.RandomState(11).randn(N, 3).astype(np.float32))

    def body(gr):
        state = opt.init(params)
        # make both grads VARYING per-slot values
        updates, _ = opt.update({"shared": gr, "local_emb": gr}, state,
                                params)
        return updates["shared"], updates["local_emb"]

    shared, local = run_spmd(hvd8, body, g)
    arr = np.asarray(g)
    # shared: averaged over ranks (same on all slots)
    np.testing.assert_allclose(np.asarray(shared[0]), -arr.mean(0),
                               rtol=1e-5)
    # local: each slot keeps its own gradient
    for r in range(N):
        np.testing.assert_allclose(np.asarray(local[r]), -arr[r], rtol=1e-5)


# ---------------------------------------------------------------------------
# 2-D mesh sugar: reduce_axes spans exactly the listed mesh axes
# ---------------------------------------------------------------------------

def test_reduce_axes_2d_mesh_average():
    """DistributedOptimizer(reduce_axes=('dp','sp')) inside a dp×sp
    shard_map: varying grads are averaged over BOTH axes; pre-reduced
    (invariant) grads are normalized, not re-summed."""
    import jax
    import optax
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd

    dp, sp = 2, 4
    mesh = Mesh(np.asarray(jax.devices()[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), reduce_axes=("dp", "sp"))
    g = jnp.asarray(np.random.RandomState(3).randn(dp * sp, 5)
                    .astype(np.float32))
    params = {"w": jnp.zeros((5,))}

    def body(gr):
        # gr: [1, 5] local shard (dim0 split over BOTH axes) -> a per-shard
        # VARYING gradient
        state = opt.init(params)
        updates, _ = opt.update({"w": gr[0]}, state, params)
        return jax.lax.pmean(jax.lax.pmean(updates["w"], "sp"), "dp")

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(("dp", "sp")),),
        out_specs=P()))(g)
    np.testing.assert_allclose(np.asarray(out), -np.asarray(g).mean(0),
                               rtol=1e-5)


def test_reduce_axes_invariant_leaf_normalized():
    """A gradient that the shard_map transpose already globally summed
    (replicated parameter) must be divided by dp*sp, not psum'd again."""
    import jax
    import optax
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd

    dp, sp = 2, 4
    mesh = Mesh(np.asarray(jax.devices()[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), reduce_axes=("dp", "sp"))
    x = jnp.asarray(np.random.RandomState(5).randn(dp * sp, 3)
                    .astype(np.float32))
    w0 = jnp.ones((3,))

    def body(w, xb):
        def loss(p):
            return jnp.sum(p * xb[0])   # per-shard loss on the local row
        g = jax.grad(loss)(w)        # transpose pre-sums over ALL shards
        state = opt.init(w)
        updates, _ = opt.update(g, state, w)
        return updates

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(("dp", "sp"))),
        out_specs=P()))(w0, x)
    # sum of per-shard grads (= sum of rows) averaged over dp*sp shards
    np.testing.assert_allclose(np.asarray(out),
                               -np.asarray(x).mean(0), rtol=1e-5)


def test_reduce_axes_outside_mesh_raises():
    import optax
    import horovod_tpu as hvd
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), reduce_axes=("dp",))
    with pytest.raises(ValueError, match="not bound"):
        opt.update({"w": jnp.ones((2,))}, opt.init({"w": jnp.ones((2,))}),
                   {"w": jnp.ones((2,))})


def test_reduce_axes_param_sharded_leaf_not_summed_over_its_axis():
    """A parameter SHARDED over one of the reduce axes (expert/tensor-
    parallel leaf) must have its gradient psum'd only over the remaining
    axes — summing over the shard axis would mix different parameters —
    while AVERAGE still divides by the full dp*ep degree."""
    import jax
    import optax
    from jax.sharding import Mesh, PartitionSpec as P
    import horovod_tpu as hvd

    dp, ep = 2, 4
    mesh = Mesh(np.asarray(jax.devices()[:dp * ep]).reshape(dp, ep),
                ("dp", "ep"))
    opt = hvd.DistributedOptimizer(optax.sgd(1.0), reduce_axes=("dp", "ep"))
    # one "expert row" per (dp, ep) cell; parameter sharded over ep
    g = jnp.asarray(np.random.RandomState(7).randn(dp, ep, 3)
                    .astype(np.float32))
    w = jnp.zeros((ep, 3), jnp.float32)

    def body(wl, gl):
        # wl: [1, 3] this ep-shard's expert; gl: [1, 1, 3] local grad
        state = opt.init({"e": wl})
        updates, _ = opt.update({"e": gl[0]}, state, {"e": wl})
        return updates["e"]

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("ep"), P("dp", "ep")),
        out_specs=P("ep")))(w, g)   # [ep, 3] reassembled over shards
    # expected: -(sum over dp of g) / (dp * ep), per ep shard
    want = -np.asarray(g).sum(axis=0) / (dp * ep)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)
