"""In-jit (SPMD) collective numerics over an 8-device mesh.

Mirrors the reference's parallel suite pattern (test/parallel/test_torch.py,
test_tensorflow.py): compute the expected value locally per rank and compare —
here the "ranks" are mesh slots and the collective runs inside shard_map so it
exercises the real XLA collective lowering.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops import collective_ops as C

N = 8


def run_spmd(hvd_mod, body, *stacked, in_specs=None, out_specs=None):
    """shard_map `body` over the mesh; stacked inputs/outputs [N, ...]."""
    mesh = hvd_mod.mesh()
    in_specs = in_specs or tuple(P("hvd") for _ in stacked)

    def inner(*xs):
        outs = body(*(x[0] for x in xs))
        if not isinstance(outs, tuple):
            outs = (outs,)
        return tuple(o[None] for o in outs)

    res = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs or P("hvd")))(*stacked)
    return res if len(res) > 1 else res[0]


@pytest.fixture()
def per_rank():
    rng = np.random.RandomState(42)
    return jnp.asarray(rng.randn(N, 4, 3).astype(np.float32))


def test_allreduce_sum(hvd8, per_rank):
    out = run_spmd(hvd8, lambda x: C.allreduce(x, C.Sum), per_rank)
    expected = np.sum(np.asarray(per_rank), axis=0)
    for r in range(N):
        np.testing.assert_allclose(out[r], expected, rtol=1e-5)


def test_allreduce_average(hvd8, per_rank):
    out = run_spmd(hvd8, lambda x: C.allreduce(x, C.Average), per_rank)
    expected = np.mean(np.asarray(per_rank), axis=0)
    np.testing.assert_allclose(out[0], expected, rtol=1e-5)
    np.testing.assert_allclose(out[7], expected, rtol=1e-5)


@pytest.mark.parametrize("op,npop", [(C.Min, np.min), (C.Max, np.max),
                                     (C.Product, np.prod)])
def test_allreduce_minmaxprod(hvd8, per_rank, op, npop):
    out = run_spmd(hvd8, lambda x: C.allreduce(x, op), per_rank)
    expected = npop(np.asarray(per_rank), axis=0)
    np.testing.assert_allclose(out[3], expected, rtol=1e-5)


def test_allreduce_int_dtypes(hvd8):
    x = jnp.asarray(np.arange(N * 4).reshape(N, 4).astype(np.int32))
    out = run_spmd(hvd8, lambda t: C.allreduce(t, C.Sum), x)
    np.testing.assert_array_equal(out[0], np.sum(np.asarray(x), axis=0))
    out = run_spmd(hvd8, lambda t: C.allreduce(t, C.Average), x)
    np.testing.assert_array_equal(
        out[0], np.sum(np.asarray(x), axis=0) // N)


def test_allreduce_bf16(hvd8):
    x = jnp.ones((N, 16), jnp.bfloat16)
    out = run_spmd(hvd8, lambda t: C.allreduce(t, C.Sum), x)
    np.testing.assert_allclose(np.asarray(out[0], np.float32), 8.0)


def test_allreduce_prescale_postscale(hvd8, per_rank):
    out = run_spmd(
        hvd8, lambda x: C.allreduce(x, C.Sum, prescale_factor=0.5,
                                    postscale_factor=3.0), per_rank)
    expected = 3.0 * np.sum(0.5 * np.asarray(per_rank), axis=0)
    np.testing.assert_allclose(out[0], expected, rtol=1e-5)


def test_allreduce_subset(hvd8, per_rank):
    members = (1, 3, 5)
    out = run_spmd(hvd8, lambda x: C.allreduce(x, C.Sum, members=members),
                   per_rank)
    arr = np.asarray(per_rank)
    expected = arr[list(members)].sum(axis=0)
    for r in members:
        np.testing.assert_allclose(out[r], expected, rtol=1e-5)
    for r in set(range(N)) - set(members):
        np.testing.assert_allclose(out[r], arr[r], rtol=1e-6)


def test_allreduce_subset_min(hvd8, per_rank):
    members = (0, 2)
    out = run_spmd(hvd8, lambda x: C.allreduce(x, C.Min, members=members),
                   per_rank)
    arr = np.asarray(per_rank)
    np.testing.assert_allclose(out[0], arr[[0, 2]].min(axis=0), rtol=1e-6)
    np.testing.assert_allclose(out[5], arr[5], rtol=1e-6)


def test_grouped_allreduce(hvd8):
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(N, 5).astype(np.float32))
    b = jnp.asarray(rng.randn(N, 2, 2).astype(np.float32))

    def body(x, y):
        return tuple(C.grouped_allreduce([x, y], C.Average))

    oa, ob = run_spmd(hvd8, body, a, b)
    np.testing.assert_allclose(oa[0], np.mean(np.asarray(a), 0), rtol=1e-5)
    np.testing.assert_allclose(ob[0], np.mean(np.asarray(b), 0), rtol=1e-5)


def test_allgather(hvd8, per_rank):
    out = run_spmd(hvd8, lambda x: C.allgather(x), per_rank)
    expected = np.asarray(per_rank).reshape(N * 4, 3)
    for r in (0, 4, 7):
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)


def test_allgather_subset(hvd8, per_rank):
    members = (2, 6)
    out = run_spmd(hvd8, lambda x: C.allgather(x, members=members), per_rank)
    arr = np.asarray(per_rank)
    expected = np.concatenate([arr[2], arr[6]], axis=0)
    np.testing.assert_allclose(out[2], expected, rtol=1e-6)
    np.testing.assert_allclose(out[6], expected, rtol=1e-6)


@pytest.mark.parametrize("root", [0, 3, 7])
def test_broadcast(hvd8, per_rank, root):
    out = run_spmd(hvd8, lambda x: C.broadcast(x, root), per_rank)
    expected = np.asarray(per_rank)[root]
    for r in range(N):
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)


def test_broadcast_bool(hvd8):
    x = jnp.asarray(np.arange(N * 3).reshape(N, 3) % 2 == 0)
    out = run_spmd(hvd8, lambda t: C.broadcast(t, 2), x)
    assert out.dtype == jnp.bool_
    np.testing.assert_array_equal(out[5], np.asarray(x)[2])


def test_broadcast_subset_relative_root(hvd8, per_rank):
    members = (4, 5, 6)
    # set-relative root 1 → global slot 5
    out = run_spmd(hvd8, lambda x: C.broadcast(x, 1, members=members),
                   per_rank)
    arr = np.asarray(per_rank)
    for r in members:
        np.testing.assert_allclose(out[r], arr[5], rtol=1e-6)
    np.testing.assert_allclose(out[0], arr[0], rtol=1e-6)


def test_alltoall(hvd8):
    # rank r sends block j to rank j; classic transpose check.
    x = jnp.asarray(
        np.arange(N * N * 2).reshape(N, N, 2).astype(np.float32))
    out = run_spmd(hvd8, lambda t: C.alltoall(t), x)
    arr = np.asarray(x)
    for r in (0, 3, 7):
        expected = np.stack([arr[src, r] for src in range(N)], axis=0)
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)


def test_alltoall_subset(hvd8):
    members = (1, 2, 5, 6)
    k = len(members)
    x = jnp.asarray(np.arange(N * k * 3).reshape(N, k, 3).astype(np.float32))
    out = run_spmd(hvd8, lambda t: C.alltoall(t, members=members), x)
    arr = np.asarray(x)
    for j, r in enumerate(members):
        expected = np.stack([arr[src, j] for src in members], axis=0)
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)


def test_reducescatter_even(hvd8):
    x = jnp.asarray(np.random.RandomState(1).randn(N, 16, 3).astype(np.float32))
    out = run_spmd(hvd8, lambda t: C.reducescatter(t, C.Sum), x)
    total = np.sum(np.asarray(x), axis=0)
    for r in range(N):
        np.testing.assert_allclose(out[r], total[r * 2:(r + 1) * 2], rtol=1e-5)


def test_reducescatter_uneven_padded(hvd8):
    # dim0=10 over 8 slots → padded to 16, block 2 each; reference gives the
    # first 10%8=2 ranks an extra row instead (collective_operations.cc).
    x = jnp.asarray(np.random.RandomState(2).randn(N, 10, 2).astype(np.float32))
    out = run_spmd(hvd8, lambda t: C.reducescatter(t, C.Sum), x)
    total = np.sum(np.asarray(x), axis=0)
    padded = np.concatenate([total, np.zeros((6, 2), np.float32)], axis=0)
    for r in range(N):
        np.testing.assert_allclose(out[r], padded[r * 2:(r + 1) * 2],
                                   rtol=1e-5, atol=1e-6)


def test_reducescatter_average(hvd8):
    x = jnp.ones((N, 8, 2), jnp.float32)
    out = run_spmd(hvd8, lambda t: C.reducescatter(t, C.Average), x)
    np.testing.assert_allclose(out[0], np.ones((1, 2)), rtol=1e-6)


def test_reducescatter_subset(hvd8):
    members = (0, 4)
    x = jnp.asarray(np.random.RandomState(3).randn(N, 6, 2).astype(np.float32))
    out = run_spmd(hvd8, lambda t: C.reducescatter(t, C.Sum, members=members),
                   x)
    arr = np.asarray(x)
    total = arr[[0, 4]].sum(axis=0)  # [6,2] over 2 members → blocks of 3
    np.testing.assert_allclose(out[0], total[0:3], rtol=1e-5)
    np.testing.assert_allclose(out[4], total[3:6], rtol=1e-5)


def test_barrier_in_jit(hvd8):
    out = run_spmd(hvd8, lambda: (C.barrier(),), out_specs=P("hvd"))
    np.testing.assert_array_equal(np.asarray(out), np.zeros((N,), np.int32))


# -- gradients: the reference registers these by hand
#    (tensorflow/mpi_ops.py:115-537); here they fall out of differentiability.

def test_allreduce_gradient_is_allreduce(hvd8, per_rank):
    def body(x):
        def loss(t):
            return jnp.sum(C.allreduce(t, C.Sum) ** 2)
        return jax.grad(loss)(x)

    out = run_spmd(hvd8, body, per_rank)
    reduced = np.sum(np.asarray(per_rank), axis=0)
    # d/dx_r sum_ranks(sum(reduced^2)) with per-rank loss: grad = 2*reduced
    # allreduced again → N * 2 * reduced... each rank's loss is local, so
    # grad_r = 2*reduced (psum transpose distributes cotangent).
    for r in range(N):
        np.testing.assert_allclose(out[r], 2 * reduced, rtol=1e-4)


def test_broadcast_gradient_reduces_to_root(hvd8, per_rank):
    root = 2

    def body(x):
        def loss(t):
            return jnp.sum(C.broadcast(t, root) * (1.0 + lax.axis_index("hvd")))
        return jax.grad(loss)(x)

    out = run_spmd(hvd8, body, per_rank)
    # Each rank r computes sum(b * (1+r)); cotangent w.r.t. root's tensor is
    # sum_r (1+r) = 36; non-root grads are zero.
    np.testing.assert_allclose(out[root],
                               36.0 * np.ones_like(out[root]), rtol=1e-5)
    for r in set(range(N)) - {root}:
        np.testing.assert_allclose(out[r], np.zeros_like(out[r]), atol=1e-6)


def test_allreduce_product_subset_ring(hvd8):
    """PRODUCT over a member subset (ring-reduce lowering): members see the
    member-product, non-members keep their input (no O(N·|x|) gather)."""
    members = (0, 3, 4)
    vals = np.arange(2, 2 + N).astype(np.float32)  # [2..9]
    x = jnp.asarray(np.stack([np.full((4,), v) for v in vals]))
    out = run_spmd(hvd8, lambda t: C.allreduce(t, C.Product,
                                               members=members), x)
    expected = np.prod(vals[list(members)])
    for r in members:
        np.testing.assert_allclose(out[r], np.full((4,), expected), rtol=1e-5)
    for r in set(range(N)) - set(members):
        np.testing.assert_allclose(out[r], np.asarray(x)[r], rtol=1e-6)


def test_allreduce_product_int_exact(hvd8):
    """Ring-reduce PRODUCT stays exact for integers (a log-exp lowering
    would not)."""
    x = jnp.asarray(np.full((N, 3), 2, dtype=np.int64))
    out = run_spmd(hvd8, lambda t: C.allreduce(t, C.Product), x)
    np.testing.assert_array_equal(out[0], np.full((3,), 2 ** N))


def test_alltoall_subset_multiblock(hvd8):
    """Subset alltoall with multi-row blocks (dim0 = 2k): ppermute ring
    must deliver whole blocks in member order."""
    members = (0, 2, 5, 7)
    k = len(members)
    x = jnp.asarray(
        np.arange(N * 2 * k * 2).reshape(N, 2 * k, 2).astype(np.float32))
    out = run_spmd(hvd8, lambda t: C.alltoall(t, members=members), x)
    arr = np.asarray(x)
    for j, r in enumerate(members):
        expected = np.concatenate(
            [arr[src, 2 * j:2 * (j + 1)] for src in members], axis=0)
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)
