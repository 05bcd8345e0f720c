"""Pipeline (parallel/pipeline.py) and tensor (parallel/tensor.py)
parallelism tests: sharded-vs-sequential equivalence on the 8-device CPU
mesh, forward AND backward."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.pipeline import gpipe_spmd, stack_stage_params
from horovod_tpu.parallel.tensor import (column_row_parallel_mlp,
                                         shard_columns, shard_rows)

S = 8  # stages / shards


def _mesh(axis):
    return Mesh(np.asarray(jax.devices()[:S]), (axis,))


def _stages(seed, d=6):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(d, d) * 0.5, jnp.float32)
            for _ in range(S)]


def _sequential(ws, xs):
    y = xs
    for w in ws:
        y = jnp.tanh(y @ w)
    return y


def test_gpipe_matches_sequential_forward():
    M, mb, d = 5, 3, 6
    ws = _stages(0, d)
    xs = jnp.asarray(np.random.RandomState(1).randn(M, mb, d), jnp.float32)
    want = _sequential(ws, xs)

    def stage_fn(p, x):
        return jnp.tanh(x @ p[0])   # local stage slice keeps leading dim 1

    def body(stacked, xs):
        return gpipe_spmd(stage_fn, stacked, xs, axis_name="pp")

    out = jax.jit(jax.shard_map(
        body, mesh=_mesh("pp"), in_specs=(P("pp"), P()),
        out_specs=P()))(stack_stage_params(ws), xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_gpipe_gradients_match_sequential():
    """jax.grad through the scan/ppermute schedule must equal the serial
    model's per-stage gradients (scan+ppermute transpose = the reverse
    pipeline schedule)."""
    M, mb, d = 4, 2, 5
    ws = _stages(2, d)
    xs = jnp.asarray(np.random.RandomState(3).randn(M, mb, d), jnp.float32)
    tgt = jnp.asarray(np.random.RandomState(4).randn(M, mb, d), jnp.float32)

    def serial_loss(stacked):
        y = xs
        for s in range(S):
            y = jnp.tanh(y @ stacked[s])
        return jnp.mean((y - tgt) ** 2)

    def stage_fn(p, x):
        return jnp.tanh(x @ p[0])

    def pipe_loss(stacked, xs, tgt):
        ys = gpipe_spmd(stage_fn, stacked, xs, axis_name="pp")
        return jnp.mean((ys - tgt) ** 2)

    stacked = stack_stage_params(ws)
    want = jax.grad(serial_loss)(stacked)

    def body(stacked, xs, tgt):
        g = jax.grad(pipe_loss)(stacked, xs, tgt)
        return g  # [1, d, d] per shard -> reassembled over 'pp'

    got = jax.jit(jax.shard_map(
        body, mesh=_mesh("pp"), in_specs=(P("pp"), P(), P()),
        out_specs=P("pp")))(stacked, xs, tgt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


def test_column_row_parallel_mlp_matches_dense():
    d, f, b = 6, 32, 4
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(b, d), jnp.float32)
    w1 = jnp.asarray(rng.randn(d, f) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.randn(f, d) * 0.3, jnp.float32)
    want = jax.nn.gelu(x @ w1) @ w2

    cols = jnp.stack(shard_columns(w1, S))   # [S, d, f/S]
    rows = jnp.stack(shard_rows(w2, S))      # [S, f/S, d]

    def body(x, c, r):
        return column_row_parallel_mlp(x, c[0], r[0], axis_name="tp")

    out = jax.jit(jax.shard_map(
        body, mesh=_mesh("tp"), in_specs=(P(), P("tp"), P("tp")),
        out_specs=P()))(x, cols, rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_column_row_parallel_grads_match_dense():
    d, f, b = 4, 16, 3
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(b, d), jnp.float32)
    w1 = jnp.asarray(rng.randn(d, f) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.randn(f, d) * 0.3, jnp.float32)

    def dense_loss(w1, w2):
        return jnp.sum(jax.nn.gelu(x @ w1) @ w2)

    gw1, gw2 = jax.grad(dense_loss, argnums=(0, 1))(w1, w2)

    def body(x, c, r):
        def loss(c0, r0):
            # Replicated scalar; its grad w.r.t. THIS shard's weight
            # slices equals the dense gradient's corresponding blocks
            # (other shards' partial sums are independent of them).
            return jnp.sum(column_row_parallel_mlp(x, c0, r0,
                                                   axis_name="tp"))
        gc, gr = jax.grad(loss, argnums=(0, 1))(c[0], r[0])
        return gc[None], gr[None]

    gc, gr = jax.jit(jax.shard_map(
        body, mesh=_mesh("tp"), in_specs=(P(), P("tp"), P("tp")),
        out_specs=(P("tp"), P("tp"))))(x, jnp.stack(shard_columns(w1, S)),
                                       jnp.stack(shard_rows(w2, S)))
    np.testing.assert_allclose(
        np.asarray(gc).transpose(1, 0, 2).reshape(d, f), np.asarray(gw1),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gr).reshape(f, d),
                               np.asarray(gw2), rtol=1e-4, atol=1e-5)
