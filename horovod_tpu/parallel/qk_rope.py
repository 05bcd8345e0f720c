"""Head norm and rotary embedding as one pass, into the flash kernels' layout.

Between a query or key projection and the flash kernels a decoder with
QK-norm (``models/sdar_moe.py``, ``models/afmoe.py``) norms every head
(RMSNorm with a learned weight over ``head_dim``), rotates it by its
position (the half-split convention: pairs ``(i, i + head_dim / 2)``) and
lays it out head-major.  As separate XLA passes that is a float32 copy of
the projection's output four times over (norm, two rotated halves, the
concatenation, the transpose), and as many again in each of the
recomputation and the backward pass.  :func:`qk_norm_rope` is all of it as
one Pallas pass a direction:

* **forward** (``hvd_qk_rope_fwd``): grid ``(S / rows, heads)``; reads the
  ``(rows, head_dim)`` block of the projection's output ``x [S, heads x
  head_dim]`` at ``(i, h)`` — never reshaped or transposed by XLA — and
  writes the ``(1, rows, head_dim)`` block at ``(h, i, 0)`` of ``[heads, S,
  head_dim]``, which is what ``flash.flash_attention_heads_first`` takes:
  ``s = rsqrt(mean(x^2) + eps)``, ``n = x s w``, ``y = n cos + roll(n,
  head_dim / 2) sin_signed``, in float32 registers with one rounding at the
  store.  The heads are the inner grid dimension, so a block of the two
  tables is fetched once for all heads of its rows.
* **backward** (``hvd_qk_rope_bwd``): reads the same block of ``x`` and
  ``dy``'s, makes the statistics again (the only residual is ``x``, which
  a caller's ``jax.checkpoint`` recomputes anyway): ``g = dy cos + roll(dy
  sin_signed, head_dim / 2)`` (the rotation's transpose: a roll by half the
  width is its own inverse), ``dw = sum_rows(g x s)`` as one ``[1,
  head_dim]`` partial a block, summed by XLA, and ``dx = s (g w - x s
  mean(g w x s))`` written at ``(i, h)`` of ``[S, heads x head_dim]``: the
  layout the projection's backward products read.

``tables`` (:func:`rope_tables`) are ``cos = [cos, cos]`` and ``sin_signed
= [-sin, +sin]`` as ``[S, head_dim]`` float32, made once a step from the
positions; ``None`` leaves the rotation out (a layer without positions).

The kernels run where ``head_dim`` is a multiple of the 128 lanes and ``S``
of 16 rows (a block is the largest power of two up to ``ROWS`` that divides
``S``); everywhere else (small heads, ragged sequences) the same formulas
run as plain ``jax.numpy`` with the roundings the separate passes had.
Nothing but the shapes chooses.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash import LANES, _out_struct, vary_like

#: Rows of a block, a power of two; a sequence that is no multiple of it
#: takes the largest power of two that divides it.  A grid step costs about
#: 0.25 us whatever it holds and 512 rows of a head cost 0.5 us: blocks of
#: 512 / 1,024 / 2,048 rows measured +9.2 / +9.8 / +10.1 % on the SDAR
#: cell (PERF.md section 6, PR 36); 4,096 rows do not fit the kernels'
#: share of the fast memory.
ROWS = 2048


def rope_tables(positions, head_dim: int, theta: float):
    """``(cos, sin_signed)``, each ``[S, head_dim]`` float32, of rotary
    positions ``positions [S]`` under the half-split convention: ``y = x
    cos + roll(x, head_dim / 2) sin_signed`` is ``[x1 cos - x2 sin, x2 cos
    + x1 sin]``."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _normed(x_ref, eps):
    """``(x s, s)`` of a block, float32: the head's values over their root
    mean square, and its reciprocal as a column."""
    x = x_ref[...].astype(jnp.float32)
    s = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * s, s


def _fwd_kernel(x_ref, w_ref, *refs, eps):
    *tables, y_ref = refs
    n = _normed(x_ref, eps)[0] * w_ref[...]
    if tables:
        cos_ref, sin_ref = tables
        n = n * cos_ref[...] \
            + pltpu.roll(n, n.shape[1] // 2, 1) * sin_ref[...]
    y_ref[0] = n.astype(y_ref.dtype)


def _bwd_kernel(x_ref, w_ref, *refs, eps):
    *tables, dy_ref, dx_ref, dw_ref = refs
    n_hat, s = _normed(x_ref, eps)
    g = dy_ref[0].astype(jnp.float32)
    if tables:
        cos_ref, sin_ref = tables
        g = g * cos_ref[...] \
            + pltpu.roll(g * sin_ref[...], g.shape[1] // 2, 1)
    dw_ref[0, 0] = jnp.sum(g * n_hat, axis=0, keepdims=True)
    gn = g * w_ref[...]
    dx = s * (gn - n_hat * jnp.mean(gn * n_hat, axis=-1, keepdims=True))
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _specs(head_dim, rows, tables):
    """``(x's, [the weight's, the tables'], y's)`` block specifications on
    the grid ``(row block, head)``."""
    shared = [pl.BlockSpec((1, head_dim), lambda i, h: (0, 0))] + [
        pl.BlockSpec((rows, head_dim), lambda i, h: (i, 0))
        for _ in tables or ()]
    return (pl.BlockSpec((rows, head_dim), lambda i, h: (i, h)), shared,
            pl.BlockSpec((1, rows, head_dim), lambda i, h: (h, i, 0)))


def _call(kernel, name, x, heads, rows, interpret, **kwargs):
    return pl.pallas_call(
        kernel, grid=(x.shape[0] // rows, heads), interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name=name, **kwargs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pass(x, weight, tables, heads, eps, rows, interpret):
    return _pass_fwd(x, weight, tables, heads, eps, rows, interpret)[0]


def _pass_fwd(x, weight, tables, heads, eps, rows, interpret):
    seq, head_dim = x.shape[0], x.shape[1] // heads
    x_spec, shared, y_spec = _specs(head_dim, rows, tables)
    y = _call(functools.partial(_fwd_kernel, eps=eps), "hvd_qk_rope_fwd",
              x, heads, rows, interpret,
              out_shape=_out_struct((heads, seq, head_dim), x.dtype, x),
              in_specs=[x_spec] + shared, out_specs=y_spec,
              )(x, weight, *tables or ())
    return y, (x, weight, tables)


def _pass_bwd(heads, eps, rows, interpret, res, dy):
    x, weight, tables = res
    seq, head_dim = x.shape[0], x.shape[1] // heads
    x_spec, shared, y_spec = _specs(head_dim, rows, tables)
    dx, dw = _call(
        functools.partial(_bwd_kernel, eps=eps), "hvd_qk_rope_bwd",
        x, heads, rows, interpret,
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _out_struct((heads, seq // rows, 1, head_dim),
                               jnp.float32, x)],
        in_specs=[x_spec] + shared + [y_spec],
        out_specs=[x_spec, pl.BlockSpec((1, 1, 1, head_dim),
                                        lambda i, h: (h, i, 0, 0))],
        )(x, weight, *tables or (), dy)
    return dx, dw.sum(axis=(0, 1)), None   # the tables take no gradient


_pass.defvjp(_pass_fwd, _pass_bwd)


def _plain(x, weight, tables, heads, eps):
    """The pass as the separate ``jax.numpy`` passes it replaces, with
    their roundings: the norm, rounded to ``x``'s dtype, then the
    rotation, rounded again, then the transpose."""
    seq = x.shape[0]
    x32 = x.reshape(seq, heads, -1).astype(jnp.float32)
    s = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    n = (x32 * s * weight).astype(x.dtype)
    if tables is not None:
        cos, sin_signed = tables
        n32 = n.astype(jnp.float32)
        n = (n32 * cos[:, None] + jnp.roll(n32, n.shape[-1] // 2, axis=-1)
             * sin_signed[:, None]).astype(x.dtype)
    return n.transpose(1, 0, 2)


def qk_norm_rope(x, weight, tables, heads: int, eps: float,
                 interpret=None):
    """``[heads, S, head_dim]``: every head of ``x [S, heads x head_dim]``
    (a query or key projection's output) under RMSNorm with ``weight
    [head_dim]`` and ``eps``, rotated by ``tables`` (:func:`rope_tables`;
    ``None``: no rotation), head-major.  Differentiable in ``x`` and
    ``weight``.  ``interpret=None`` takes the Pallas interpreter off the
    TPU."""
    seq, width = x.shape
    head_dim = width // heads
    rows = math.gcd(seq, ROWS)      # the largest block that divides S
    if head_dim % LANES or rows % 16:
        return _plain(x, weight, tables, heads, eps)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Inside ``shard_map`` the weight and the tables are replicated where
    # ``x`` varies: typed like ``x``, their cotangents are too, and the
    # cast's transpose is the weight's gradient summed over the axis.
    weight = vary_like(weight.astype(jnp.float32).reshape(1, head_dim), x)
    if tables is not None:
        tables = tuple(vary_like(t, x) for t in tables)
    return _pass(x, weight, tables, heads, eps, rows, interpret)
