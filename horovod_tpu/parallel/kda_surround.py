"""What surrounds Kimi Delta Attention's scan, as one pass each way.

Between a KDA layer's projections and its scan (``models/kimi_linear.py``)
lie, for ``q``, ``k`` and ``v``, a causal depthwise convolution of a few
taps from a zero history and SiLU, for ``q`` and ``k`` the L2 norm of every
head, and for the decay ``g = -exp(A_log_h) softplus(x + dt_bias)``; behind
the scan, ``y = RMSNorm(o) (a head, a weight [d]) sigmoid(gate)`` in front
of ``W_o``.  As separate XLA passes over ``[S, heads x d]`` that is a dozen
float32 copies a direction and a re-lay of ``f32[S, heads, d]`` in front of
every per-head sum.  Here each is one Pallas pass forward and one backward,
on the grid ``(S / rows, heads)`` of ``qk_rope.py``: a grid step reads the
``(rows, d)`` block at ``(i, h)`` of the projection's output ``[S, heads x
d]`` (a head is a 128-lane block: nothing is reshaped or transposed, and a
per-head sum is a sum over the block's lanes), keeps float32 in registers
and writes the block of the result once, in the layout ``kda._kda`` and
the projections' backward products take:

* :func:`short_conv_silu` (``hvd_kda_conv_fwd``, ``hvd_kda_conv_bwd``):
  ``a_t = SiLU(sum_j w[:, j] x_{t - (taps - 1) + j})`` and, with ``unit``,
  ``a_t unit / sqrt(|a_t|^2 + eps)`` a head.  A block needs the ``taps -
  1`` rows before it: they come as a second block of the same operand,
  ``HALO`` rows high (zero in front of the sequence).  The backward kernel
  reads the ``HALO`` rows after its block too, of ``x`` and of ``dy``,
  makes the pre-activations of the block and of the 8 rows after it again
  (the only residuals are the pass's inputs, which the layer's
  ``jax.checkpoint`` recomputes anyway), and returns ``dx`` in ``x``'s
  layout and ``dw`` as one ``[taps, d]`` partial a block, summed by XLA.
* :func:`decay` (``hvd_kda_decay_fwd``, ``hvd_kda_decay_bwd``): ``g =
  rate softplus(x + bias)``, float32 out, with ``rate = -exp(A_log)`` a
  head laid over its lanes by XLA, whose transpose sums the partials of
  ``drate`` back to ``dA_log``.
* :func:`gated_norm` (``hvd_kda_out_fwd``, ``hvd_kda_out_bwd``): the
  gated norm, ``do``, ``dgate`` and the weight's partials.

Everything between a load and a store is float32 (convolution, SiLU,
softplus, both sigmoids, the norms and their sums), with one rounding at
each store.  The kernels run where a head is the 128 lanes and ``S`` a
multiple of ``HALO`` rows (a block is the largest power of two up to
``ROWS`` that divides ``S``); everywhere else (the small heads of the CPU
rehearsal, ragged sequences) the same formulas run as plain ``jax.numpy``.
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

#: Rows of a block, a power of two (``qk_rope.ROWS``' kind: a grid step
#: costs what it costs whatever it holds).  Alone at the Kimi-Linear cell's
#: shapes 512 / 1,024 / 2,048 rows took 1.88 / 1.58 / 1.47 ms forward and
#: 2.59 / 2.45 / 2.43 ms backward in front of the scan (PERF.md section 6,
#: PR 38).
ROWS = 2048
#: Rows of the block that brings a block's neighbours: bf16's sublane tile.
HALO = 16
#: Rows of a halo a kernel uses (float32's sublane tile): a convolution
#: reaches at most that far.
REACH = 8

_f32 = lambda ref: ref[...].astype(jnp.float32)


def _block_rows(x, heads, taps=1):
    """Rows of a block of ``x [S, heads x d]``, or ``None`` where the
    kernels do not run."""
    rows = math.gcd(x.shape[0], ROWS)
    if x.shape[1] != heads * LANES or rows % HALO or taps - 1 > REACH:
        return None
    return rows


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _call(kernel, name, x, heads, rows, interpret, **kwargs):
    return pl.pallas_call(
        kernel, grid=(x.shape[0] // rows, heads), interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name=name, **kwargs)


def _specs(x, rows, lead=1, sums=None):
    """``(a block of [S, heads x d], the HALO rows before it, those after
    it, a [lead, heads x d] row of weights, a [heads, blocks, sums, d]
    partial; sums = lead unless given)`` of grid step ``(i, h)``; past
    either end of the sequence a halo is the nearest block that exists,
    which its kernel zeroes."""
    per, last = rows // HALO, x.shape[0] // HALO - 1
    return (pl.BlockSpec((rows, LANES), lambda i, h: (i, h)),
            pl.BlockSpec((HALO, LANES),
                         lambda i, h: (jnp.maximum(i * per - 1, 0), h)),
            pl.BlockSpec((HALO, LANES),
                         lambda i, h: (jnp.minimum((i + 1) * per, last), h)),
            pl.BlockSpec((lead, LANES), lambda i, h: (0, h)),
            pl.BlockSpec((1, 1, sums or lead, LANES),
                         lambda i, h: (h, i, 0, 0)))


def _partials(x, heads, rows, lead):
    return _out_struct((heads, x.shape[0] // rows, lead, LANES),
                       jnp.float32, x)


def _over_blocks(partials):
    """``[lead, heads x d]`` of the kernels' ``[heads, blocks, lead, d]``
    partials."""
    lead = partials.shape[2]
    return partials.sum(axis=1).transpose(1, 0, 2).reshape(lead, -1)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


# -- the short convolution, SiLU, the L2 norm -------------------------------

def _shifted(ext, taps, back=True):
    """``[ext[t - (taps - 1 - j)] for j in range(taps)]`` (``back=False``:
    ``ext[t + (taps - 1 - j)]``) at every row ``t`` but the ``taps - 1``
    at the end the roll wraps round."""
    return [ext if j == taps - 1 else pltpu.roll(
        ext, taps - 1 - j if back else ext.shape[0] - (taps - 1 - j), 0)
        for j in range(taps)]


def _taps(shifted, w):
    return sum(w[j:j + 1] * rows for j, rows in enumerate(shifted))


def _with_history(x_ref, before_ref):
    """The block with the ``REACH`` rows before it in front, float32; in
    front of the sequence they are zero."""
    before = _f32(before_ref)[HALO - REACH:]
    return jnp.concatenate(
        [jnp.where(pl.program_id(0) > 0, before, 0.0), _f32(x_ref)], axis=0)


def _inverse_norm(a, eps):
    """``1 / sqrt(|a|^2 + eps)`` a head, as a column."""
    return lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)


def _conv_fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, unit, eps):
    w = w_ref[...]
    pre = _taps(_shifted(_with_history(x_ref, before_ref), w.shape[0]),
                w)[REACH:]
    a = pre * _sigmoid(pre)
    if unit is not None:
        a = a * (_inverse_norm(a, eps) * unit)
    y_ref[...] = a.astype(y_ref.dtype)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, w_ref, dy_ref,
                     dy_after_ref, dx_ref, dw_ref, *, unit, eps):
    rows, taps = x_ref.shape[0], w_ref.shape[0]
    more = pl.program_id(0) < pl.num_programs(0) - 1
    w = w_ref[...]
    # The block, the REACH rows before it and the REACH after it; the
    # pre-activations of the block and of the rows after it, whose
    # gradients reach back into the block; nothing comes back from past
    # the sequence's end.
    history = _shifted(jnp.concatenate(
        [_with_history(x_ref, before_ref), _f32(after_ref)[:REACH]], axis=0),
        taps)
    pre = _taps(history, w)[REACH:]
    s = _sigmoid(pre)
    da = jnp.concatenate(
        [_f32(dy_ref), jnp.where(more, _f32(dy_after_ref)[:REACH], 0.0)],
        axis=0)
    if unit is not None:
        r = _inverse_norm(pre * s, eps)
        n = pre * s * r
        da = r * unit * (da - n * jnp.sum(da * n, axis=-1, keepdims=True))
    dpre = da * (s * (1.0 + pre * (1.0 - s)))
    # The convolution's transpose: dx_t = sum_j w[j] dpre[t + taps - 1 - j].
    dx_ref[...] = _taps(_shifted(dpre, taps, back=False),
                        w)[:rows].astype(dx_ref.dtype)
    for j, x_back in enumerate(history):
        dw_ref[0, 0, j:j + 1, :] = jnp.sum(
            dpre[:rows] * x_back[REACH:REACH + rows], axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _conv(x, w, heads, unit, eps, rows, interpret):
    return _conv_fwd(x, w, heads, unit, eps, rows, interpret)[0]


def _conv_fwd(x, w, heads, unit, eps, rows, interpret):
    block, before, _, weights, _ = _specs(x, rows, w.shape[0])
    y = _call(functools.partial(_conv_fwd_kernel, unit=unit, eps=eps),
              "hvd_kda_conv_fwd", x, heads, rows, interpret,
              out_shape=_out_struct(x.shape, x.dtype, x),
              in_specs=[block, before, weights], out_specs=block)(x, x, w)
    return y, (x, w)


def _conv_bwd(heads, unit, eps, rows, interpret, res, dy):
    x, w = res
    block, before, after, weights, partial = _specs(x, rows, w.shape[0])
    dx, dw = _call(
        functools.partial(_conv_bwd_kernel, unit=unit, eps=eps),
        "hvd_kda_conv_bwd", x, heads, rows, interpret,
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _partials(x, heads, rows, w.shape[0])],
        in_specs=[block, before, after, weights, block, after],
        out_specs=[block, partial])(x, x, x, w, dy, dy)
    return dx, _over_blocks(dw)


_conv.defvjp(_conv_fwd, _conv_bwd)


def short_conv_silu(x, weight, heads: int, unit=None, eps: float = 0.0,
                    interpret=None):
    """``[S, heads x d]`` in ``x``'s dtype: the causal depthwise
    convolution of ``x [S, heads x d]`` (a projection's output) with
    ``weight [heads x d, taps]`` from a zero history, ``sum_j weight[:, j]
    x_{t - (taps - 1) + j}``, then SiLU, then, where ``unit`` is a number,
    every head scaled to that length, ``a unit / sqrt(|a|^2 + eps)``.
    Differentiable in ``x`` and ``weight``."""
    rows = _block_rows(x, heads, weight.shape[1])
    if rows is None:
        return _plain_conv(x, weight, heads, unit, eps)
    return _conv(x, vary_like(weight.astype(jnp.float32).T, x), heads, unit,
                 eps, rows, _interpret(interpret))


def _plain_conv(x, weight, heads, unit, eps):
    seq, taps = x.shape[0], weight.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((taps - 1, 0), (0, 0)))
    a = jax.nn.silu(sum(padded[j:j + seq] * weight[:, j]
                        for j in range(taps)))
    if unit is not None:
        a = a.reshape(seq, heads, -1)
        a = (a * (lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)
                  * unit)).reshape(seq, -1)
    return a.astype(x.dtype)


# -- the decay ---------------------------------------------------------------

def _decay_fwd_kernel(x_ref, bias_ref, rate_ref, g_ref):
    g_ref[...] = rate_ref[...] * _softplus(_f32(x_ref) + bias_ref[...])


def _decay_bwd_kernel(x_ref, bias_ref, rate_ref, dg_ref, dx_ref, dw_ref):
    z = _f32(x_ref) + bias_ref[...]
    dg = dg_ref[...]
    dz = dg * rate_ref[...] * _sigmoid(z)
    dx_ref[...] = dz.astype(dx_ref.dtype)
    dw_ref[0, 0, 0:1, :] = jnp.sum(dz, axis=0, keepdims=True)
    dw_ref[0, 0, 1:2, :] = jnp.sum(dg * _softplus(z), axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _decay(x, bias, rate, heads, rows, interpret):
    return _decay_fwd(x, bias, rate, heads, rows, interpret)[0]


def _decay_fwd(x, bias, rate, heads, rows, interpret):
    block, _, _, row, _ = _specs(x, rows)
    g = _call(_decay_fwd_kernel, "hvd_kda_decay_fwd", x, heads, rows,
              interpret, out_shape=_out_struct(x.shape, jnp.float32, x),
              in_specs=[block, row, row], out_specs=block)(x, bias, rate)
    return g, (x, bias, rate)


def _decay_bwd(heads, rows, interpret, res, dg):
    x, bias, rate = res
    block, _, _, row, partial = _specs(x, rows, sums=2)
    dx, dw = _call(
        _decay_bwd_kernel, "hvd_kda_decay_bwd", x, heads, rows, interpret,
        out_shape=[_out_struct(x.shape, x.dtype, x),
                   _partials(x, heads, rows, 2)],
        in_specs=[block, row, row, block], out_specs=[block, partial],
        )(x, bias, rate, dg)
    dw = _over_blocks(dw)
    return dx, dw[0:1], dw[1:2]


_decay.defvjp(_decay_fwd, _decay_bwd)


def decay(x, dt_bias, a_log, heads: int, interpret=None):
    """``g [S, heads x d]`` float32, the log of the scan's decay: ``-exp(
    a_log_h) softplus(x + dt_bias)`` of the low-rank pre-activation ``x [S,
    heads x d]``, ``dt_bias [heads x d]``, ``a_log [heads]``.
    Differentiable in all three."""
    rate = -jnp.exp(a_log.astype(jnp.float32))
    rows = _block_rows(x, heads)
    if rows is None:
        return jnp.repeat(rate, x.shape[1] // heads) * jax.nn.softplus(
            x.astype(jnp.float32) + dt_bias)
    row = lambda t: vary_like(t.astype(jnp.float32).reshape(1, -1), x)
    return _decay(x, row(dt_bias), row(jnp.repeat(rate, LANES)), heads, rows,
                  _interpret(interpret))


# -- the gated norm ----------------------------------------------------------

def _normed(o_ref, eps):
    """``(o s, s)`` of a block, float32: every head over its root mean
    square, and the reciprocal as a column."""
    o = _f32(o_ref)
    s = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * s, s


def _out_fwd_kernel(o_ref, gate_ref, w_ref, y_ref, *, eps):
    y = _normed(o_ref, eps)[0] * w_ref[...] * _sigmoid(_f32(gate_ref))
    y_ref[...] = y.astype(y_ref.dtype)


def _out_bwd_kernel(o_ref, gate_ref, w_ref, dy_ref, do_ref, dgate_ref,
                    dw_ref, *, eps):
    n_hat, s = _normed(o_ref, eps)
    open_ = _sigmoid(_f32(gate_ref))
    dy = _f32(dy_ref)
    dn = dy * open_                         # the normed head's gradient / w
    dw_ref[0, 0] = jnp.sum(dn * n_hat, axis=0, keepdims=True)
    dn = dn * w_ref[...]
    do = s * (dn - n_hat * jnp.mean(dn * n_hat, axis=-1, keepdims=True))
    do_ref[...] = do.astype(do_ref.dtype)
    dgate = dy * n_hat * w_ref[...] * (open_ * (1.0 - open_))
    dgate_ref[...] = dgate.astype(dgate_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _out(o, gate, w, heads, eps, rows, interpret):
    return _out_fwd(o, gate, w, heads, eps, rows, interpret)[0]


def _out_fwd(o, gate, w, heads, eps, rows, interpret):
    block, _, _, row, _ = _specs(o, rows)
    y = _call(functools.partial(_out_fwd_kernel, eps=eps), "hvd_kda_out_fwd",
              o, heads, rows, interpret,
              out_shape=_out_struct(o.shape, o.dtype, o),
              in_specs=[block, block, row], out_specs=block)(o, gate, w)
    return y, (o, gate, w)


def _out_bwd(heads, eps, rows, interpret, res, dy):
    o, gate, w = res
    block, _, _, row, partial = _specs(o, rows)
    do, dgate, dw = _call(
        functools.partial(_out_bwd_kernel, eps=eps), "hvd_kda_out_bwd",
        o, heads, rows, interpret,
        out_shape=[_out_struct(o.shape, o.dtype, o),
                   _out_struct(gate.shape, gate.dtype, o),
                   _partials(o, heads, rows, 1)],
        in_specs=[block, block, row, block],
        out_specs=[block, block, partial])(o, gate, w, dy)
    return do, dgate, _over_blocks(dw)


_out.defvjp(_out_fwd, _out_bwd)


def gated_norm(o, gate, weight, heads: int, eps: float, interpret=None):
    """``[S, heads x d]`` in ``o``'s dtype: every head of the scan's output
    ``o [S, heads x d]`` under RMSNorm with ``weight [d]`` and ``eps``,
    times ``sigmoid(gate)`` of the output gate's pre-activation ``gate [S,
    heads x d]``.  Differentiable in all three."""
    rows = _block_rows(o, heads)
    if rows is None:
        seq = o.shape[0]
        o32 = o.astype(jnp.float32).reshape(seq, heads, -1)
        s = lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
        return ((o32 * s * weight).reshape(seq, -1) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(o.dtype)
    # The weight laid over every head's lanes, as the decay's rate is; the
    # tiling's transpose sums the heads' gradients.
    weight = jnp.tile(weight.astype(jnp.float32).reshape(1, LANES),
                      (1, heads))
    return _out(o, gate, vary_like(weight, o), heads, eps, rows,
                _interpret(interpret))
