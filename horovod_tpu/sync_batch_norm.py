"""Synchronized batch normalization across ranks.

Reference: horovod/tensorflow/sync_batch_norm.py:22 (SyncBatchNormalization:
allreduces batch mean and variance across ranks inside the layer) and the
torch equivalent.  On TPU the statistics reduction is a psum over the mesh
axis inside the compiled step — the same pattern flax's BatchNorm supports
via ``axis_name``; this module provides (a) the raw stats reduction for
custom layers and (b) a flax module preconfigured for the framework axis.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import scopes as _scopes
from .process_sets import ProcessSet


def sync_batch_stats(x: jax.Array,
                     *,
                     axis_name: str = "hvd",
                     reduction_axes=None,
                     process_set: Optional[ProcessSet] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Cross-rank batch mean/variance (sync_batch_norm.py:22 semantics).

    Computes E[x] and Var[x] over the local reduction axes *and* the mesh
    axis, using the E[x^2]-E[x]^2 form so one fused psum of (sum, sumsq,
    count) crosses ICI — the reference allreduces mean and variance
    separately; fusing into one collective is the TPU-native improvement."""
    if reduction_axes is None:
        reduction_axes = tuple(range(x.ndim - 1))  # all but features
    members = process_set.members() if process_set is not None else None
    n_local = 1
    for a in reduction_axes:
        n_local *= x.shape[a]
    s = jnp.sum(x, axis=reduction_axes)
    sq = jnp.sum(jnp.square(x), axis=reduction_axes)
    from .ops import collective_ops as C
    # Flatten so ANY reduction_axes (stats of any rank) ride the single
    # collective; reshape back after the split.
    shape, k = s.shape, s.size
    # The scope is what tells a statistic's all-reduce (and its transpose
    # in the backward pass) from a parameter gradient's in the compiled
    # step: both are ``psum_invariant`` under the same flax module.
    with _scopes.scope("hvd::sync_bn_stats"):
        vec = jnp.concatenate([s.ravel(), sq.ravel(),
                               jnp.full((1,), n_local, x.dtype)])
        vec = C.allreduce(vec, C.Sum, axis_name=axis_name, members=members)
        s, sq, cnt = (vec[:k].reshape(shape), vec[k:2 * k].reshape(shape),
                      vec[-1])
    mean = s / cnt
    # Clamp: the E[x^2]-E[x]^2 form can go epsilon-negative in finite
    # precision, and rsqrt(var + eps) downstream must not see it.
    var = jnp.maximum(sq / cnt - jnp.square(mean), 0.0)
    return mean, var


def FusedBatchNorm(**kwargs):
    """Batch norm with float32 statistics and a bf16-foldable epilogue —
    the TPU-shaped batch norm (flax-compatible param/stat tree).

    Why not ``flax.linen.BatchNorm(dtype=float32)`` (what the ResNet ran
    through round 4): that layer upcasts the WHOLE activation tensor to
    f32 for the normalize chain, so every BN in the net pays full-tensor
    bf16->f32->bf16 converts and an f32 elementwise pass — the
    "convert/multiply_reduce fusions ~0.5-1 ms each" in the round-2
    profile.  ``BatchNorm(dtype=bfloat16)`` fixes
    the bandwidth but computes the STATISTICS in bf16, which is numerically
    unacceptable.  This layer splits the two concerns:

    * statistics: one multi-output f32 reduction (sum, sum-of-squares) —
      and under ``axis_name`` ONE psum of the concatenated
      (sum, sumsq, count) vector (the reference's SyncBatchNormalization,
      tensorflow/sync_batch_norm.py:22, allreduces mean and variance
      separately);
    * application: the per-channel scale/offset are FOLDED in f32
      (``a = gamma*rsqrt(var+eps)``, ``b = beta - mean*a``) and applied as
      a pure-bf16 ``x*a + b`` — an elementwise op XLA fuses with the
      surrounding ReLU / residual add / conv epilogue instead of a
      standalone f32 normalize kernel (VERDICT r4 next-step #5; pinned by
      tests/test_models.py's compiled-HLO kernel-count check).

    A plain factory returning a flax module instance (the class is built
    lazily so importing this file does not import flax)."""
    return _fused_bn_cls()(**kwargs)


def _fused_bn_cls():
    global _FusedBatchNorm
    if _FusedBatchNorm is not None:
        return _FusedBatchNorm

    import flax.linen as nn
    from typing import Any, Callable

    # NOTE: named ``BatchNorm`` so flax's auto-naming produces the same
    # submodule keys ("BatchNorm_0", ...) as flax.linen.BatchNorm — the
    # fused layer is checkpoint-compatible drop-in, tree keys included.
    class BatchNorm(nn.Module):
        use_running_average: Optional[bool] = None
        axis_name: Optional[str] = None
        momentum: float = 0.99
        epsilon: float = 1e-5
        dtype: Optional[Any] = None   # apply dtype; default = input dtype
        use_bias: bool = True
        use_scale: bool = True
        bias_init: Callable = nn.initializers.zeros
        scale_init: Callable = nn.initializers.ones

        # The scope is in the ``op_name`` of every operation of the layer,
        # forward and backward; flax's parameter names do not see it.
        @nn.compact
        @_scopes.scope("hvd::batch_norm")
        def __call__(self, x, use_running_average: Optional[bool] = None):
            ura = nn.merge_param("use_running_average",
                                 self.use_running_average,
                                 use_running_average)
            feat = x.shape[-1]
            reduction_axes = tuple(range(x.ndim - 1))
            ra_mean = self.variable("batch_stats", "mean",
                                    lambda: jnp.zeros((feat,), jnp.float32))
            ra_var = self.variable("batch_stats", "var",
                                   lambda: jnp.ones((feat,), jnp.float32))
            scale = self.param("scale", self.scale_init, (feat,),
                               jnp.float32) if self.use_scale else None
            bias = self.param("bias", self.bias_init, (feat,),
                              jnp.float32) if self.use_bias else None
            if ura:
                mean, var = ra_mean.value, ra_var.value
            else:
                xf = x.astype(jnp.float32)
                if self.axis_name is not None and \
                        not self.is_initializing():
                    # ONE collective for the whole stats exchange (flax
                    # likewise skips the collective during init); the
                    # concat-psum lives in sync_batch_stats — one
                    # implementation of the exchange, not two.
                    mean, var = sync_batch_stats(
                        xf, axis_name=self.axis_name,
                        reduction_axes=reduction_axes)
                else:
                    mean = jnp.mean(xf, axis=reduction_axes)
                    var = jnp.maximum(
                        jnp.mean(jnp.square(xf), axis=reduction_axes)
                        - jnp.square(mean), 0.0)
                if not self.is_initializing():
                    m = self.momentum
                    ra_mean.value = m * ra_mean.value + (1 - m) * mean
                    ra_var.value = m * ra_var.value + (1 - m) * var
            a = lax.rsqrt(var + self.epsilon)
            if scale is not None:
                a = a * scale
            b = -mean * a
            if bias is not None:
                b = b + bias
            # dtype=None matches flax BatchNorm's promotion (bf16 input +
            # f32 params -> f32 output), so drop-in users keep their dtype
            # contract; passing an explicit bf16 dtype is the opt-in for
            # the folded bf16 epilogue (what the ResNet does).
            dtype = self.dtype if self.dtype is not None else \
                jnp.promote_types(x.dtype, jnp.float32)
            return x.astype(dtype) * a.astype(dtype) + b.astype(dtype)

    _FusedBatchNorm = BatchNorm
    return BatchNorm


_FusedBatchNorm = None


#: FusedBatchNorm's full kwarg surface (SyncBatchNorm routes here when the
#: caller stays inside it, and to flax BatchNorm otherwise).
_FUSED_KWARGS = frozenset({
    "use_running_average", "axis_name", "momentum", "epsilon", "dtype",
    "use_bias", "use_scale", "bias_init", "scale_init", "name", "parent"})


def SyncBatchNorm(**kwargs):
    """Batch norm synchronized over the framework mesh axis (the
    hvd.SyncBatchNormalization analog, tensorflow/sync_batch_norm.py:22).

    Common configurations get :class:`FusedBatchNorm` (repo-owned: f32
    one-psum stats, foldable application); flax-only kwargs the fused
    layer does not implement (``axis``, ``axis_index_groups``,
    ``param_dtype``, ``use_fast_variance``, ...) keep the documented
    "accepts all flax BatchNorm kwargs" contract by falling back to
    ``flax.linen.BatchNorm`` with the mesh axis preconfigured."""
    kwargs.setdefault("axis_name", "hvd")
    kwargs.setdefault("use_running_average", None)
    if set(kwargs) <= _FUSED_KWARGS:
        return FusedBatchNorm(**kwargs)
    import flax.linen as nn
    return nn.BatchNorm(**kwargs)
