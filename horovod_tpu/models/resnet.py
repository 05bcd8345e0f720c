"""ResNet family (v1.5) in flax — the framework's flagship benchmark model.

Reference analog: the reference benchmarks Horovod with tf_cnn_benchmarks /
Keras applications ResNet-50 (docs/benchmarks.rst:27-43,
examples/tensorflow2/tensorflow2_synthetic_benchmark.py:25-80 uses
``applications.ResNet50``).  The model itself is not reference code — this is
a standard ResNet-v1.5 written TPU-first:

* NHWC layout + channels padded to MXU-friendly multiples;
* bfloat16 activations/weights with float32 batch-norm statistics and loss
  (the canonical TPU mixed-precision recipe);
* optional cross-rank synchronized batch norm via ``axis_name`` (the
  hvd.SyncBatchNormalization analog, sync_batch_norm.py:22).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import flax.linen as nn

from .. import scopes as _scopes

ModuleDef = Any

#: The model's parts as they appear in an ``op_name`` (``scopes.py``): the
#: scopes of ``ResNet.__call__`` with four stages.
PARTS = ("stem", "max_pool", "stage1", "stage2", "stage3", "stage4", "head")


def _space_to_depth(x):
    """(N, H, W, C) -> (N, H/2, W/2, 4C); depth flattened as (di, dj, c)."""
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // 2, w // 2, 4 * c)


class SpaceToDepthStem(nn.Module):
    """The stem's 7x7/stride-2 conv re-indexed as a 4x4/stride-1 conv on
    2x2 space-to-depth input (the MLPerf TPU ResNet trick).

    Identical math: y[p,q] = sum_{u,v} w[u,v] x[2p+u-2, 2q+v-2] becomes,
    with u = 2A + di (A in 0..3, di in 0..1) and s2d rows m holding
    original rows 2m+di, a 4-tap conv over m = p-1..p+2, i.e. kernel 4,
    stride 1, padding (1, 2).  The kernel is stored in the ORIGINAL
    (7, 7, C, F) layout (checkpoint-compatible with the naive conv),
    zero-padded to 8x8 and regrouped per call — 12K floats, free next to
    the conv itself.  Why bother: the naive stem conv runs at 224^2
    spatial with 3 input channels — the worst MXU shape in the net and
    the largest single fusion in the round-2 profile; the re-indexed conv
    runs at 112^2 with 12 channels."""
    features: int = 64
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        if x.shape[1] % 2 or x.shape[2] % 2:
            raise ValueError("SpaceToDepthStem requires even H and W, got "
                             f"{x.shape}; use the naive stem (fast_stem="
                             "False) for odd extents")
        c = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (7, 7, c, self.features), jnp.float32)
        k = jnp.pad(kernel, ((0, 1), (0, 1), (0, 0), (0, 0)))
        k = k.reshape(4, 2, 4, 2, c, self.features)
        k = k.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c,
                                                  self.features)
        return jax.lax.conv_general_dilated(
            _space_to_depth(x).astype(self.dtype), k.astype(self.dtype),
            window_strides=(1, 1), padding=((1, 2), (1, 2)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _max_pool_3x3s2(x):
    return nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")


@jax.custom_vjp
def max_pool_eq_grad(x):
    """3x3/stride-2 SAME max pool whose backward pass is written as
    elementwise equality gathers instead of XLA's ``select_and_scatter``
    (1.4 ms/step in the round-2 ResNet profile; no MXU, poorly tiled on
    TPU).  Tie semantics differ deliberately: ``select_and_scatter``
    routes the gradient to the FIRST max of a window, this routes 1/n to
    EACH of n tied maxima — the gradient sum is preserved, which is the
    property training cares about."""
    return _max_pool_3x3s2(x)


def _mp_fwd(x):
    if x.shape[1] % 2 or x.shape[2] % 2:
        # The parity-gather backward assumes SAME padding (0, 1) per
        # spatial dim, which holds only for even extents.
        raise ValueError("max_pool_eq_grad requires even H and W, got "
                         f"{x.shape}; use nn.max_pool for odd extents")
    y = _max_pool_3x3s2(x)
    return y, (x, y)


def _mp_bwd(res, g):
    x, y = res
    n, h, w, c = x.shape
    oh, ow = y.shape[1], y.shape[2]
    neg = jnp.asarray(-jnp.inf, x.dtype)
    # SAME for k=3, s=2, even H: pad lo 0, hi 1.
    xp = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)), constant_values=neg)

    # Tie counts per window, at output resolution (padded -inf never
    # equals y: every window contains at least one real element).
    cnt = jnp.zeros(y.shape, jnp.float32)
    for u in range(3):
        for v in range(3):
            win = jax.lax.slice(xp, (0, u, v, 0),
                                (n, u + 2 * oh - 1, v + 2 * ow - 1, c),
                                (1, 2, 2, 1))
            cnt = cnt + (win == y).astype(jnp.float32)
    gn = g.astype(jnp.float32) / cnt

    def row_gathers(a):
        """a at output rows -> (A, B) at input rows: A[i] = a[i//2]
        (valid for all i: window floor(i/2) always covers row i),
        B[i] = a[i//2 - 1] (covers row i only for even i >= 2)."""
        rep = jnp.repeat(a, 2, axis=1)[:, :h]
        shifted = jnp.pad(rep, ((0, 0), (2, 0), (0, 0), (0, 0)))[:, :h]
        return rep, shifted

    def col_gathers(a):
        rep = jnp.repeat(a, 2, axis=2)[:, :, :w]
        shifted = jnp.pad(rep, ((0, 0), (0, 0), (2, 0), (0, 0)))[:, :, :w]
        return rep, shifted

    row_even = (jnp.arange(h) % 2 == 0) & (jnp.arange(h) >= 2)
    col_even = (jnp.arange(w) % 2 == 0) & (jnp.arange(w) >= 2)
    row_masks = (jnp.ones(h, bool), row_even)
    col_masks = (jnp.ones(w, bool), col_even)

    grad = jnp.zeros(x.shape, jnp.float32)
    ga_rows, gy_rows = row_gathers(gn), row_gathers(y)
    for ri in range(2):
        g_r, y_r = ga_rows[ri], gy_rows[ri]
        g_rc, y_rc = col_gathers(g_r), col_gathers(y_r)
        for ci in range(2):
            mask = (row_masks[ri][None, :, None, None]
                    & col_masks[ci][None, None, :, None])
            eq = (x == y_rc[ci]) & mask
            grad = grad + jnp.where(eq, g_rc[ci], 0.0)
    return (grad.astype(x.dtype),)


max_pool_eq_grad.defvjp(_mp_fwd, _mp_bwd)


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1), use_bias=False)(x)
        y = self.norm()(y)
        y = self.act(y)
        # v1.5: stride on the 3x3, not the 1x1 (what tf_cnn_benchmarks runs).
        y = self.conv(self.filters, (3, 3), self.strides, use_bias=False)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1), use_bias=False)(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 use_bias=False, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None  # set to "hvd" for sync batch norm
    block_cls: ModuleDef = BottleneckBlock
    s2d_stem: bool = False       # space-to-depth re-indexed stem conv
    eq_pool_grad: bool = False   # maxpool backward without select_and_scatter
    fused_bn: bool = True        # f32-stats / bf16-apply folded batch norm

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, dtype=self.dtype, padding="SAME")
        if self.fused_bn:
            # FusedBatchNorm (sync_batch_norm.py): f32 statistics, folded
            # per-channel scale/offset applied in the activation dtype, so
            # the BN+ReLU+add epilogue fuses with its conv neighbors
            # instead of a standalone f32 normalize chain (same
            # param/stat tree as flax BatchNorm).
            from ..sync_batch_norm import FusedBatchNorm
            norm = partial(FusedBatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           axis_name=self.axis_name if train else None)
        else:
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=jnp.float32,
                           axis_name=self.axis_name if train else None)
        # The named scopes are the model's parts in a device trace: they
        # enter the ``op_name`` of every operation traced under them,
        # forward and backward, and leave flax's parameter names alone.
        with _scopes.scope("stem"):
            x = x.astype(self.dtype)
            if self.s2d_stem:
                x = SpaceToDepthStem(self.num_filters, dtype=self.dtype,
                                     name="conv_init")(x)
            else:
                # use_bias=False: the bias feeds straight into BN, which
                # subtracts it right back out (and it kept the param tree
                # from matching SpaceToDepthStem's).
                x = conv(self.num_filters, (7, 7), (2, 2), use_bias=False,
                         name="conv_init")(x)
            x = norm(name="bn_init")(x)
            x = nn.relu(x)
        with _scopes.scope("max_pool"):
            if self.eq_pool_grad:
                x = max_pool_eq_grad(x)
            else:
                x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_size in enumerate(self.stage_sizes):
            with _scopes.scope(f"stage{i + 1}"):
                for j in range(block_size):
                    strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                    x = self.block_cls(self.num_filters * 2 ** i,
                                       strides=strides, conv=conv,
                                       norm=norm)(x)
        with _scopes.scope("head"):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])


def migrate_pre_r3_checkpoint(params):
    """Migrate a checkpoint saved before the stem went bias-free.

    Earlier rounds' ``conv_init`` carried a bias that BN immediately
    subtracted out; dropping it changed the param tree, so old checkpoints
    no longer restore directly.  This deletes the redundant ``bias`` leaf
    (a no-op if already absent) and returns a tree matching the current
    model.  Safe because the bias never affected the function computed."""
    import flax
    flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(params))
    flat = {k: v for k, v in flat.items()
            if not (k[-1] == "bias" and "conv_init" in k)}
    return flax.traverse_util.unflatten_dict(flat)


def create_resnet50(num_classes: int = 1000, dtype=jnp.bfloat16,
                    sync_bn: bool = False, fast_stem: bool = False,
                    fused_bn: bool = True):
    """``fast_stem=True`` enables the two TPU stem optimizations
    (SpaceToDepthStem + max_pool_eq_grad); ``fused_bn`` (default) uses the
    f32-stats/bf16-apply folded batch norm — same math, same param tree."""
    return ResNet50(num_classes=num_classes, dtype=dtype,
                    axis_name="hvd" if sync_bn else None,
                    s2d_stem=fast_stem, eq_pool_grad=fast_stem,
                    fused_bn=fused_bn)
