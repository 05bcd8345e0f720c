"""DistributedOptimizer / gradient-tape layer — Horovod's L6 on TPU.

Reference surface being reproduced:

* ``hvd.DistributedOptimizer(opt, backward_passes_per_step, compression,
  op, gradient_predivide_factor, groups, process_set)`` — Torch:
  horovod/torch/optimizer.py:36 (per-parameter hooks + async allreduce,
  ``synchronize()`` waits handles, local aggregation when
  backward_passes_per_step > 1); TF: horovod/tensorflow/__init__.py:896.
* ``DistributedGradientTape`` — horovod/tensorflow/__init__.py:1125.
* ``_DistributedAdasumOptimizer`` — horovod/torch/optimizer.py:345: applies
  the optimizer locally to a parameter copy, Adasum-reduces the *delta*, adds
  it back (Adasum must see post-optimizer deltas).

TPU-native design: the optimizer layer is an **optax gradient
transformation**, because under jit the "per-parameter hook + async handle"
machinery is unnecessary — the gradients' reduction is part of one compiled
step program, and cutting it into buckets and hiding them behind compute is
the compiler's work, not the host's.  It does not do that unasked: left to
its defaults XLA combines every gradient all-reduce into one or two
synchronous operations after the backward pass (PERF.md, PR 25).  On a
multi-chip TPU mesh ``hvd.shard_step`` therefore compiles the step with
per-program options (``parallel/__init__.py: _ASYNC_BUCKETS``): the
combiner merges gradients only up to ``_BUCKET_BYTES``, and an all-reduce it
leaves with one operand (a leaf of that size or more) runs as an
asynchronous collective behind the optimizer's update loops — as much of
Horovod's hand-engineered overlap (SURVEY.md §7 "Matching the NCCL
baseline's overlap") as the chip showed to pay; PERF.md, PR 26, has the
sweep and what the overlap with backward convolutions cost.  Buckets under
jit are the compiler's: ``groups=`` stays advisory there, and
``fusion_threshold_bytes`` (``HOROVOD_FUSION_THRESHOLD``) sizes the eager
path's buckets only.  The transformation composes with any optax optimizer
and runs identically:

* inside ``jit``/``shard_map`` (axis bound) — grads reduce via ``lax.psum``;
* eagerly — via the engine (ops/__init__.py dispatch).

``backward_passes_per_step`` reproduces the reference's local gradient
aggregation (tensorflow/gradient_aggregation.py:23,
torch/optimizer.py:126): gradients accumulate locally for N steps; the
allreduce happens only on the Nth, and the inner optimizer sees zero updates
in between (optax.MultiSteps-style gating, implemented explicitly here so
the allreduce sits at the aggregation boundary exactly like the reference).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from . import ops as _ops
from . import scopes as _scopes
from .compression import Compression
from .ops import ReduceOp
from .process_sets import ProcessSet, global_process_set

try:
    import optax
except ImportError:  # pragma: no cover - optax is baked into the image
    optax = None


def _axis_name() -> str:
    from . import core as _core
    return _core.mesh_axis() if _core.is_initialized() else "hvd"


def _axis_bound(axis: str) -> bool:
    try:
        jax.lax.axis_index(axis)
        return True
    except NameError:
        return False


def _is_invariant(x, axis: str) -> bool:
    """True when ``x`` does not vary over the mesh axis (vma semantics):
    under shard_map, gradients w.r.t. replicated parameters come back
    *already psum'd* by the transpose rule, so they are axis-invariant.

    Under ``shard_map(check_vma=False)`` or a bare ``axis_env`` trace
    nothing is tracked: every value types as invariant and no transpose
    pre-sums anything, so there every gradient is local and "varies"."""
    return axis not in jax.typeof(x).vma and _vma_tracked((axis,))


def _vma_tracked(axes) -> bool:
    """Whether the enclosing trace tracks varying manual axes: an
    explicitly varying probe value carries them exactly when it does."""
    return bool(jax.typeof(
        jax.lax.pcast(jnp.zeros(()), axes, to="varying")).vma)


def _to_varying(tree, axis: str):
    """pcast every invariant leaf to varying — used to recover *local*
    gradient semantics before an explicit Horovod-style allreduce."""
    def cast(x):
        if _is_invariant(x, axis):
            return jax.lax.pcast(x, axis, to="varying")
        return x

    return jax.tree_util.tree_map(cast, tree)


def _reduce_grad_leaf(l, op, compression, prescale, postscale, process_set):
    """Allreduce one gradient leaf with pre-summed-awareness.

    In-trace, an axis-invariant gradient is one XLA already globally summed
    (shard_map transpose of a replicated parameter).  For those: SUM is
    complete, AVERAGE divides by the participant count — running a literal
    psum would silently multiply by N.  Varying (local) gradients get the
    normal collective.  This mirrors what the reference gets implicitly from
    always seeing *local* gradients in framework hooks."""
    axis = _axis_name()
    if _axis_bound(axis) and _is_invariant(l, axis):
        members = None if process_set is None or process_set.ranks is None \
            else process_set.members()
        n = len(members) if members is not None else jax.lax.axis_size(axis)
        from .ops import collective_ops as C
        l = C._apply_scale(l, prescale)
        if op == ReduceOp.AVERAGE:
            l = l / n
        elif op != ReduceOp.SUM:
            raise ValueError(
                f"gradient leaf is axis-invariant (already reduced); only "
                f"Sum/Average make sense, got {op!r}")
        return C._apply_scale(l, postscale)
    return _ops.allreduce(l, op=op, compression=compression,
                          prescale_factor=prescale,
                          postscale_factor=postscale,
                          process_set=process_set)


def _reduce_multi_axis_leaf(l, op, prescale, postscale, reduce_axes,
                            param=None):
    """Reduce one gradient leaf over a SUBSET of a multi-axis mesh's axes
    (the dp×sp / dp×tp / dp×ep cases the reference never reaches —
    SURVEY.md §2.3).

    Semantics: psum over whichever of ``reduce_axes`` the leaf is still
    varying on (vma); leaves the shard_map transpose already summed (grads
    of replicated params arrive invariant) are not re-summed.  Axes the
    PARAMETER itself varies on are excluded: a parameter sharded over an
    axis (expert weights over 'ep') has per-shard-distinct gradients
    there — summing would mix different parameters elementwise.

    AVERAGE divides by the product of all reduce_axes sizes uniformly.
    That is the global token mean ONLY when the batch/token dimension is
    sharded over EVERY listed axis (the dp and dp×ep layouts); list
    exactly the axes the batch is sharded over.  A tensor-parallel-style
    axis that shards weights but NOT the batch must not appear in
    reduce_axes — its gradients are already complete per shard and the
    uniform divisor would shrink them by that axis's size."""
    vma = jax.typeof(l).vma
    param_vma = jax.typeof(param).vma if param is not None else frozenset()
    from .ops import collective_ops as C
    l = C._apply_scale(l, prescale)
    varying = tuple(a for a in reduce_axes
                    if a in vma and a not in param_vma)
    if varying:
        l = jax.lax.psum(l, varying)
    if op == ReduceOp.AVERAGE:
        n = 1
        for a in reduce_axes:
            n *= jax.lax.axis_size(a)
        l = l / n
    return C._apply_scale(l, postscale)


def _allreduce_tree(grads, op, compression, prescale, postscale, process_set,
                    groups=None, reduce_axes=None, params=None):
    """Tree-map allreduce; ``groups`` (list of param-name buckets) reproduces
    the reference's `groups` option (torch/optimizer.py grouped allreduce) —
    under jit the grouping is advisory since XLA's combiner re-buckets, so we
    lower each group through grouped_allreduce for eager parity.
    ``reduce_axes`` switches to multi-axis mesh reduction (2-D sugar)."""
    if reduce_axes is not None:
        axes = tuple(reduce_axes)
        # Leaf-independent validation, once per tree (not once per leaf).
        for a in axes:
            try:
                jax.lax.axis_size(a)
            except NameError:
                raise ValueError(
                    f"reduce_axes={axes}: axis {a!r} is not bound — "
                    f"multi-axis gradient reduction only works inside "
                    f"shard_map over a mesh carrying those axes")
        # Under shard_map(check_vma=False) vma tracking is OFF: every
        # value types as frozenset() and would be treated as pre-reduced,
        # silently skipping the psum.  Then we cannot tell which axes a
        # gradient is still local on; fail loudly rather than diverge
        # quietly.
        if not _vma_tracked(axes):
            raise ValueError(
                "reduce_axes requires varying-manual-axes tracking to "
                "tell local gradients from pre-reduced ones; use "
                "shard_map(..., check_vma=True) (the default) with "
                "DistributedOptimizer(reduce_axes=...)")
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            raise ValueError(
                f"reduce_axes supports Sum/Average gradients, got {op!r}")
        if params is None:
            # Without params we cannot tell an unsummed gradient from a
            # sharded parameter's own gradient on a listed axis — the
            # wrong guess silently elementwise-sums DIFFERENT parameters
            # (e.g. experts).  Fail loudly instead.
            raise ValueError(
                "DistributedOptimizer(reduce_axes=...) needs the params "
                "argument: call opt.update(grads, state, params) so "
                "sharded-parameter leaves can be excluded from their own "
                "shard axis")
        return jax.tree_util.tree_map(
            lambda l, p: _reduce_multi_axis_leaf(
                l, op, prescale, postscale, axes, param=p),
            grads, params)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if groups:
        axis = _axis_name()
        bound = _axis_bound(axis)
        reduced = list(leaves)
        import numpy as np
        idx_groups = np.array_split(np.arange(len(leaves)), groups) \
            if isinstance(groups, int) else groups
        for g in idx_groups:
            live = [i for i in g
                    if not (bound and _is_invariant(leaves[i], axis))]
            pre = [i for i in g if i not in set(live)]
            for i in pre:  # already-reduced leaves: local rescale only
                reduced[i] = _reduce_grad_leaf(
                    leaves[i], op, compression, prescale, postscale,
                    process_set)
            if live:
                out = _ops.grouped_allreduce(
                    [leaves[i] for i in live], op=op, compression=compression,
                    prescale_factor=prescale, postscale_factor=postscale,
                    process_set=process_set)
                for i, o in zip(live, out):
                    reduced[i] = o
        return jax.tree_util.tree_unflatten(treedef, reduced)
    axis = _axis_name()
    if not _axis_bound(axis) and len(leaves) > 1 and \
            op in (ReduceOp.AVERAGE, ReduceOp.SUM):
        # Eager path: each dispatch is a separate compiled collective, so
        # bucket leaves with the native fusion planner (controller.cc:901
        # FuseResponses) up to the fusion threshold — the Horovod tensor-
        # fusion behavior the compiled path gets for free from XLA's
        # combiner.  Autotune (HOROVOD_AUTOTUNE=1) scores these windows.
        from . import core as _core
        from .csrc import plan_fusion
        import time as _time
        pm = _core._state.param_manager
        threshold = pm.fusion_threshold_bytes if pm is not None else \
            _core._state.config.fusion_threshold_bytes
        entries = [(str(i), str(l.dtype), int(l.size * l.dtype.itemsize),
                    int(op), 0) for i, l in enumerate(leaves)]
        buckets = plan_fusion(entries, threshold)
        reduced = list(leaves)
        t0 = _time.perf_counter()
        total_bytes = sum(e[2] for e in entries)
        # True multi-process dispatch packs each bucket into ONE flat
        # fusion buffer (single device transfer + single collective — the
        # reference's fusion-buffer data path, operations.cc:519), with
        # fp16/bf16 compression applied once to the packed buffer (the
        # planner's buckets are same-dtype, so one cast covers the whole
        # bucket — the per-tensor grouped compress path documented as the
        # gap in docs/tensor_fusion.md until ISSUE 5).  Emulated mode
        # keeps grouped dispatch: its tensors are per-rank stacks the
        # flat packing would mangle, and it has no per-tensor assembly
        # cost to amortize.
        topo = _core._state.topology
        # Only the known-ELEMENTWISE compressors may compress the packed
        # buffer once (compress(concat) == concat(compress) holds for
        # casts only): a custom Compressor subclass (e.g. per-tensor
        # scaled quantization) keeps the per-tensor grouped path so its
        # per-tensor semantics survive.
        use_fused = (topo is not None and topo.size > 1
                     and not topo.emulated
                     and compression in (Compression.none,
                                         Compression.fp16,
                                         Compression.bf16))
        for bucket in buckets:
            if use_fused:
                outs = _ops._fused_allreduce(
                    [leaves[i] for i in bucket], op=op,
                    compression=compression,
                    prescale_factor=prescale, postscale_factor=postscale,
                    process_set=process_set)
            else:
                outs = _ops.grouped_allreduce(
                    [leaves[i] for i in bucket], op=op,
                    compression=compression,
                    prescale_factor=prescale, postscale_factor=postscale,
                    process_set=process_set)
            for i, o in zip(bucket, outs):
                reduced[i] = o
        if pm is not None and pm.enabled and not pm.converged:
            jax.block_until_ready(reduced)
            pm.record_sample(total_bytes, _time.perf_counter() - t0)
        return jax.tree_util.tree_unflatten(treedef, reduced)
    reduced = [
        _reduce_grad_leaf(l, op, compression, prescale, postscale,
                          process_set)
        for l in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, reduced)


def _scoped(transformation, scope: str):
    """``transformation`` with its update traced under
    ``jax.named_scope(scope)``: the scope lands in the ``op_name`` of every
    operation the update compiles to, which is how a device trace tells the
    optimizer from the model.  Metadata only: state and arithmetic are
    the wrapped transformation's."""
    inner = optax.with_extra_args_support(transformation)

    def update_fn(updates, state, params=None, **extra_args):
        with _scopes.scope(scope):
            return inner.update(updates, state, params, **extra_args)

    return optax.GradientTransformationExtraArgs(inner.init, update_fn)


#: Every transformation this module returns traces its update under this
#: scope (``hvd::<kind>``, the convention of ops/eager.py); inside it,
#: ``reduce_gradients`` (the gradients' reduction or, where they arrive
#: already summed, their rescaling) and ``inner_update`` (the wrapped
#: optimizer).
_OPTIMIZER_SCOPE = _scopes.OPTIMIZER


class DistributedState(NamedTuple):
    inner_state: Any
    acc_grads: Any        # local aggregation buffer (backward_passes_per_step)
    counter: jax.Array    # passes since last sync


def distributed_gradient_transformation(
        op: ReduceOp = ReduceOp.AVERAGE,
        compression=Compression.none,
        gradient_predivide_factor: float = 1.0,
        process_set: ProcessSet = global_process_set,
        groups=None,
        reduce_axes: Optional[Sequence[str]] = None):
    """The bare allreduce-gradients transformation (composable with any
    optax chain).  Equivalent of wrapping compute_gradients
    (tensorflow/__init__.py:896 DistributedOptimizer._compute_gradients).
    Local gradient aggregation (``backward_passes_per_step``) lives in
    ``DistributedOptimizer``, which gates the whole chain."""
    return _scoped(_gradient_reduction(
        op, compression, gradient_predivide_factor, process_set, groups,
        reduce_axes), _OPTIMIZER_SCOPE)


def _gradient_reduction(op, compression, gradient_predivide_factor,
                        process_set, groups, reduce_axes):
    """``distributed_gradient_transformation`` before its scope, for
    ``DistributedOptimizer`` to put under its own."""
    if optax is None:
        raise ImportError("optax is required for the optimizer layer")

    # gradient_predivide_factor splits the averaging divide across pre/post
    # scale (reference: torch/optimizer.py gradient_predivide_factor —
    # prescale = 1/(factor*size) handled by the op layer when op=Average).
    if gradient_predivide_factor != 1.0:
        if op != ReduceOp.AVERAGE:
            raise ValueError("gradient_predivide_factor supported only with "
                             "op=Average (torch/optimizer.py:64)")
        prescale = 1.0 / gradient_predivide_factor
        postscale = gradient_predivide_factor
    else:
        prescale = postscale = 1.0

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        with _scopes.scope("reduce_gradients"):
            reduced = _allreduce_tree(updates, op, compression, prescale,
                                      postscale, process_set, groups,
                                      reduce_axes=reduce_axes, params=params)
        return reduced, state

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(optimizer,
                         named_parameters=None,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         op: ReduceOp = ReduceOp.AVERAGE,
                         gradient_predivide_factor: float = 1.0,
                         num_groups: int = 0,
                         groups=None,
                         process_set: ProcessSet = global_process_set,
                         reduce_axes: Optional[Sequence[str]] = None):
    """Wrap an optax optimizer with Horovod-style gradient reduction
    (hvd.DistributedOptimizer, torch/optimizer.py:36 /
    tensorflow/__init__.py:896).

    Returns an optax GradientTransformation: ``update(grads, state, params)``
    (1) accumulates grads locally for ``backward_passes_per_step`` passes,
    (2) allreduces at the boundary (with compression / predivide / groups /
    process set), (3) applies the wrapped optimizer.  Between boundaries the
    parameter updates are zero, mirroring the reference where ``step()``
    only synchronizes on aggregation boundaries (torch/optimizer.py:126).

    ``named_parameters`` is accepted for API parity and ignored: JAX pytrees
    carry structure, and under jit issue-order is program order so the
    reference's name-based negotiation isn't needed (SURVEY.md §1 TPU note).

    Adasum: pass ``op=hvd.Adasum``.  For SGD-family optimizers reducing the
    gradient is equivalent to the reference's delta reduction
    (_DistributedAdasumOptimizer, torch/optimizer.py:345: delta = lr*grad is
    proportional to grad); for adaptive optimizers prefer reducing deltas
    explicitly via ``adasum_delta_step``.

    2-D+ meshes: ``reduce_axes=("dp", "sp")`` makes the gradient reduction
    span exactly those mesh axes inside a multi-axis ``shard_map`` (e.g.
    data-parallel × sequence-parallel training): leaves still varying on a
    listed axis are psum'd over it, pre-reduced leaves are not re-summed,
    and Average divides by the product of the listed axis sizes.  Beyond
    the reference's single-communicator scope; see docs/
    sequence_parallelism.md.
    """
    if optax is None:
        raise ImportError("optax is required for the optimizer layer")
    if num_groups and groups is None:
        groups = num_groups
    if reduce_axes is not None:
        if process_set is not global_process_set:
            raise ValueError("reduce_axes and process_set are mutually "
                             "exclusive (subset semantics live on the 1-D "
                             "framework axis)")
        if compression is not Compression.none or groups is not None:
            # In-trace multi-axis psum has no compression/grouping stage;
            # silently ignoring these options would let a user believe
            # fp16-compressed or bucketed reduction is active.
            raise ValueError("compression/groups are not supported with "
                             "reduce_axes (XLA fuses and buckets in-trace "
                             "collectives itself)")
    allreduce_t = _gradient_reduction(
        op, compression, gradient_predivide_factor, process_set, groups,
        reduce_axes)
    optimizer = _scoped(optimizer, "inner_update")
    n = max(1, int(backward_passes_per_step))

    def _maybe_analyzed(t):
        # HVD_ANALYZE=1: the first eager update runs the jaxpr collective-
        # consistency checker over this optimizer's reduction program and
        # publishes its collective census (analysis/hook.py).  In-trace
        # updates are covered by the shard_step-level hook instead.
        from .analysis import hook as _analysis_hook
        if _analysis_hook.enabled():
            return _analysis_hook.wrap_optimizer(t)
        return t

    if n == 1:
        return _maybe_analyzed(_scoped(optax.chain(allreduce_t, optimizer),
                                       _OPTIMIZER_SCOPE))

    def init_fn(params):
        return DistributedState(
            inner_state=optimizer.init(params),
            acc_grads=jax.tree_util.tree_map(jnp.zeros_like, params),
            counter=jnp.zeros((), jnp.int32),
        )

    def update_fn(updates, state, params=None):
        acc = jax.tree_util.tree_map(lambda a, g: a + g,
                                     state.acc_grads, updates)
        counter = state.counter + 1
        sync = counter >= n
        axis = _axis_name()
        bound = _axis_bound(axis)
        leaves = jax.tree_util.tree_leaves(acc)
        all_invariant = bound and all(_is_invariant(l, axis) for l in leaves)

        # Average over the local passes like the reference's helper
        # (gradient_aggregation.py averages by backward_passes_per_step).
        def sync_branch(acc_and_inner):
            acc_, inner_ = acc_and_inner
            scaled = jax.tree_util.tree_map(lambda a: a / n, acc_)
            reduced, _ = allreduce_t.update(scaled, optax.EmptyState(),
                                            params)
            su, si = optimizer.update(reduced, inner_, params)
            return su, si, jax.tree_util.tree_map(jnp.zeros_like, acc_)

        if all_invariant:
            # In-trace with pre-reduced gradients: the "allreduce" is a pure
            # division (_reduce_grad_leaf), so computing both branches and
            # selecting with jnp.where costs no communication and keeps
            # vma types consistent (everything invariant).
            sync_updates, sync_inner, _ = sync_branch(
                (acc, state.inner_state))

            def sel(a, b):
                return jnp.where(sync, a, b)

            new_updates = jax.tree_util.tree_map(
                lambda u, z: sel(u, jnp.zeros_like(z)), sync_updates, acc)
            new_inner = jax.tree_util.tree_map(sel, sync_inner,
                                               state.inner_state)
            new_acc = jax.tree_util.tree_map(
                lambda a: sel(jnp.zeros_like(a), a), acc)
        else:
            # Varying (true local) gradients or eager mode: a real collective
            # runs on sync — gate it with lax.cond so accumulation steps stay
            # communication-free (the whole point of
            # backward_passes_per_step).  Branch outputs are pcast to varying
            # for consistent cond typing.
            def _vary(tree):
                if not bound:
                    return tree

                def cast(x):
                    if _is_invariant(x, axis):
                        return jax.lax.pcast(x, axis, to="varying")
                    return x

                return jax.tree_util.tree_map(cast, tree)

            def do_sync(arg):
                return _vary(sync_branch(arg))

            def no_sync(arg):
                acc_, inner_ = arg
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc_)
                return _vary((zeros, inner_, acc_))

            new_updates, new_inner, new_acc = jax.lax.cond(
                sync, do_sync, no_sync, (acc, state.inner_state))
        new_counter = jnp.where(sync, 0, counter)
        return new_updates, DistributedState(new_inner, new_acc, new_counter)

    return _maybe_analyzed(_scoped(
        optax.GradientTransformation(init_fn, update_fn), _OPTIMIZER_SCOPE))


def PartialDistributedOptimizer(optimizer,
                                local_filter: Callable[[tuple, Any], bool],
                                compression=Compression.none,
                                op: ReduceOp = ReduceOp.AVERAGE,
                                process_set: ProcessSet = global_process_set):
    """DistributedOptimizer that leaves some parameters LOCAL (un-reduced).

    Reference: PartialDistributedGradientTape / PartialDistributedOptimizer
    (tensorflow/__init__.py:1204; keras PartialDistributedOptimizer) —
    registered local variables (e.g. per-rank embeddings or adapters) skip
    the allreduce while everything else synchronizes.

    ``local_filter(path, leaf) -> True`` marks a gradient leaf as local.
    ``path`` is the jax tree path (tuple of keys)."""
    if optax is None:
        raise ImportError("optax is required for the optimizer layer")

    def init_fn(params):
        return optimizer.init(params)

    optimizer = _scoped(optimizer, "inner_update")

    def update_fn(updates, state, params=None):
        flat, treedef = jax.tree_util.tree_flatten_with_path(updates)
        reduced = []
        with _scopes.scope("reduce_gradients"):
            for path, leaf in flat:
                if local_filter(path, leaf):
                    reduced.append(leaf)
                else:
                    reduced.append(_reduce_grad_leaf(
                        leaf, op, compression, 1.0, 1.0, process_set))
        synced = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(updates), reduced)
        return optimizer.update(synced, state, params)

    return _scoped(optax.GradientTransformation(init_fn, update_fn),
                   _OPTIMIZER_SCOPE)


def local_value_and_grad(fun: Callable, **jax_kwargs):
    """``jax.value_and_grad`` that returns genuinely LOCAL (per-slot)
    gradients in-trace, pcasting replicated primals to varying so shard_map's
    transpose doesn't pre-sum them.  This is what Adasum needs — it adapts
    between sum and average from the *divergence* of per-rank gradients
    (adasum.h:396-409), which pre-summed gradients erase."""
    vg = jax.value_and_grad(fun, **jax_kwargs)

    def wrapped(*args, **kwargs):
        axis = _axis_name()
        if _axis_bound(axis):
            args = _to_varying(args, axis)
        return vg(*args, **kwargs)

    return wrapped


def adasum_delta_step(optimizer, params, grads, opt_state,
                      process_set: ProcessSet = global_process_set,
                      per_layer_stacked: Optional[Callable] = None):
    """Adasum on post-optimizer deltas (_DistributedAdasumOptimizer,
    torch/optimizer.py:345): apply the optimizer locally, Adasum-reduce the
    parameter delta, add the reduced delta to the original parameters.

    ``grads`` must be LOCAL per-slot gradients (use ``local_value_and_grad``
    in-trace); Adasum over pre-summed gradients degenerates to identity.
    Under shard_map, run the step with ``shard_step(..., check_vma=False)``:
    the butterfly's output is equal on every slot but typed varying.

    ``per_layer_stacked(path) -> bool``: leaves for which it returns True
    are treated as stacked [L, ...] per-layer parameters (a ``scan_layers``
    model's ``blocks`` subtree) and Adasum computes INDEPENDENT
    coefficients per layer slice — the reference's per-tensor adaptation
    granularity, preserved through the stacked layout."""
    local_updates, new_state = optimizer.update(grads, opt_state, params)
    if per_layer_stacked is None:
        reduced_updates = jax.tree_util.tree_map(
            lambda u: _ops.allreduce(u, op=ReduceOp.ADASUM,
                                     process_set=process_set),
            local_updates)
    else:
        from .ops.adasum import adasum_allreduce as _adasum
        if not _axis_bound(_axis_name()):
            # The stacked branch runs the butterfly directly over the
            # mesh axis; outside shard_map there is none to run over —
            # and the rest of this function's contract (LOCAL per-slot
            # grads) is in-trace anyway, so name the requirement instead
            # of letting lax.axis_size raise a bare NameError.
            raise ValueError(
                "adasum_delta_step(per_layer_stacked=...) must run "
                "in-trace under shard_map (hvd.parallel.shard_step) — "
                "the per-slice Adasum butterfly needs the bound mesh "
                "axis")

        def _leaf(path, u):
            if per_layer_stacked(path):
                return _adasum(
                    u, axis_name=_axis_name(),
                    members=None if process_set is global_process_set
                    else process_set.members(),
                    per_slice_axis0=True)
            return _ops.allreduce(u, op=ReduceOp.ADASUM,
                                  process_set=process_set)

        reduced_updates = jax.tree_util.tree_map_with_path(
            _leaf, local_updates)
    # Stateful optimizers (adam moments etc.) updated their state from LOCAL
    # gradients, so it diverges per rank; average it back to consistency —
    # without this, returning the state through replicated out_specs would
    # silently hand each rank different "replicated" buffers.
    new_state = jax.tree_util.tree_map(
        lambda s: _ops.allreduce(s, op=ReduceOp.AVERAGE,
                                 process_set=process_set)
        if isinstance(s, jax.Array) and jnp.issubdtype(
            jnp.asarray(s).dtype, jnp.floating) else s,
        new_state)
    new_params = optax.apply_updates(params, reduced_updates) \
        if optax is not None else jax.tree_util.tree_map(
            lambda p, u: p + u, params, reduced_updates)
    return new_params, new_state


# ---------------------------------------------------------------------------
# Gradient-tape style API (tensorflow/__init__.py:1125 DistributedGradientTape)
# ---------------------------------------------------------------------------

def value_and_grad(fun: Callable, *,
                   op: ReduceOp = ReduceOp.AVERAGE,
                   compression=Compression.none,
                   process_set: ProcessSet = global_process_set,
                   **jax_kwargs):
    """``jax.value_and_grad`` whose gradients are allreduced — the
    DistributedGradientTape analog (tensorflow/__init__.py:1125): every
    rank computes its *local* gradient, the tape returns the combined one.

    In-trace, differentiated arguments are pcast to varying first so the
    gradient really is the local one (otherwise shard_map's transpose rule
    pre-sums gradients of replicated primals and the explicit allreduce
    would double-count)."""
    vg = jax.value_and_grad(fun, **jax_kwargs)

    def wrapped(*args, **kwargs):
        axis = _axis_name()
        if _axis_bound(axis):
            args = _to_varying(args, axis)
        val, grads = vg(*args, **kwargs)
        grads = _allreduce_tree(grads, op, compression, 1.0, 1.0, process_set)
        return val, grads

    return wrapped


def grad(fun: Callable, *,
         op: ReduceOp = ReduceOp.AVERAGE,
         compression=Compression.none,
         process_set: ProcessSet = global_process_set,
         **jax_kwargs):
    """``jax.grad`` with allreduced local gradients (see value_and_grad)."""
    g = jax.grad(fun, **jax_kwargs)

    def wrapped(*args, **kwargs):
        axis = _axis_name()
        if _axis_bound(axis):
            args = _to_varying(args, axis)
        grads = g(*args, **kwargs)
        return _allreduce_tree(grads, op, compression, 1.0, 1.0, process_set)

    return wrapped
