"""Public collective-op API — the Horovod op surface on TPU.

Mirrors horovod/torch/mpi_ops.py:110-1315 and tensorflow/mpi_ops.py: sync +
``_async`` + in-place variants of allreduce/allgather/broadcast/alltoall/
reducescatter, grouped variants, ``poll``/``synchronize``, ``barrier`` and
``join``.  (JAX arrays are immutable, so the in-place spellings — kept for API
compatibility — return new arrays; the reference's in-place forms exist to
avoid output allocation, which XLA handles via buffer donation instead.)

Dispatch: when called inside a jit/shard_map trace where the framework mesh
axis is bound, these lower *directly* to the axis-level primitives in
``collective_ops`` (the compiled data plane — no runtime hop at all, the
reference's HOROVOD_ENABLE_XLA_OPS path done natively, SURVEY.md §3.5).
Called eagerly, they dispatch through ops/eager.py over the device mesh.
"""

from __future__ import annotations

import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .collective_ops import (  # noqa: F401
    ReduceOp, Average, Sum, Adasum, Min, Max, Product,
    reducescatter_padded_size,
)
from . import collective_ops as C
from .. import core as _core
from .. import scopes as _scopes
from ..compression import Compression
from ..process_sets import ProcessSet, global_process_set


def _axis() -> str:
    if _core.is_initialized():
        return _core._state.config.mesh_axis
    return "hvd"


def _axis_bound(axis_name: str) -> bool:
    """True when a mesh axis of that name is bound (inside shard_map/pmap) —
    the dispatch switch between the compiled and eager paths."""
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def _engine():
    st = _core._require_init()
    if st.eager_engine is None:
        from .eager import EagerEngine
        st.eager_engine = EagerEngine(st.mesh, st.config.mesh_axis, st.topology)
    return st.eager_engine


def _members(process_set: Optional[ProcessSet]):
    if process_set is None or process_set.ranks is None:
        return None
    return process_set.members()


def _wire_ps(process_set: Optional[ProcessSet]) -> dict:
    """Canonical wire identity of a process set for negotiation signatures.

    The LOCAL process_set_id depends on per-rank registration order, so it
    must never cross the wire: two ranks that registered the same sets in a
    different order would fail validation on a perfectly matched collective,
    and a joined rank could replay a record against the wrong set.  Instead
    the wire carries (a) a membership-derived 31-bit id (FNV-1a over the
    sorted ranks — order-independent, feeds the native cache/message table)
    and (b) the member ranks themselves, from which a replaying rank
    resolves — or auto-registers — the matching local set.  Reference
    semantics: process-set ids are agreed collectively at registration
    (operations.cc:1262); here the membership IS the agreement."""
    members = _members(process_set)
    if members is None:
        return {"ps_id": 0, "ps_ranks": None}
    h = 0x811C9DC5
    for r in members:
        h = ((h ^ (r + 1)) * 0x01000193) & 0x7FFFFFFF
    return {"ps_id": h or 1, "ps_ranks": list(members)}


def _normalize_op(op, average):
    """Resolve the deprecated ``average`` flag vs ``op``
    (torch/mpi_ops.py:110-150 handle_average_backwards_compatibility)."""
    if average is not None:
        if op is not None:
            raise ValueError("The op parameter supersedes average; "
                             "please provide only one of them")
        warnings.warn("average is deprecated, use op=hvd.Average or "
                      "op=hvd.Sum instead", DeprecationWarning, stacklevel=3)
        return ReduceOp.AVERAGE if average else ReduceOp.SUM
    return ReduceOp.AVERAGE if op is None else ReduceOp(op)


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------

def allreduce(tensor,
              average=None,
              name: Optional[str] = None,
              compression=Compression.none,
              op=None,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0,
              process_set: ProcessSet = global_process_set):
    """Allreduce (hvd.allreduce; torch/mpi_ops.py:335, tensorflow mpi_ops).

    In-trace (axis bound): lowers to a lax collective inline.
    Eager: dispatches via the engine; see ops/eager.py mode semantics.
    """
    rop = _normalize_op(op, average)
    axis = _axis()
    members = _members(process_set)
    tensor, ctx = compression.compress(tensor)
    if _axis_bound(axis):
        # HOROVOD_HIERARCHICAL_ALLREDUCE / HOROVOD_TORUS_ALLREDUCE
        # (nccl_operations.h:231, :253) are accepted and map to the flat
        # lax.psum: on TPU, XLA already lowers psum with torus-native
        # hierarchical decomposition, which is precisely what the
        # reference's software torus approximates (SURVEY.md §7).  Routing
        # through the explicit two-phase form here would also change the
        # result's vma type (grouped collectives yield varying outputs) and
        # break replicated out_specs that plain psum satisfies.  The
        # explicit form stays available for 2-D mesh experts as
        # collective_ops.hierarchical_allreduce.
        with _scopes.collective("allreduce", name):
            out = C.allreduce(tensor, rop, axis_name=axis, members=members,
                              prescale_factor=prescale_factor,
                              postscale_factor=postscale_factor)
        return compression.decompress(out, ctx)

    eng = _engine()

    def body(x):
        return C.allreduce(x, rop, axis_name=axis, members=members,
                           prescale_factor=prescale_factor,
                           postscale_factor=postscale_factor)

    def single(ts):
        # np=1: every ReduceOp reduces a single operand to itself; only the
        # scale factors apply (rop was validated by _normalize_op).
        x = C._apply_scale(ts[0], prescale_factor)
        return [C._apply_scale(x, postscale_factor)]

    out = eng.run("allreduce",
                  body, [tensor],
                  (int(rop), members, prescale_factor, postscale_factor),
                  single, name=name, op_id=int(rop),
                  prescale=prescale_factor, postscale=postscale_factor,
                  **_wire_ps(process_set))[0]
    return compression.decompress(out, ctx)


def allreduce_async(tensor, average=None, name=None, op=None,
                    prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0,
                    process_set: ProcessSet = global_process_set) -> int:
    """Async allreduce → handle (torch/mpi_ops.py:260 allreduce_async_).
    JAX dispatch is already asynchronous; the handle wraps the future
    output arrays."""
    out = allreduce(tensor, average=average, name=name, op=op,
                    prescale_factor=prescale_factor,
                    postscale_factor=postscale_factor,
                    process_set=process_set)
    return _engine().handles.allocate(out)


# In-place spellings kept for API parity (JAX arrays are immutable; XLA
# buffer donation provides the memory win the reference's in-place ops target).
allreduce_ = allreduce
allreduce_async_ = allreduce_async


def grouped_allreduce(tensors: Sequence,
                      average=None,
                      name=None,
                      compression=Compression.none,
                      op=None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      process_set: ProcessSet = global_process_set) -> List:
    """Grouped allreduce: all-or-nothing readiness (GroupTable,
    group_table.h:31; torch/mpi_ops.py grouped_allreduce)."""
    rop = _normalize_op(op, average)
    axis = _axis()
    members = _members(process_set)
    compressed = [compression.compress(t) for t in tensors]
    ts = [c[0] for c in compressed]
    ctxs = [c[1] for c in compressed]
    if _axis_bound(axis):
        with _scopes.collective("grouped_allreduce", name):
            outs = C.grouped_allreduce(ts, rop, axis_name=axis,
                                       members=members,
                                       prescale_factor=prescale_factor,
                                       postscale_factor=postscale_factor)
    else:
        eng = _engine()

        def body(*xs):
            return tuple(C.grouped_allreduce(
                list(xs), rop, axis_name=axis, members=members,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor))

        def single(xs):
            return [C._apply_scale(C._apply_scale(x, prescale_factor),
                                   postscale_factor) for x in xs]

        outs = eng.run("grouped_allreduce", body, list(ts),
                       (int(rop), members, prescale_factor, postscale_factor),
                       single, name=name, op_id=int(rop),
                       prescale=prescale_factor, postscale=postscale_factor,
                       **_wire_ps(process_set))
    return [compression.decompress(o, c) for o, c in zip(outs, ctxs)]


def grouped_allreduce_async(tensors, average=None, name=None, op=None,
                            prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0,
                            process_set: ProcessSet = global_process_set) -> int:
    outs = grouped_allreduce(tensors, average=average, name=name, op=op,
                             prescale_factor=prescale_factor,
                             postscale_factor=postscale_factor,
                             process_set=process_set)
    return _engine().handles.allocate(outs)


grouped_allreduce_ = grouped_allreduce
grouped_allreduce_async_ = grouped_allreduce_async


def _fusion_pack(*ts):
    """Device-side pack: one concatenate instead of a device→host copy
    per tensor (the reference engineered the same away with batched D2D
    memcpy kernels, cuda_kernels.h:32-46).  Deliberately EAGER, not
    jitted: autotune shifts fusion thresholds across scoring windows, so
    bucket compositions change and a jitted pack would recompile on the
    very steps autotune is timing; eager dispatch is a handful of cheap
    reshape views plus one concatenate op."""
    return jnp.concatenate([t.ravel() for t in ts])


def _fused_allreduce(tensors: Sequence, op,
                     prescale_factor: float = 1.0,
                     postscale_factor: float = 1.0,
                     compression=Compression.none,
                     process_set: ProcessSet = global_process_set) -> List:
    """Eager fused allreduce over one FLAT fusion buffer: device-side pack
    (MemcpyInFusionBuffer, operations.cc:519 — here an eager device-side
    concatenate, see _fusion_pack, so gradients stay device-resident
    instead of round-tripping through host numpy), a single dispatched
    collective for the whole bucket,
    then device-side slice+reshape (MemcpyOutFusionBuffer).  One global-
    array assembly instead of one per tensor — the reference's tensor-
    fusion data path, which is where the eager dispatch time went.

    ``compression`` (fp16/bf16) is applied ONCE to the packed buffer —
    the planner's buckets are same-dtype, and a cast is elementwise, so
    compress(concat(ts)) == concat(compress(t) for ts) and the per-tensor
    grouped path's numerics are preserved with one cast + one collective
    per bucket instead of one pair per tensor (docs/tensor_fusion.md).

    All tensors must share one dtype (the fusion planner only buckets
    same-dtype entries, csrc PlanFusion / controller.cc:901)."""
    rop = ReduceOp(op)
    axis = _axis()
    members = _members(process_set)
    eng = _engine()
    ts = [jnp.asarray(t) for t in tensors]
    shapes = [t.shape for t in ts]
    sizes = [int(np.prod(s, dtype=np.int64)) for s in shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    flat, cctx = compression.compress(_fusion_pack(*ts))

    def body(x):
        return C.allreduce(x, rop, axis_name=axis, members=members,
                           prescale_factor=prescale_factor,
                           postscale_factor=postscale_factor)

    def single(ts):
        x = C._apply_scale(ts[0], prescale_factor)
        return [C._apply_scale(x, postscale_factor)]

    out = eng.run("allreduce", body, [flat],
                  (int(rop), members, prescale_factor, postscale_factor),
                  single, name=f"fusedbuf.{flat.dtype}.{int(offsets[-1])}",
                  op_id=int(rop), prescale=prescale_factor,
                  postscale=postscale_factor,
                  **_wire_ps(process_set))[0]
    out = compression.decompress(out, cctx)  # ctx = pre-wire flat dtype
    return [out[int(a):int(b)].reshape(s)
            for a, b, s in zip(offsets[:-1], offsets[1:], shapes)]


# ---------------------------------------------------------------------------
# allgather
# ---------------------------------------------------------------------------

def allgather(tensor, name: Optional[str] = None,
              process_set: ProcessSet = global_process_set):
    """Concatenate every participant's tensor along axis 0 (hvd.allgather,
    torch/mpi_ops.py:700).

    Under jit all participants must pass equal shapes.  Eagerly, ragged dim0
    (allgatherv, MPI_Allgatherv analog) is supported: in emulated mode pass a
    *list* of per-rank tensors; in multi-process mode ragged local dim0 is
    handled via a size exchange + pad-to-max + slice (the reference controller
    gathers recvcounts the same way, collective_operations.h:126).

    HOROVOD_HIERARCHICAL_ALLGATHER (MPIHierarchicalAllgather,
    mpi_operations.cc) is accepted and maps to the flat lax.all_gather —
    XLA lowers it with the torus-native hierarchical decomposition the
    reference's node-leader gather approximates in software."""
    axis = _axis()
    members = _members(process_set)
    if _axis_bound(axis):
        with _scopes.collective("allgather", name):
            return C.allgather(tensor, axis_name=axis, members=members)
    eng = _engine()
    if isinstance(tensor, (list, tuple)) and eng.topo.emulated:
        return _allgatherv_emulated(list(tensor), members)
    if not eng.topo.emulated and eng.n > 1:
        return _allgatherv_multiproc(tensor, members, name)

    def body(x):
        return C.allgather(x, axis_name=axis, members=members)

    def single(ts):
        return [ts[0]]

    return eng.run("allgather", body, [tensor], (members,), single,
                   name=name,
                   **_wire_ps(process_set))[0]


def _allgatherv_emulated(tensors: List, members) -> List:
    """Ragged allgather, emulated mode: list of per-rank tensors in, list of
    per-rank gathered results out (all equal: the member concat)."""
    eng = _engine()
    n = eng.n
    if len(tensors) != n:
        raise ValueError(
            f"emulated allgatherv takes one tensor per rank ({n}); got "
            f"{len(tensors)}")
    sel = range(n) if members is None else members
    gathered = jnp.concatenate([jnp.asarray(tensors[r]) for r in sel], axis=0)
    return [gathered if members is None or r in set(sel) else
            jnp.asarray(tensors[r]) for r in range(n)]


def _allgatherv_parts(tensor, name):
    """Raw ragged gather: exchange dim0 sizes (fixed shape), pad to max,
    gather, slice per rank — the static-shape-safe allgatherv
    (SURVEY.md §7 "dynamic shapes").  Returns (per-rank blocks, sizes);
    a joined rank's block is empty (its size announcement is 0).

    The two dispatches here are mirrored one-to-one by the join replay
    (ops/eager.py _replay_allgather_record) — change them together."""
    eng = _engine()
    n = eng.n
    t = jnp.asarray(tensor)
    rows = int(t.shape[0])
    size_vec = jnp.asarray(np.array([rows], np.int64))

    def size_body(x):
        return C.allgather(x, axis_name=_axis())

    # The size vector is the one legitimate host sync: the announced row
    # counts determine SHAPES (the reference's recvcounts gather does the
    # same).  The DATA stays device-resident: device-side pad, gather,
    # and per-rank slices — no host round-trip of the payload.
    sizes = np.asarray(eng.run("allgather_sizes", size_body, [size_vec],
                               (), lambda ts: ts, name=None)[0]).ravel()
    max_rows = int(sizes.max())
    if max_rows > rows:
        pad = ((0, max_rows - rows),) + ((0, 0),) * (t.ndim - 1)
        padded = jnp.pad(t, pad)
    else:
        padded = t

    def body(x):
        return lax.all_gather(x, _axis(), axis=0)  # [n, max, ...]

    gathered = eng.run("allgather", body,
                       [padded], (max_rows,),
                       lambda ts: [ts[0][None]], name=name)[0]
    return [gathered[r, :int(sizes[r])] for r in range(n)], sizes


def _allgatherv_multiproc(tensor, members, name):
    """Ragged allgather, multi-process: member blocks concatenated."""
    eng = _engine()
    n = eng.n
    if members is not None and _core.rank() not in set(members):
        # Non-members still participate in the global exchange (the run is
        # SPMD-total over all processes) but keep their input.
        _allgatherv_parts(tensor, name)
        return jnp.asarray(tensor)
    blocks, _ = _allgatherv_parts(tensor, name)
    sel = range(n) if members is None else members
    return jnp.concatenate([blocks[r] for r in sel], axis=0)


def allgather_async(tensor, name=None,
                    process_set: ProcessSet = global_process_set) -> int:
    out = allgather(tensor, name=name, process_set=process_set)
    return _engine().handles.allocate(out)


def grouped_allgather(tensors, name=None,
                      process_set: ProcessSet = global_process_set) -> List:
    return [allgather(t, name=name, process_set=process_set) for t in tensors]


def grouped_allgather_async(tensors, name=None,
                            process_set: ProcessSet = global_process_set) -> int:
    outs = grouped_allgather(tensors, name=name, process_set=process_set)
    return _engine().handles.allocate(outs)


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------

def broadcast(tensor, root_rank: int = 0, name: Optional[str] = None,
              process_set: ProcessSet = global_process_set,
              stacked: Optional[bool] = None):
    """Root's tensor to all participants (hvd.broadcast,
    torch/mpi_ops.py:914).

    ``stacked`` (TPU-build extension, emulated mode only): declare whether
    the tensor is a per-rank stack [N, ...] (True) or a replicated value
    (False); None uses the leading-dim heuristic (see ops/eager.py)."""
    axis = _axis()
    members = _members(process_set)
    if _axis_bound(axis):
        with _scopes.collective("broadcast", name):
            return C.broadcast(tensor, root_rank, axis_name=axis,
                               members=members)
    eng = _engine()

    def body(x):
        return C.broadcast(x, root_rank, axis_name=axis, members=members)

    def single(ts):
        return [ts[0]]

    return eng.run("broadcast", body, [tensor], (root_rank, members),
                   single, name=name, stacked=stacked,
                   op_id=int(root_rank),
                   **_wire_ps(process_set))[0]


def broadcast_async(tensor, root_rank: int = 0, name=None,
                    process_set: ProcessSet = global_process_set) -> int:
    out = broadcast(tensor, root_rank=root_rank, name=name,
                    process_set=process_set)
    return _engine().handles.allocate(out)


broadcast_ = broadcast
broadcast_async_ = broadcast_async


# ---------------------------------------------------------------------------
# alltoall
# ---------------------------------------------------------------------------

def alltoall(tensor, splits=None, name: Optional[str] = None,
             process_set: ProcessSet = global_process_set):
    """All-to-all row exchange (hvd.alltoall, torch/mpi_ops.py:1063;
    AlltoallOp PrepareOutputAndParams collective_operations.h:199-268).

    Without ``splits``: equal blocks (dim0 divisible by participants).
    With ``splits`` (len-N int vector: rows I send to each participant):
    returns ``(output, received_splits)`` like the reference.  Ragged exchange
    is an eager-only feature — XLA programs need static shapes."""
    axis = _axis()
    members = _members(process_set)
    if splits is None:
        if _axis_bound(axis):
            with _scopes.collective("alltoall", name):
                return C.alltoall(tensor, axis_name=axis, members=members)
        eng = _engine()

        def body(x):
            return C.alltoall(x, axis_name=axis, members=members)

        def single(ts):
            return [ts[0]]

        return eng.run("alltoall", body, [tensor], (members,), single,
                       name=name,
                       **_wire_ps(process_set))[0]

    if _axis_bound(axis):
        raise ValueError(
            "alltoall with uneven splits requires eager mode: XLA compiled "
            "programs need static shapes (SURVEY.md §7 dynamic shapes)")
    return _alltoallv_eager(tensor, splits, members)


def _alltoallv_eager(tensor, splits, members):
    """Ragged alltoall on the eager path (alltoallv; the controller alltoalls
    the split vectors then sizes the output, collective_operations.h:199-268).

    Emulated mode: ``tensor`` is a list of per-rank tensors (ragged stacks
    can't be one array) and ``splits`` is [N, N]; returns (list of outputs,
    received_splits [N, N]).  Single rank: identity."""
    eng = _engine()
    n = eng.n
    if n == 1:
        return jnp.asarray(tensor), jnp.asarray(splits)
    if eng.topo.emulated:
        tensors = [np.asarray(t) for t in tensor]
        sp = np.asarray(splits).reshape(n, n)
        offsets = np.concatenate(
            [np.zeros((n, 1), np.int64), np.cumsum(sp, axis=1)], axis=1)
        outputs = []
        for recv in range(n):
            parts = [tensors[src][offsets[src, recv]:offsets[src, recv + 1]]
                     for src in range(n)]
            outputs.append(jnp.asarray(np.concatenate(parts, axis=0)))
        received = jnp.asarray(sp.T.copy())
        return outputs, received
    # Multi-process ragged path: gather splits, gather ragged data blocks,
    # then slice received sub-blocks host-side.  A joined rank contributes
    # an EMPTY block to both gathers (ops/eager.py join replay) — its splits
    # row stays all-zero, i.e. it sends nothing to anyone.
    sp_local = np.asarray(splits, dtype=np.int64)
    sp_blocks, sp_sizes = _allgatherv_parts(jnp.asarray(sp_local)[None, :],
                                            None)
    all_splits = np.zeros((n, n), np.int64)
    # ONE device→host sync for the whole split table (it is pure shape
    # metadata): per-block np.asarray would cost n tiny blocking copies.
    present = [src for src in range(n) if sp_sizes[src]]
    if present:
        flat_sp = np.asarray(jnp.concatenate(
            [sp_blocks[src].reshape(-1) for src in present]))
        if flat_sp.size != len(present) * n:
            # A malformed announcement (e.g. the emulated-mode [N, N]
            # splits form passed in multi-process mode) must fail loudly:
            # fixed-stride chunking over a wrong-length vector would
            # silently shift every later rank's row.
            raise ValueError(
                f"alltoall splits exchange returned {flat_sp.size} values "
                f"for {len(present)} ranks (expected {n} per rank); some "
                f"rank announced a malformed splits vector")
        for i, src in enumerate(present):
            all_splits[src] = flat_sp[i * n:(i + 1) * n]
    t = jnp.asarray(tensor)
    data_blocks, _ = _allgatherv_parts(t, None)
    rank = _core.rank()
    offsets = np.concatenate(
        [np.zeros((n, 1), np.int64), np.cumsum(all_splits, axis=1)], axis=1)
    # Device-side sub-block slices + one concatenate: the split table is
    # host metadata (it determines shapes), the payload never leaves the
    # device.
    parts = [data_blocks[src][int(offsets[src, rank]):
                              int(offsets[src, rank + 1])]
             for src in range(n)]
    out = jnp.concatenate(parts, axis=0) if parts else \
        jnp.zeros((0,) + t.shape[1:], t.dtype)
    return out, jnp.asarray(all_splits[:, rank].copy())


def alltoall_async(tensor, splits=None, name=None,
                   process_set: ProcessSet = global_process_set) -> int:
    out = alltoall(tensor, splits=splits, name=name, process_set=process_set)
    return _engine().handles.allocate(out)


# ---------------------------------------------------------------------------
# reducescatter
# ---------------------------------------------------------------------------

def reducescatter(tensor, op=ReduceOp.SUM, name: Optional[str] = None,
                  prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0,
                  process_set: ProcessSet = global_process_set):
    """Reduce + scatter row blocks (hvd.reducescatter, torch/mpi_ops.py:1203).

    Deviation: uneven dim0 is zero-padded to a multiple of the participant
    count (SPMD uniform shards) instead of the reference's first-ranks-get-
    extra-rows split; ``reducescatter_padded_size`` exposes the padding."""
    rop = ReduceOp(op) if op is not None else ReduceOp.SUM
    axis = _axis()
    members = _members(process_set)
    if _axis_bound(axis):
        with _scopes.collective("reducescatter", name):
            return C.reducescatter(tensor, rop, axis_name=axis,
                                   members=members,
                                   prescale_factor=prescale_factor,
                                   postscale_factor=postscale_factor)
    eng = _engine()

    def body(x):
        return C.reducescatter(x, rop, axis_name=axis, members=members,
                               prescale_factor=prescale_factor,
                               postscale_factor=postscale_factor)

    def single(ts):
        x = C._apply_scale(ts[0], prescale_factor)
        return [C._apply_scale(x, postscale_factor)]

    return eng.run("reducescatter", body, [tensor],
                   (int(rop), members, prescale_factor, postscale_factor),
                   single, name=name, op_id=int(rop),
                   prescale=prescale_factor, postscale=postscale_factor,
                   **_wire_ps(process_set))[0]


def reducescatter_async(tensor, op=ReduceOp.SUM, name=None,
                        process_set: ProcessSet = global_process_set) -> int:
    out = reducescatter(tensor, op=op, name=name, process_set=process_set)
    return _engine().handles.allocate(out)


def grouped_reducescatter(tensors, op=ReduceOp.SUM, name=None,
                          process_set: ProcessSet = global_process_set) -> List:
    return [reducescatter(t, op=op, name=name, process_set=process_set)
            for t in tensors]


def grouped_reducescatter_async(tensors, op=ReduceOp.SUM, name=None,
                                process_set: ProcessSet = global_process_set) -> int:
    outs = grouped_reducescatter(tensors, op=op, name=name,
                                 process_set=process_set)
    return _engine().handles.allocate(outs)


# ---------------------------------------------------------------------------
# handles / synchronization / barrier / join
# ---------------------------------------------------------------------------

def poll(handle: int) -> bool:
    """True when the async op's outputs are materialized (hvd.poll,
    torch/mpi_ops.py:1251)."""
    return _engine().handles.poll(handle)


def synchronize(handle: int):
    """Block until the async op completes and return its output(s)
    (hvd.synchronize, torch/mpi_ops.py:1265)."""
    return _engine().handles.wait(handle)


def barrier(process_set: ProcessSet = global_process_set) -> None:
    """Blocking barrier over the set (hvd.barrier, torch/mpi_ops.py:1315;
    BarrierOp collective_operations.h:335)."""
    axis = _axis()
    if _axis_bound(axis):
        with _scopes.collective("barrier", None):
            C.barrier(axis_name=axis)
        return
    eng = _engine()
    if eng.n == 1:
        return

    def body(x):
        return x + C.barrier(axis_name=axis)

    token = jnp.zeros((eng.n, 1), jnp.int32) if eng.topo.emulated else \
        jnp.zeros((1,), jnp.int32)
    out = eng.run("barrier", body, [token], (), lambda ts: ts)[0]
    jax.block_until_ready(out)


def join(device: int = -1) -> int:
    """Signal this rank has no more data (hvd.join, torch/mpi_ops.py:1293;
    JoinOp collective_operations.h:308): blocks until every rank joined,
    contributing ZEROS to collectives the surviving ranks keep issuing
    (uneven-data semantics), and returns the id of the last rank to join.

    ``device`` is accepted for API parity (the reference pins the zero
    buffers to a GPU; XLA manages placement here).  Under SPMD jit, uneven
    per-rank step counts cannot occur inside one compiled program — join is
    an eager/multi-controller feature."""
    del device
    return _engine().join()
