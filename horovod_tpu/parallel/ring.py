"""Ring attention — sequence/context parallelism over the ICI ring.

The reference has **no** long-context support (SURVEY.md §5.8: no ring
attention, no sequence sharding anywhere; its closest primitives are
Alltoallv and an internal point-to-point).  This module is the TPU-native
capability the survey calls out as the path to beating the reference on
long-sequence workloads: shard the sequence dimension across the mesh and
compute exact attention by rotating K/V blocks around the ring with
``lax.ppermute`` — each hop is a neighbor transfer on the physical torus —
while accumulating with an online (flash-style) softmax so nothing ever
materializes the full [S, S] score matrix.

Math: blockwise softmax accumulation (the numerically-stable streaming form)
    m_new = max(m, rowmax(s));  corr = exp(m - m_new)
    l_new = l * corr + rowsum(exp(s - m_new))
    acc_new = acc * corr + exp(s - m_new) @ v
run in float32 islands regardless of input dtype.

Hop schedule (``schedule="overlap"``, the default): the ring is
**double-buffered** — two K/V buffer pairs ride the scan carry, and each
hop issues the *next* hop's ``ppermute`` on the already-received spare
buffer BEFORE running the current hop's kernel/fold.  The transfer and the
compute share no data dependency inside the hop body, so XLA's async
collective scheduler can put the ICI transfer of hop t+1 under the MXU
work of hop t (the latency hiding Ring Attention, Liu et al. 2023, is
built around).  Total ICI traffic is n-1 rotations — one FEWER than the
serial schedule, whose final compute-then-rotate iteration issues a dead
rotation (the prefetch lands before the scan, the scan issues hops
2..n-1, and the last two hops fold after it with both buffers in hand).
``schedule="serial"`` keeps the legacy issue order — compute, then
rotate — as the parity/bench reference.

Causal masking is block-aware.  In the contiguous layout a query block at
ring position i fully attends K/V blocks from positions < i, applies the
triangular mask at position i, and — under the overlap schedule — **truly
skips** positions > i: a ``lax.cond``/``lax.switch`` arm returns the
accumulator unchanged (einsum path) or ``(zeros, -inf)`` (flash path)
without touching the MXU.  (Earlier revisions described these hops as
"skipped" while actually running a fully-masked kernel and discarding the
result — roughly half the ring's kernel FLOPs at large n.  The serial
schedule still behaves that way, by design, so the two schedules can be
pinned against each other.)  The striped layout balances the mask across
hops instead — every hop is near-triangular, so no whole hop is skippable
(except the degenerate one-row-per-shard case, which the flash path does
skip) but no hop is mostly wasted either.

Layout contract: q, k, v are the *local sequence shards* ``[B, S/n, H, D]``
inside shard_map with the sequence dimension sharded over ``axis_name``.

Observability: ``set_ring_timeline`` registers a ``timeline.Timeline`` to
receive the per-hop schedule (hop index, bytes rotated, mask rule, shards
skipping) at trace time; ``set_ring_kernel_callback`` registers a runtime
callback fired (via ``jax.debug.callback``) each time a per-hop flash
kernel actually executes — skip arms never fire it, which is how tests
prove the skip is real.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

SCHEDULES = ("overlap", "serial")

# -- observability hooks ------------------------------------------------------

# (Timeline, tensor_name) receiving trace-time hop-schedule events, plus
# the configs already emitted: one jitted fwd+grad call retraces the ring
# several times (forward, grad, checkpoint remat), and each retrace would
# otherwise duplicate the whole hop schedule.
_ring_timeline = None
_ring_timeline_seen: set = set()
# Runtime callback fired from inside executed flash-kernel branches
# (jax.debug.callback); the skip arm carries no callback, so counting
# firings counts true kernel invocations.  Checked at TRACE time: set it
# before building/jitting the program you want instrumented.
_ring_kernel_callback: Optional[Callable[[int], None]] = None


def set_ring_timeline(timeline, tensor_name: str = "ring") -> None:
    """Register a ``timeline.Timeline`` (or None to clear) to receive the
    per-hop ring schedule — hop index, bytes rotated, mask rule, schedule,
    and how many shards skip the hop's kernel — whenever a ring collective
    is traced.  The device plane is invisible to the host timeline
    (docs/timeline.md), so these are trace-time schedule events.  Each
    distinct ring configuration is emitted once per registration —
    retraces (grad, checkpoint remat) of the same call do not duplicate
    the schedule."""
    global _ring_timeline
    _ring_timeline = None if timeline is None else (timeline, tensor_name)
    _ring_timeline_seen.clear()


def set_ring_kernel_callback(cb: Optional[Callable[[int], None]]) -> None:
    """Register a callback ``cb(mask_mode)`` fired at RUNTIME once per
    executed per-hop flash kernel (skip arms never fire it).  Trace-time
    registration: set before tracing/jitting the instrumented call."""
    global _ring_kernel_callback
    _ring_kernel_callback = cb


def _emit_hop_schedule(kind: str, n: int, bytes_per_hop: int, causal: bool,
                       striped: bool, schedule: str) -> None:
    if _ring_timeline is None:
        return
    key = (kind, n, bytes_per_hop, causal, striped, schedule)
    if key in _ring_timeline_seen:
        return  # retrace of an already-recorded configuration
    _ring_timeline_seen.add(key)
    tl, name = _ring_timeline
    mask = ("causal-striped" if causal and striped else
            "causal-contiguous" if causal else "none")
    for hop in range(n):
        # Contiguous causal under the overlap schedule: hop t (t >= 1)
        # carries the block of owner my+t, which is above the diagonal on
        # the n-t shards with my < n-t — those shards take the skip arm.
        skipped = 0
        if causal and not striped and schedule == "overlap" and hop > 0:
            skipped = n - hop
        tl.ring_hop(f"{name}/{kind}", hop, bytes_rotated=bytes_per_hop,
                    mask=mask, schedule=schedule, skipped_shards=skipped)


def _check_schedule(schedule: str) -> None:
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, "
                         f"got {schedule!r}")


def _block_scores(q32, k32, scale):
    # [B, Sq, H, D] x [B, Sk, H, D] -> [B, H, Sq, Sk]
    return jnp.einsum("bqhd,bkhd->bhqk", q32, k32) * scale


def online_fold(s, v32, acc, m, l):
    """One online-softmax accumulation of a masked score block into the
    running ``(acc, m, l)`` state — the fold at the heart of
    ``ring_attention``'s hop loop.

    ``s`` is ``[B, H, Sq, Sk]`` with masked entries already at ``-1e30``;
    ``v32`` is ``[B, Sk, H, D]``; ``acc [B, H, Sq, D]`` and ``m, l
    [B, H, Sq, 1]`` carry the streaming-softmax state.  The running max is
    floored at half the mask value so a fully-masked block is an exact
    no-op even while the state is still empty (``p`` underflows to 0.0);
    rows that see at least one real key anywhere are bit-identical with
    or without the floor — real scores sit astronomically above it.
    """
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_new = jnp.maximum(m_new, jnp.float32(-1e30) * 0.5)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum("bhqk,bkhd->bhqd", p, v32)
    return acc_new, m_new, l_new


def stripe_sequence(x: jax.Array, n: int, axis: int = 1) -> jax.Array:
    """Re-order a GLOBAL sequence into the striped layout: shard i receives
    tokens [i, i+n, i+2n, ...] instead of a contiguous block.  Under causal
    ring attention the striped layout balances the mask across ring hops
    (contiguous blocks concentrate the real work on late shards — the skip
    arm saves the masked hops' FLOPs but cannot rebalance the remaining
    work).  Apply before sharding; invert with ``unstripe_sequence``."""
    x = jnp.moveaxis(x, axis, 0)
    S = x.shape[0]
    if S % n:
        raise ValueError(f"sequence length {S} not divisible by {n}")
    # position p -> stripe p % n, offset p // n; shard-major concat
    x = x.reshape(S // n, n, *x.shape[1:])
    x = jnp.moveaxis(x, 1, 0).reshape(S, *x.shape[2:])
    return jnp.moveaxis(x, 0, axis)


def unstripe_sequence(x: jax.Array, n: int, axis: int = 1) -> jax.Array:
    """Inverse of ``stripe_sequence``."""
    x = jnp.moveaxis(x, axis, 0)
    S = x.shape[0]
    x = x.reshape(n, S // n, *x.shape[1:])
    x = jnp.moveaxis(x, 1, 0).reshape(S, *x.shape[2:])
    return jnp.moveaxis(x, 0, axis)


def striped_positions(s_local: int, *, axis_name: str = "hvd") -> jax.Array:
    """Global token positions of this shard's striped tokens
    ([i, i+n, i+2n, ...]) — feed to position embeddings when training in the
    striped layout."""
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    return jnp.arange(s_local, dtype=jnp.int32) * n + i


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *,
                   axis_name: str = "hvd",
                   causal: bool = False,
                   scale: Optional[float] = None,
                   striped: bool = False,
                   remat_hops: bool = True,
                   schedule: str = "overlap") -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Args:
      q, k, v: local shards [B, S_local, H, D] (sequence axis 1 sharded).
      causal: apply causal masking consistent with the *global* sequence
        order.
      scale: score scale; default 1/sqrt(D).
      striped: tokens are laid out round-robin (shard i holds global tokens
        i, i+n, ...; see ``stripe_sequence``).  With causal masking this
        balances the per-hop mask across shards: every hop attends a
        near-triangular block instead of all-or-nothing.  Default False =
        contiguous blocks (shard i holds tokens [i*S_local, (i+1)*S_local)).
      remat_hops: rematerialize each hop in the backward pass (default).
        Without it, scan autodiff saves every hop's [Sq, Sk] probability
        block — O(S_global * S_local) per device, the exact memory wall
        ring attention exists to avoid; with it, the backward recomputes
        the block scores from the streamed K/V (the RingAttention
        recipe's memory bound) at ~one extra forward of FLOPs.
      schedule: "overlap" (default) double-buffers the ring — the next
        hop's K/V ``ppermute`` is issued on a spare buffer before the
        current hop's fold, so ICI transfer hides under compute (and one
        rotation fewer runs than serial: n-1 vs n), and contiguous-causal
        above-diagonal hops take a true skip branch (no score einsum at
        all).  "serial" is the legacy compute-then-rotate order with
        masked (but executed) hops; both schedules produce identical
        values and gradients.

    Returns local attention output [B, S_local, H, D] (same sharding as q).
    """
    _check_schedule(schedule)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    q32 = q.astype(jnp.float32)
    neg_inf = jnp.float32(-1e30)

    # Online-softmax state, derived from q32 so the carry's varying-manual-
    # axes type matches the scan body's outputs (fresh constants would be
    # axis-invariant and lax.scan requires carry-type equality).
    acc = jnp.einsum("bqhd->bhqd", q32) * 0.0          # [B, H, Sq, D]
    m = jnp.max(acc, axis=-1, keepdims=True) * 0.0 + neg_inf
    l = jnp.zeros_like(m)

    # Rotate K/V around the ring: after step t, we hold the block that
    # originated on rank (my + t) % n.  ppermute source->dest pairs send
    # each shard to its left neighbor (dest = src - 1 mod n), so hop t
    # brings in blocks from increasing ring distance.  The rotation runs
    # under lax.scan so the compiled program is O(1) in ring size — a
    # 256-chip ring must not unroll 256 attention blocks into the HLO.
    perm = [(i, (i - 1) % n) for i in range(n)]

    if causal:
        iota_q = lax.broadcasted_iota(jnp.int32, (Sq, Sq), 0)
        iota_k = lax.broadcasted_iota(jnp.int32, (Sq, Sq), 1)
        tri_mask = iota_q >= iota_k        # within-block causal
        tri_strict = iota_q > iota_k       # striped off-diagonal rule

    _emit_hop_schedule("ring_attention", n, 2 * B * Sq * H * D * 4,
                       causal, striped, schedule)

    def fold(kv_k, kv_v, acc, m, l, step, allow_skip):
        """One hop's online-softmax fold; identical math in both schedules.

        ``allow_skip`` (overlap schedule only): contiguous-causal hops with
        owner > my are fully masked — numerically an exact no-op after the
        step-0 diagonal hop establishes a finite running max (p underflows
        to exactly 0.0) — so a lax.cond arm returns the state untouched
        without computing the score block at all."""
        owner = (my + step) % n  # global position of the current K/V block

        def compute(args):
            kv_k, kv_v, acc, m, l = args
            s = _block_scores(q32, kv_k, scale)  # [B, H, Sq, Sk]
            if causal and striped:
                # Striped layout: query a (global a*n + my) attends key b
                # (global b*n + owner) iff b < a, or b == a and
                # owner <= my — a near-triangular mask at EVERY hop
                # (balanced work).
                block_mask = jnp.where(owner <= my, tri_mask, tri_strict)
                s = jnp.where(block_mask[None, None], s, neg_inf)
            elif causal:
                # Block-contiguous layout: owner < my -> full attend;
                # owner == my -> triangular; owner > my -> fully masked.
                block_mask = jnp.where(
                    owner == my, tri_mask,
                    jnp.broadcast_to(owner < my, tri_mask.shape))
                s = jnp.where(block_mask[None, None], s, neg_inf)
            return online_fold(s, kv_v, acc, m, l)

        args = (kv_k, kv_v, acc, m, l)
        if allow_skip and causal and not striped:
            return lax.cond(owner > my,
                            lambda a: (a[2], a[3], a[4]),  # true skip
                            compute, args)
        return compute(args)

    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    if schedule == "serial":
        def round_fn(carry, step):
            kv_k, kv_v, acc, m, l = carry
            acc, m, l = fold(kv_k, kv_v, acc, m, l, step, False)
            kv_k = lax.ppermute(kv_k, axis_name, perm)
            kv_v = lax.ppermute(kv_v, axis_name, perm)
            return (kv_k, kv_v, acc, m, l), None

        body = jax.checkpoint(round_fn) if remat_hops else round_fn
        (_, _, acc, m, l), _ = lax.scan(
            body, (k32, v32, acc, m, l), jnp.arange(n, dtype=jnp.int32))
    elif n == 1:
        # Single shard: one fold, no rotation at all.
        tail = lambda: fold(k32, v32, acc, m, l, 0, True)  # noqa: E731
        acc, m, l = (jax.checkpoint(tail) if remat_hops else tail)()
    else:
        # Double-buffered: the carry holds the CURRENT hop's K/V and the
        # next hop's, already in flight.  Each body iteration first issues
        # the hop-(t+2) transfer on the spare buffer — no data dependency
        # with the hop-t fold, so the transfer hides under the compute —
        # then folds hop t.  The scan runs n-2 iterations (issuing hops
        # 2..n-1); the LAST TWO hops fold outside it, where both buffers
        # are already in hand and nothing remains to rotate — n-1 total
        # rotations, one fewer than the serial schedule's n (whose final
        # rotation is dead weight).
        def round_fn(carry, step):
            cur_k, cur_v, nxt_k, nxt_v, acc, m, l = carry
            nn_k = lax.ppermute(nxt_k, axis_name, perm)
            nn_v = lax.ppermute(nxt_v, axis_name, perm)
            acc, m, l = fold(cur_k, cur_v, acc, m, l, step, True)
            return (nxt_k, nxt_v, nn_k, nn_v, acc, m, l), None

        nxt_k = lax.ppermute(k32, axis_name, perm)  # hop-1 prefetch, issued
        nxt_v = lax.ppermute(v32, axis_name, perm)  # before the hop-0 fold
        body = jax.checkpoint(round_fn) if remat_hops else round_fn
        (cur_k, cur_v, nxt_k, nxt_v, acc, m, l), _ = lax.scan(
            body, (k32, v32, nxt_k, nxt_v, acc, m, l),
            jnp.arange(n - 2, dtype=jnp.int32))

        def tail(ck, cv, nk, nv, a, mm, ll):
            a, mm, ll = fold(ck, cv, a, mm, ll, n - 2, True)
            return fold(nk, nv, a, mm, ll, n - 1, True)

        if remat_hops:
            tail = jax.checkpoint(tail)
        acc, m, l = tail(cur_k, cur_v, nxt_k, nxt_v, acc, m, l)

    out = acc / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         *,
                         axis_name: str = "hvd",
                         causal: bool = False,
                         scale: Optional[float] = None,
                         striped: bool = False,
                         block_q: int = 128,
                         block_k: int = 128,
                         interpret: Optional[bool] = None,
                         schedule: str = "overlap") -> jax.Array:
    """``ring_attention`` with the per-hop block math in the Pallas flash
    kernel (parallel/flash.py) instead of XLA einsums.

    Same contract and layouts as :func:`ring_attention`; the difference is
    WHERE the [Sq, Sk] score block lives: the XLA formulation materializes
    it in HBM every hop, the flash kernel streams it through VMEM tiles
    (FlashAttention-2), with each hop emitting a normalized partial output
    plus its per-row logsumexp and the hops combined by the standard
    (out, lse) logsumexp merge — exact, not approximate.  The merge
    weights depend on lse, so the per-hop kernel is differentiable in
    both outputs (flash_attention_lse); the hop body is rematerialized in
    the backward like ring_attention's.

    Per-hop masks map to static kernel variants chosen by the traced
    block owner.  Contiguous causal under the default ``schedule=
    "overlap"``: a three-arm ``lax.switch`` — NONE below the diagonal,
    CAUSAL on it, and a TRUE SKIP above it that returns ``(zeros, -inf)``
    without invoking the Pallas kernel (the -inf lse zeroes the hop's
    merge weight and its gradient path, exactly as the executed-but-
    discarded kernel did).  Striped causal = CAUSAL for owner <= my,
    STRICT above (rows a strict hop fully masks carry -inf lse and drop
    out of the merge); a strict hop is provably empty as a whole only in
    the one-row-per-shard case (S_local == 1), where the skip arm replaces
    the STRICT kernel.  ``schedule="serial"`` keeps the legacy two-arm
    path that runs a full MASK_NONE kernel on above-diagonal hops and
    discards it via forced -inf lse — the parity/bench reference.
    """
    from .flash import (MASK_CAUSAL, MASK_NONE, MASK_STRICT,
                        flash_attention_lse)
    _check_schedule(schedule)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    neg_inf = jnp.float32(-1e30)

    def hop_flash(mode):
        def run(args):
            qq, kk, vv = args
            if _ring_kernel_callback is not None:
                # Runtime proof-of-execution: fires only when THIS branch
                # runs (lax.cond/switch execute one arm), so skip arms are
                # observable as absent firings.
                cb = _ring_kernel_callback
                jax.debug.callback(lambda cb=cb, mode=mode: cb(mode))
            # f32 partials: ONE quantization to q.dtype at the end of the
            # ring, not one per hop.
            return flash_attention_lse(
                qq, kk, vv, mask_mode=mode, scale=scale,
                block_q=block_q, block_k=block_k, interpret=interpret,
                out_dtype=jnp.float32)
        return run

    def hop_skip(args):
        # True skip: no kernel invocation.  Outputs derived from q so the
        # branch's varying-manual-axes types match the kernel arms'; the
        # -inf lse gives the hop merge weight (and gradient) exactly 0.
        qq, _, _ = args
        o = qq.astype(jnp.float32) * 0.0
        lse = jnp.einsum("bqhd->bhq", qq.astype(jnp.float32)) * 0.0 + neg_inf
        return o, lse

    # Carries derived from the varying inputs (see ring_attention's note
    # on scan carry typing under shard_map).  K/V rotate in f32 like
    # ring_attention's carries: bf16 rotation would halve ICI traffic,
    # but it would also accumulate the K/V carry COTANGENTS across n hops
    # in bf16 — a gradient-precision regression the "matches
    # ring_attention" contract refuses.
    out_acc = jnp.einsum("bqhd->bqhd", q.astype(jnp.float32)) * 0.0
    lse_acc = jnp.einsum("bqhd->bhq", q.astype(jnp.float32)) * 0.0 + neg_inf
    perm = [(i, (i - 1) % n) for i in range(n)]

    _emit_hop_schedule("ring_flash_attention", n, 2 * B * Sq * H * D * 4,
                       causal, striped, schedule)

    def fold(kv_k, kv_v, out_acc, lse_acc, step, allow_skip):
        owner = (my + step) % n
        args = (q, kv_k, kv_v)
        if causal and striped:
            if allow_skip and Sq == 1:
                # One row per shard: a strict hop masks its only row —
                # the whole hop is provably empty, skip the kernel.
                o_h, lse_h = lax.cond(owner <= my, hop_flash(MASK_CAUSAL),
                                      hop_skip, args)
            else:
                o_h, lse_h = lax.cond(owner <= my, hop_flash(MASK_CAUSAL),
                                      hop_flash(MASK_STRICT), args)
        elif causal:
            if allow_skip:
                # owner < my -> 0 (NONE), == -> 1 (CAUSAL), > -> 2 (skip).
                arm = ((owner >= my).astype(jnp.int32) +
                       (owner > my).astype(jnp.int32))
                o_h, lse_h = lax.switch(
                    arm, [hop_flash(MASK_NONE), hop_flash(MASK_CAUSAL),
                          hop_skip], args)
            else:
                o_h, lse_h = lax.cond(owner == my, hop_flash(MASK_CAUSAL),
                                      hop_flash(MASK_NONE), args)
                # Blocks above the diagonal contribute nothing: -inf lse
                # zeroes their merge weight AND their gradient path.
                lse_h = jnp.where(owner > my, neg_inf, lse_h)
        else:
            o_h, lse_h = hop_flash(MASK_NONE)(args)
        # (out, lse) logsumexp merge with masked-row guards: a fully
        # masked row's lse is ~-1e30 and its (undefined) output must get
        # weight exactly 0 — plain logaddexp would give two -inf sources
        # weight 0.5 each.
        masked_a = lse_acc <= neg_inf * 0.5
        masked_h = lse_h <= neg_inf * 0.5
        lse_new = jnp.where(
            masked_h, lse_acc,
            jnp.where(masked_a, lse_h, jnp.logaddexp(lse_acc, lse_h)))
        w_a = jnp.where(masked_a, 0.0, jnp.exp(lse_acc - lse_new))
        w_h = jnp.where(masked_h, 0.0, jnp.exp(lse_h - lse_new))
        bcast = lambda w: jnp.einsum("bhq->bqh", w)[..., None]  # noqa: E731
        out_new = out_acc * bcast(w_a) + o_h.astype(jnp.float32) * bcast(w_h)
        return out_new, lse_new

    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    if schedule == "serial":
        def round_fn(carry, step):
            kv_k, kv_v, out_acc, lse_acc = carry
            out_acc, lse_acc = fold(kv_k, kv_v, out_acc, lse_acc, step,
                                    False)
            kv_k = lax.ppermute(kv_k, axis_name, perm)
            kv_v = lax.ppermute(kv_v, axis_name, perm)
            return (kv_k, kv_v, out_acc, lse_acc), None

        (_, _, out_acc, lse_acc), _ = lax.scan(
            jax.checkpoint(round_fn), (k32, v32, out_acc, lse_acc),
            jnp.arange(n, dtype=jnp.int32))
    elif n == 1:
        out_acc, lse_acc = jax.checkpoint(
            lambda: fold(k32, v32, out_acc, lse_acc, 0, True))()
    else:
        # Double-buffered schedule — see ring_attention.  The hop-(t+2)
        # ppermute is issued on the spare buffer before the hop-t kernel;
        # the last two hops fold outside the scan with both buffers in
        # hand (n-1 rotations total, vs serial's n).
        def round_fn(carry, step):
            cur_k, cur_v, nxt_k, nxt_v, out_acc, lse_acc = carry
            nn_k = lax.ppermute(nxt_k, axis_name, perm)
            nn_v = lax.ppermute(nxt_v, axis_name, perm)
            out_acc, lse_acc = fold(cur_k, cur_v, out_acc, lse_acc, step,
                                    True)
            return (nxt_k, nxt_v, nn_k, nn_v, out_acc, lse_acc), None

        nxt_k = lax.ppermute(k32, axis_name, perm)  # hop-1 prefetch
        nxt_v = lax.ppermute(v32, axis_name, perm)
        (cur_k, cur_v, nxt_k, nxt_v, out_acc, lse_acc), _ = lax.scan(
            jax.checkpoint(round_fn),
            (k32, v32, nxt_k, nxt_v, out_acc, lse_acc),
            jnp.arange(n - 2, dtype=jnp.int32))

        def tail(ck, cv, nk, nv, oa, la):
            oa, la = fold(ck, cv, oa, la, n - 2, True)
            return fold(nk, nv, oa, la, n - 1, True)

        out_acc, lse_acc = jax.checkpoint(tail)(
            cur_k, cur_v, nxt_k, nxt_v, out_acc, lse_acc)

    return out_acc.astype(q.dtype)


def ring_attention_reference(q, k, v, *, causal: bool = False,
                             scale: Optional[float] = None):
    """Unsharded reference attention (for tests): q/k/v [B, S, H, D]."""
    B, S, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        iq = lax.broadcasted_iota(jnp.int32, (S, S), 0)
        ik = lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((iq >= ik)[None, None], s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
