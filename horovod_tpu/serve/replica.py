"""Replica scheduler: serving replicas over ``process_sets``, least-loaded
routing, preemption-aware failover.

Mapping: a serving *replica* is an independent copy of the model owning a
disjoint subgroup of the job's slot ranks — exactly what
``process_sets.ProcessSet`` models for training collectives
(``build_replicas`` registers one contiguous set per replica via
``partition_process_sets``).  Requests route to the least-loaded healthy
replica (load = in-flight sequences + queued requests — queue depth alone
under-counts a replica mid-decode).

Failure handling rides the elastic subsystem's machinery: TPU-VM
preemption notices surface as host markers in the rendezvous KV scope
``preempt`` (elastic/preemption.PreemptionSentinel), and ``horovodrun``'s
elastic driver reports lost ranks the same way the training side consumes
them.  ``watch_preemption`` polls that scope; any replica whose process
set intersects a lost host's ranks is marked dead: it leaves the routing
set, its queued AND in-flight requests are resubmitted to the survivors
(the drained replica's only — nobody else's work moves), and ``healthz``
degrades.  Requeued requests restart from the prompt — greedy decoding
makes the eventual answer identical, so a client never observes the loss
beyond latency.

The fleet also GROWS back (docs/serving.md scale-up): when a marked
host's preemption clears — the sentinel deletes its marker from the same
KV scope, exactly what happens when a maintenance event cancels or the
recovered host's new sentinel reconciles at startup — ``watch_preemption``
translates the clearance into ``mark_alive``: the dead replica's batcher
reopens, its engine loop restarts on the existing (masked, therefore
safe) cache arrays, and least-loaded routing rebalances new work onto it
immediately.  ``add_replica`` admits a genuinely new replica (a freshly
rendezvoused process set) into the routing set the same way.  The watcher
itself is hardened: a transient KV error is counted
(``hvd_serve_preempt_poll_errors_total``), backed off, and survived — a
silently-dead watcher would mean preemptions go unnoticed forever.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from ..faultline import runtime as _faultline
from ..obs import tracing as _obs
from ..utils import get_logger
from .batcher import DynamicBatcher, QueueFullError, Request
from .engine import InferenceEngine, ModelAdapter
from .metrics import ServeMetrics


class NoHealthyReplicaError(Exception):
    """Every replica is dead — the server answers 503 from /generate and
    ``/healthz`` reports ``unserving``."""


class Replica:
    """One serving replica: a process set, an engine, and its batcher."""

    def __init__(self, replica_id: str, process_set, engine: InferenceEngine):
        self.replica_id = replica_id
        self.process_set = process_set
        self.engine = engine
        self.state = "healthy"  # healthy | dead
        # True only while registry.roll() is walking THIS replica through
        # drain -> swap -> revive; the FleetController must not treat the
        # transient dead state as scale-up capacity (controller.py).
        self.rolling = False

    @property
    def ranks(self) -> List[int]:
        if self.process_set is None:
            return []
        if self.process_set.ranks is None:
            return list(range(self.process_set.size() or 0))
        return list(self.process_set.ranks)

    def load(self) -> int:
        return self.engine.load()

    def to_dict(self) -> dict:
        out = {"id": self.replica_id, "state": self.state,
               "ranks": self.ranks, "load": self.load(),
               "active": self.engine.active_count,
               "queued": self.engine.batcher.depth(),
               "attn_impl": self.engine.attn_impl,
               "kv_dtype": self.engine.kv_dtype,
               "rolling": self.rolling,
               "models": {name: self.engine._model_versions.get(name, 0)
                          for name in sorted(self.engine._adapters)}}
        kv = self.engine.kv_stats()
        out["kv_blocks"] = {k: kv[k] for k in
                            ("total", "used", "free", "retained")}
        if "bytes_per_block" in kv:
            out["kv_blocks"]["bytes_per_block"] = kv["bytes_per_block"]
        # hvdmem budget plan: pool + weight bytes, and the headroom
        # against HVD_MEM_BUDGET_BYTES / probed HBM when known —
        # surfaced on healthz so an operator sees a mis-sized
        # BlockManager before it OOMs (docs/serving.md).
        # n>1 CoW fork + speculative observability (ISSUE 11): the
        # fork counters and spec config ride healthz next to the
        # block stats, so the n-best path is visible per replica
        # from the first forked request.
        # hvdshard go/no-go (ISSUE 17): the static replica-plan
        # verdict (pool budget x comm budget) rides the same
        # surface, so healthz shows plan_go per replica.
        for extra in ("pool_bytes", "weight_bytes",
                      "kv_headroom_bytes", "seq_forks",
                      "forked_requests", "spec_k",
                      "plan_go", "plan_findings"):
            if extra in kv:
                out["kv_blocks"][extra] = kv[extra]
        return out


class ReplicaScheduler:
    """Routes requests across replicas; drains dead ones (module doc)."""

    def __init__(self, replicas: Sequence[Replica],
                 metrics: Optional[ServeMetrics] = None):
        if not replicas:
            raise ValueError("need at least one replica")
        self.replicas: List[Replica] = list(replicas)
        self.metrics = metrics or ServeMetrics()
        self._lock = threading.Lock()
        self._watch_stop = threading.Event()
        self._watch_thread: Optional[threading.Thread] = None
        self._started = False
        for r in self.replicas:
            self._register_metrics(r)
        _faultline.maybe_install_from_env()

    def _register_metrics(self, r: Replica) -> None:
        self.metrics.register_queue_depth(
            r.replica_id, r.engine.batcher.depth)
        self.metrics.register_kv_stats(
            r.replica_id, r.engine.kv_stats)

    # -- routing -------------------------------------------------------------

    def _healthy(self) -> List[Replica]:
        with self._lock:
            return [r for r in self.replicas if r.state == "healthy"]

    def fleet(self) -> List[Replica]:
        """Point-in-time copy of the replica list (any state) — the
        FleetController's snapshot/actuation view (controller.py); the
        copy means its per-replica sampling never runs under our lock."""
        with self._lock:
            return list(self.replicas)

    def submit(self, request: Request) -> Replica:
        """Least-loaded routing with failover: a replica at queue capacity
        backpressures; the next-least-loaded healthy replica is tried
        before the request is shed."""
        if _faultline.PLAN is not None:
            # ``replica.route`` injection point: a kill-rank fault here
            # models a loss DETECTED at routing time (an all-numeric
            # target is a slot rank, anything else a replica id) — the
            # direct path other detectors use via report_rank_lost,
            # bypassing the sentinel/marker plumbing.  No instance is
            # passed: the spec's target names the VICTIM, not this
            # scheduler (one scheduler per process; the plan's instance
            # filter is for multi-instance points like engines/hosts).
            for f in _faultline.fire("replica.route"):
                if f.kind != "kill-rank" or f.target is None:
                    continue
                if f.target.isdigit():
                    self.report_rank_lost(int(f.target))
                else:
                    self.mark_dead(f.target, reason="faultline kill-rank")
        if _obs.TRACER is not None and not request._sampling_decided:
            # Front-end-less ingress (bench storms, direct submits): the
            # scheduler is the sampling point and the engine emits the
            # root span at completion (no http-handle exists).  An HTTP
            # request that already lost the front-end's roll is NOT
            # re-rolled (_sampling_decided) — re-rolling would double
            # the effective sample rate and trace requests whose
            # responses carry no X-Trace-Id.
            request._sampling_decided = True
            if _obs.TRACER.should_sample():
                request.trace = _obs.TRACER.new_context()
                request._emit_root = True
        candidates = sorted(self._healthy(), key=lambda r: r.load())
        if request.model is not None:
            # Variant routing (hvdtenant): only replicas RESIDENT for the
            # requested model are candidates.  An unknown-everywhere model
            # is the caller's error (the server 400s it before this), but
            # a model known to SOME replicas while all of them are dead
            # is a fleet-health condition -> NoHealthyReplicaError / 503.
            candidates = [r for r in candidates
                          if request.model in r.engine._adapters]
        if not candidates:
            self.metrics.count_request("error", tenant=request.tenant)
            raise NoHealthyReplicaError(
                "no healthy replicas" if request.model is None else
                f"no healthy replica holds model {request.model!r}")
        last_exc: Optional[Exception] = None
        for replica in candidates:
            try:
                replica.engine.batcher.submit(request)
                return replica
            except QueueFullError as e:
                last_exc = e
        self.metrics.count_request("shed", tenant=request.tenant)
        raise last_exc  # every healthy queue is full: explicit shed

    def start(self) -> "ReplicaScheduler":
        self._started = True
        for r in self.replicas:
            r.engine.start()
        return self

    def stop(self) -> None:
        self._watch_stop.set()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=10)
            self._watch_thread = None
        for r in self.replicas:
            for req in r.engine.batcher.close():
                req.fail(NoHealthyReplicaError("server shutting down"))
            # drain() (not stop()) so in-flight requests fail NOW instead
            # of parking their handler threads for the full request
            # timeout.
            for req in r.engine.drain():
                req.fail(NoHealthyReplicaError("server shutting down"))

    # -- failure handling ----------------------------------------------------

    def report_rank_lost(self, rank: int) -> Optional[str]:
        """Elastic/preemption integration point: a lost slot rank kills
        the replica whose process set contains it.  Returns the dead
        replica's id (None if the rank maps to no live replica)."""
        with self._lock:
            victim = next((r for r in self.replicas
                           if r.state == "healthy" and rank in r.ranks),
                          None)
        if victim is None:
            return None
        self.mark_dead(victim.replica_id,
                       reason=f"rank {rank} lost")
        return victim.replica_id

    def mark_dead(self, replica_id: str, reason: str = "") -> None:
        """Remove a replica from routing and requeue ITS work (queued +
        in-flight) onto the survivors.  Only the dead replica's requests
        move — the survivors' batches are untouched."""
        with self._lock:
            victim = next((r for r in self.replicas
                           if r.replica_id == replica_id), None)
            if victim is None or victim.state == "dead":
                return
            victim.state = "dead"
        self.metrics.count_replica_event("mark_dead")
        get_logger().warning("serve: replica %s marked dead (%s); draining",
                             replica_id, reason or "operator request")
        # CLOSE (not merely drain) the victim's batcher: a submit() that
        # snapshotted the victim as healthy before state flipped would
        # otherwise enqueue into a queue nothing will ever poll; closed,
        # that late submit raises QueueFullError and fails over to the
        # next candidate.  close() returns the queued requests.
        queued = victim.engine.batcher.close()
        now = time.monotonic()
        for req in queued:
            req.requeues += 1  # engine.drain() bumps its own
            req.resubmitted_at = now
        orphans = queued + victim.engine.drain()
        try:
            # Tiered engines retract their fleet-directory entries: a
            # peer mid-migration toward a dead holder must miss fast
            # and degrade to recompute, not wait out fetch retries.
            victim.engine.tier_unpublish()
        except Exception:
            get_logger().warning(
                "serve: %s tier unpublish failed on mark_dead",
                replica_id, exc_info=True)
        if not orphans:
            return
        if _obs.TRACER is not None:
            # Failover forensics: each traced orphan gets a resubmit
            # instant naming the dead replica; the span closing at the
            # survivor's admission starts from resubmitted_at.
            for req in orphans:
                if req.trace is None:
                    continue
                try:
                    _obs.TRACER.instant(
                        req.trace, "resubmit", replica_id,
                        args={"from": replica_id,
                              "reason": reason or "mark_dead"})
                except Exception:
                    pass
        # Already-accepted work must NOT shed on a replica loss: it goes
        # to the FRONT of the survivors' queues past the capacity bound
        # (requeue_front's contract), dealt round-robin starting at the
        # least-loaded survivor; one batched call per survivor keeps each
        # chunk's relative order.  Variant-pinned orphans (request.model
        # set) only deal onto survivors RESIDENT for that model — during
        # a registry.roll the drained replica's work for the rolling
        # variant lands exactly on the replicas still serving it.
        survivors = sorted(self._healthy(), key=lambda r: r.load())
        if not survivors:
            for req in orphans:
                self.metrics.count_request("error", tenant=req.tenant)
                req.fail(NoHealthyReplicaError(
                    f"replica {replica_id} lost with no survivors"))
            return
        chunks = {s.replica_id: [] for s in survivors}
        rr: Dict[Optional[str], int] = {}  # per-model deal cursor
        for req in orphans:
            eligible = survivors if req.model is None else [
                s for s in survivors
                if req.model in s.engine._adapters]
            if not eligible:
                self.metrics.count_request("error", tenant=req.tenant)
                req.fail(NoHealthyReplicaError(
                    f"no surviving replica holds model {req.model!r}"))
                continue
            i = rr.get(req.model, 0)
            rr[req.model] = i + 1
            self.metrics.count_request("requeued", tenant=req.tenant)
            chunks[eligible[i % len(eligible)].replica_id].append(req)
        for s in survivors:
            s.engine.batcher.requeue_front(chunks[s.replica_id])
        get_logger().warning("serve: requeued %d request(s) from %s",
                             len(orphans), replica_id)

    # -- scale-up (docs/serving.md) ------------------------------------------

    def mark_alive(self, replica_id: str, reason: str = "") -> None:
        """Re-admit a dead replica into the routing set: reopen its
        (closed, empty) batcher, restart its engine loop, flip state.

        Safe on the existing cache arrays: the dead engine's drain freed
        every slot and block reference, and both cache layouts mask
        positions beyond a live sequence's length to weight exactly 0 —
        a revived engine's first prefill overwrites everything it will
        ever read, so no state reset is needed (and retained prefix
        blocks keep their still-valid K/V).  Least-loaded routing
        rebalances onto the empty revived replica on the next submit."""
        with self._lock:
            replica = next((r for r in self.replicas
                            if r.replica_id == replica_id), None)
            if replica is None or replica.state == "healthy":
                return
            replica.state = "healthy"
        replica.engine.batcher.reopen()
        if self._started:
            replica.engine.start()
        self.metrics.count_replica_event("mark_alive")
        get_logger().warning("serve: replica %s re-admitted (%s)",
                             replica_id, reason or "operator request")

    def report_rank_recovered(self, rank: int) -> Optional[str]:
        """Scale-up analog of ``report_rank_lost``: a recovered slot rank
        revives the dead replica whose process set contains it.  Returns
        the revived replica's id (None when the rank maps to no dead
        replica — e.g. a brand-new process set, which enters via
        ``add_replica`` instead)."""
        with self._lock:
            dead = next((r for r in self.replicas
                         if r.state == "dead" and rank in r.ranks), None)
        if dead is None:
            return None
        self.mark_alive(dead.replica_id, reason=f"rank {rank} recovered")
        return dead.replica_id

    def add_replica(self, replica: Replica) -> None:
        """Admit a NEW replica (a freshly rendezvoused process set) into
        the routing set — fleet growth beyond reviving a known replica."""
        with self._lock:
            if any(r.replica_id == replica.replica_id
                   for r in self.replicas):
                raise ValueError(
                    f"replica id {replica.replica_id} already registered")
            self.replicas.append(replica)
        self._register_metrics(replica)
        if self._started:
            replica.engine.start()
        self.metrics.count_replica_event("mark_alive")
        get_logger().warning("serve: replica %s added (scale-up); "
                             "fleet size now %d",
                             replica.replica_id, len(self.replicas))

    def watch_preemption(self, kv_client, host_ranks: Dict[str, List[int]],
                         poll_s: Optional[float] = None) -> None:
        """Poll the rendezvous KV ``preempt`` scope (the same markers the
        elastic driver's PreemptionAwareDiscovery consumes) and translate
        marker churn into fleet transitions: a host APPEARING kills the
        replicas its ranks map to, a previously-marked host DISAPPEARING
        (the sentinel cleared its marker — event cancelled, or the
        recovered host's startup reconcile) revives them via
        ``mark_alive``.  ``host_ranks`` maps the discovery-plane hostname
        to the slot ranks it carries (the launcher's host allocation
        plan; tests pass a synthetic map).

        The poller must outlive transient KV trouble: every failed
        iteration is counted (``hvd_serve_preempt_poll_errors_total``),
        backed off exponentially (capped at 30 s), and retried forever —
        a watcher that died on the first flake would mean every later
        preemption goes unnoticed and the fleet only ever shrinks by
        surprise."""
        from ..elastic.preemption import PREEMPT_SCOPE
        poll_s = poll_s if poll_s is not None else float(
            os.environ.get("HVD_SERVE_PREEMPT_POLL_S", "1"))

        def loop():
            marked_prev: set = set()
            errors = 0
            while not self._watch_stop.is_set():
                try:
                    marked = set(kv_client.scan(PREEMPT_SCOPE))
                    for host in marked - marked_prev:
                        for rank in host_ranks.get(host, []):
                            self.report_rank_lost(rank)
                    for host in marked_prev - marked:
                        for rank in host_ranks.get(host, []):
                            self.report_rank_recovered(rank)
                    marked_prev = marked
                    errors = 0
                except Exception as e:
                    # Count + back off + KEEP POLLING (module doc).  The
                    # marker diff state is untouched: the next successful
                    # scan sees exactly the churn this one missed.
                    errors += 1
                    self.metrics.count_preempt_poll_error()
                    backoff = min(poll_s * (2 ** min(errors, 5)), 30.0)
                    get_logger().warning(
                        "preempt watcher: poll error #%d (%s); retrying "
                        "in %.1fs", errors, e, backoff)
                    self._watch_stop.wait(backoff)
                    continue
                self._watch_stop.wait(poll_s)

        self._watch_thread = threading.Thread(
            target=loop, daemon=True, name="hvd-serve-preempt-watch")
        self._watch_thread.start()

    # -- health --------------------------------------------------------------

    def healthz(self) -> dict:
        with self._lock:
            replicas = [r.to_dict() for r in self.replicas]
        healthy = sum(1 for r in replicas if r["state"] == "healthy")
        if healthy == len(replicas):
            status = "ok"
        elif healthy > 0:
            status = "degraded"
        else:
            status = "unserving"
        return {"status": status, "healthy": healthy,
                "total": len(replicas), "replicas": replicas}


def build_replicas(adapter_factory: Callable[[], ModelAdapter],
                   num_replicas: Optional[int] = None,
                   max_batch: Optional[int] = None,
                   metrics: Optional[ServeMetrics] = None,
                   **engine_kwargs) -> ReplicaScheduler:
    """Partition the initialized world into ``num_replicas`` process sets
    and stand up one engine per set (adapter_factory is called per replica
    — each replica owns its model arrays and KV block pool).

    ``engine_kwargs`` pass through to each ``InferenceEngine`` (
    num_blocks / prefill_chunk / prefix_cache — the paged-cache knobs,
    docs/serving.md); unset ones fall back to their ``HVD_SERVE_*`` envs.

    Requires ``hvd.init()``; with no runtime (pure local serving) pass
    ``num_replicas`` explicitly and the process-set mapping is skipped.
    """
    from .. import core as _core
    sets: List[Optional[object]] = []
    if _core.is_initialized():
        from ..process_sets import partition_process_sets
        n = num_replicas if num_replicas is not None else int(
            os.environ.get("HVD_SERVE_REPLICAS",
                           str(max(_core.num_slots() // 2, 1))))
        sets = list(partition_process_sets(n))
    else:
        n = num_replicas or int(os.environ.get("HVD_SERVE_REPLICAS", "1"))
        sets = [None] * n
    metrics = metrics or ServeMetrics()
    replicas = []
    for i, ps in enumerate(sets):
        rid = f"replica-{i}"
        engine = InferenceEngine(adapter_factory(),
                                 batcher=DynamicBatcher(),
                                 metrics=metrics, max_batch=max_batch,
                                 replica_id=rid, **engine_kwargs)
        replicas.append(Replica(rid, ps, engine))
    return ReplicaScheduler(replicas, metrics=metrics)
