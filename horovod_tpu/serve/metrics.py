"""Serving metrics: latency histograms, occupancy, throughput counters.

No reference analog — the reference (and the training half of this repo)
ends at the optimizer step.  The metric set follows the continuous-batching
serving literature: Orca (OSDI '22) makes *iteration-level batch occupancy*
the defining throughput statistic (a serving engine whose occupancy sits at
1 has degenerated into request-level batching), and TTFT / per-output-token
latency are the standard user-facing latency split (prefill cost vs decode
cadence).

Export surfaces:

* ``render()`` — Prometheus text exposition for the HTTP ``/metrics``
  endpoint (serve/server.py);
* ``snapshot()`` — plain dict for the ``BENCH_MODEL=serve`` record
  (bench.py) and tests;
* ``maybe_emit_timeline()`` — Chrome-trace counter events through
  ``timeline.Timeline.serve_counter`` (SERVE/<component> counters chart
  next to the training-side op lifecycle in the same viewer), rate-limited
  to every ``HVD_SERVE_TIMELINE_EVERY`` decode steps so the trace stays
  bounded under sustained load.

Everything is guarded by one lock: observers run on engine threads while
``/metrics`` renders on HTTP handler threads.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from .tenancy import TenantAccounting

#: Histogram bucket upper bounds in milliseconds (Prometheus ``le`` label).
#: Spans sub-ms MLP decodes through multi-second cold-compile prefills.
DEFAULT_BUCKETS_MS = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class Histogram:
    """Fixed-bucket latency histogram (Prometheus semantics: cumulative
    bucket counts, +Inf implicit via ``count``)."""

    def __init__(self, buckets_ms=DEFAULT_BUCKETS_MS):
        self.bounds: List[float] = list(buckets_ms)
        self.counts: List[int] = [0] * len(self.bounds)
        self.count = 0
        self.sum = 0.0

    def observe(self, value_ms: float) -> None:
        self.count += 1
        self.sum += value_ms
        for i, b in enumerate(self.bounds):
            if value_ms <= b:
                self.counts[i] += 1

    def quantile(self, q: float) -> float:
        """Approximate quantile from bucket counts (upper bound of the
        bucket containing the q-th observation) — good enough for bench
        records; exact quantiles would need reservoir state."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        for i, b in enumerate(self.bounds):
            if self.counts[i] >= target:
                return b
        return self.bounds[-1]

    def to_dict(self) -> dict:
        return {"count": self.count, "sum_ms": round(self.sum, 3),
                "p50_ms": self.quantile(0.5), "p99_ms": self.quantile(0.99)}


class ServeMetrics:
    """One instance per server (shared across that server's replicas —
    replica identity travels in the per-counter labels where it matters)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.started_at = time.monotonic()
        self.ttft_ms = Histogram()
        self.token_step_ms = Histogram()
        # Per-request stage decomposition (obs tracing, ROADMAP item 4):
        # queue / prefill / decode / spec / retry milliseconds per
        # COMPLETED
        # request, an exact partition of its end-to-end latency
        # (Request.stage_add) — the autoscaler's per-stage inputs beyond
        # the aggregate TTFT/token-step histograms above.
        self.stage_ms: Dict[str, Histogram] = {
            s: Histogram() for s in ("queue", "prefill", "decode",
                                     "spec", "retry")}
        self.tokens_total = 0
        self.decode_steps_total = 0
        self.prefills_total = 0
        # Speculative decoding (docs/serving.md): draft/verify token
        # accounting — acceptance_rate = accepted / drafted, and
        # decode_steps_total counts TARGET-model invocations (one per
        # verify step), so target-calls-per-emitted-token is readable
        # straight off the snapshot (the bench spec arm's acceptance
        # bar).
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        self.spec_rejected_total = 0
        self.spec_steps_total = 0
        # Per-iteration prefill/decode token split (chunked prefill's
        # fairness statistic): prompt tokens processed vs decode tokens
        # produced, per engine iteration (serve/engine.py paged loop).
        self.prefill_tokens_total = 0
        self.decode_tokens_total = 0
        self.iterations_total = 0
        # Request outcomes: ok / shed (queue full) / expired (deadline) /
        # requeued (drained off a dead replica, re-routed) / preempted
        # (evicted for KV blocks, re-admitted locally) / error.
        self.requests: Dict[str, int] = {"ok": 0, "shed": 0, "expired": 0,
                                         "requeued": 0, "preempted": 0,
                                         "error": 0}
        # Multi-tenant plane (serve/tenancy.py): per-tenant outcome
        # counters and stage histograms, both keyed by the CAPPED label
        # (TenantAccounting collapses past-the-cap tenants into
        # "other").  tenant_stage_ms is its OWN dict — stage_ms keys
        # carry the "stage|tier" convention, and a tenant label must
        # never parse as a tier.
        self._tenants = TenantAccounting()
        self.tenant_requests: Dict[Tuple[str, str], int] = {}
        self.tenant_stage_ms: Dict[Tuple[str, str], Histogram] = {}
        # Live hot-swap progress per model (serve/registry.py roll):
        # (replicas done, replicas total) of the in-flight/last roll.
        self.swap_progress: Dict[str, Tuple[int, int]] = {}
        # Zero-cold-start warmup (engine.warmup): wall ms of the last
        # warmup and the number of warmups each replica ran — the
        # regression surface for "mark_alive re-warms" (tests pin that
        # runs increments on every engine (re)start).
        self.warmup_ms: Dict[str, float] = {}
        self.warmup_runs: Dict[str, int] = {}
        # Preemption-watcher health: transient KV errors the poller
        # survived (a dead watcher means preemptions go unnoticed
        # forever, so its error count must be observable).
        self.preempt_poll_errors = 0
        # Replica lifecycle transitions (mark_dead / mark_alive — the
        # fleet's shrink/grow events, docs/serving.md scale-up).
        self.replica_events: Dict[str, int] = {"mark_dead": 0,
                                               "mark_alive": 0}
        # Fleet-controller plane (serve/controller.py): current brownout
        # rung (gauge), controller action counters, and per-QoS-tier
        # end-to-end request-latency histograms — the latency-tier one
        # is what the controller's windowed SLO check diffs between
        # polls.
        self.brownout_level = 0
        self.ctl_events: Dict[str, int] = {}
        self.request_ms: Dict[str, Histogram] = {
            "latency": Histogram(), "throughput": Histogram()}
        # EWMA of per-request service time (ms), all tiers — the queue-
        # drain-rate input of the load-aware Retry-After hint
        # (server._budget_headers).
        self._service_ms: Optional[float] = None
        # Tiered-KV plane (serve/tiering.py): fault-stall episodes —
        # iterations where the ahead-of-decode prefetch lost its race
        # and the loop had nothing runnable — plus the bytes moved each
        # direction and the migration hit counters.  The fault-stall
        # histogram is part of the inter-decode-step p99 contract now:
        # a tier fault IS a token-step latency event (docs/serving.md).
        self.tier_stall_ms = Histogram()
        self.tier_faults_total = 0
        self.tier_spill_bytes = 0
        self.tier_promote_bytes = 0
        self.tier_demote_bytes = 0
        self.tier_migrated_tokens = 0
        self.tier_migrations_total = 0
        # Batch occupancy: sequences active per decode step.
        self.occupancy_last = 0
        self.occupancy_max = 0
        self.occupancy_sum = 0
        self.occupancy_samples = 0
        self._queue_depth_fns: Dict[str, object] = {}
        self._kv_stats_fns: Dict[str, object] = {}
        self._timeline = None
        self._timeline_every = int(os.environ.get(
            "HVD_SERVE_TIMELINE_EVERY", "16"))
        self._steps_since_emit = 0

    # -- observers (engine/batcher threads) ---------------------------------

    def observe_ttft(self, ms: float) -> None:
        with self._lock:
            self.ttft_ms.observe(ms)
            self.prefills_total += 1
            self.tokens_total += 1  # the prefill's first generated token

    def observe_decode_step(self, ms: float, occupancy: int,
                            new_tokens: int) -> None:
        with self._lock:
            self.token_step_ms.observe(ms)
            self.decode_steps_total += 1
            self.tokens_total += new_tokens
            self.occupancy_last = occupancy
            self.occupancy_max = max(self.occupancy_max, occupancy)
            self.occupancy_sum += occupancy
            self.occupancy_samples += 1
            self._steps_since_emit += 1

    def observe_iteration(self, prefill_tokens: int,
                          decode_tokens: int) -> None:
        """One engine iteration's prefill-vs-decode token split (the
        chunked-prefill fairness statistic, docs/serving.md)."""
        with self._lock:
            self.prefill_tokens_total += prefill_tokens
            self.decode_tokens_total += decode_tokens
            self.iterations_total += 1

    def count_request(self, outcome: str,
                      tenant: Optional[str] = None) -> None:
        # label() takes the accounting's own (leaf) lock BEFORE we take
        # self._lock — never nested inside it, so no new ordering edge.
        label = self._tenants.label(tenant) if tenant is not None else None
        with self._lock:
            self.requests[outcome] = self.requests.get(outcome, 0) + 1
            if label is not None:
                key = (label, outcome)
                self.tenant_requests[key] = \
                    self.tenant_requests.get(key, 0) + 1

    def count_tokens(self, n: int) -> None:
        """Tokens emitted outside the TTFT/decode-step observers (the
        n-1 extra first tokens an n>1 fork moment draws)."""
        with self._lock:
            self.tokens_total += n

    def observe_spec(self, drafted: int, accepted: int,
                     rejected: int) -> None:
        """One speculative step's draft accounting (engine._spec_once)."""
        with self._lock:
            self.spec_drafted_total += drafted
            self.spec_accepted_total += accepted
            self.spec_rejected_total += rejected
            self.spec_steps_total += 1

    def observe_stage(self, stage: str, ms: float) -> None:
        """One completed request's time in ``stage`` (queue / prefill /
        decode / spec / retry) — engine._complete feeds every non-zero
        stage."""
        with self._lock:
            h = self.stage_ms.get(stage)
            if h is None:
                h = self.stage_ms[stage] = Histogram()
            h.observe(ms)

    def observe_tenant_stage(self, tenant: str, stage: str,
                             ms: float) -> None:
        """One completed request's time in ``stage`` attributed to its
        tenant (cardinality-capped label) — engine._complete's
        per-tenant emission next to the aggregate observe_stage."""
        label = self._tenants.label(tenant)
        with self._lock:
            key = (label, stage)
            h = self.tenant_stage_ms.get(key)
            if h is None:
                h = self.tenant_stage_ms[key] = Histogram()
            h.observe(ms)

    def set_swap_progress(self, model: str, done: int,
                          total: int) -> None:
        """Roll progress gauge (serve/registry.py): ``done`` of
        ``total`` replicas serve the target version."""
        with self._lock:
            self.swap_progress[model] = (int(done), int(total))

    def swap_event(self, model: str, replica: str, phase: str,
                   version: int) -> None:
        """One hot-swap phase transition → SWAP timeline instant (the
        brownout_event discipline: read the timeline under the lock,
        emit outside it, never let the trace path break the roll)."""
        with self._lock:
            tl = self._timeline
        if tl is None:
            return
        try:
            tl.swap_event(model, replica, phase, version)
        except Exception:
            pass  # the metrics path must never take down a roll

    def observe_warmup(self, replica_id: str, ms: float) -> None:
        """One engine warmup pass (engine.warmup): last duration gauge +
        run counter per replica."""
        with self._lock:
            self.warmup_ms[replica_id] = float(ms)
            self.warmup_runs[replica_id] = \
                self.warmup_runs.get(replica_id, 0) + 1

    def observe_request_ms(self, tier: str, ms: float) -> None:
        """One COMPLETED request's end-to-end latency by QoS tier
        (engine._complete — the sum of its stage_ms partition).  Also
        advances the service-time EWMA the Retry-After hint reads."""
        with self._lock:
            h = self.request_ms.get(tier)
            if h is None:
                h = self.request_ms[tier] = Histogram()
            h.observe(ms)
            self._service_ms = (ms if self._service_ms is None
                                else 0.2 * ms + 0.8 * self._service_ms)

    def recent_service_s(self) -> float:
        """EWMA per-request service time in SECONDS (0.0 until the
        first completion) — depth x this = the queue-drain estimate
        behind the load-aware Retry-After hint."""
        with self._lock:
            return (self._service_ms or 0.0) / 1e3

    def request_window(self, tier: str):
        """``(bounds, cumulative bucket counts, total count)`` snapshot
        of one tier's request-latency histogram — the controller diffs
        consecutive snapshots for its WINDOWED p99 (controller.py)."""
        with self._lock:
            h = self.request_ms.get(tier)
            if h is None:
                return ([], [], 0)
            return (list(h.bounds), list(h.counts), h.count)

    def ttft_window(self):
        """``(bounds, cumulative bucket counts, total count)`` snapshot
        of the time-to-first-token histogram — same diffing contract as
        :meth:`request_window`, feeding the controller's interactive
        TTFT SLO term (streamed clients feel TTFT, not end-to-end
        latency, so the pressure ladder may watch it directly)."""
        with self._lock:
            h = self.ttft_ms
            return (list(h.bounds), list(h.counts), h.count)

    def set_brownout_level(self, level: int, reason: str = "") -> None:
        """Controller rung walk: gauge update + BROWNOUT timeline
        instant (``reason`` is the action, e.g. ``brownout_up``)."""
        with self._lock:
            self.brownout_level = int(level)
            tl = self._timeline
        if tl is None:
            return
        try:
            tl.brownout_event(
                "down" if reason.endswith("down") else "up",
                level, rung=reason)
        except Exception:
            pass  # the metrics path must never take down the controller

    def count_ctl_event(self, event: str) -> None:
        with self._lock:
            self.ctl_events[event] = self.ctl_events.get(event, 0) + 1

    def observe_tier_stall(self, ms: float) -> None:
        """One tier-fault stall episode (serve/tiering.py): the engine
        loop waited ``ms`` for an in-flight tier fetch with nothing else
        runnable — the prefetch lost its race."""
        with self._lock:
            self.tier_stall_ms.observe(ms)
            self.tier_faults_total += 1

    def count_tier_bytes(self, spill: int = 0, promote: int = 0,
                         demote: int = 0) -> None:
        """Bytes moved across tier boundaries: device→host (spill),
        host→device (promote), host→KV-server (demote)."""
        with self._lock:
            self.tier_spill_bytes += spill
            self.tier_promote_bytes += promote
            self.tier_demote_bytes += demote

    def count_tier_migration(self, tokens: int) -> None:
        """One cross-replica prefix-block migration worth ``tokens``
        tokens of skipped prefill."""
        with self._lock:
            self.tier_migrated_tokens += tokens
            self.tier_migrations_total += 1

    def count_preempt_poll_error(self) -> None:
        with self._lock:
            self.preempt_poll_errors += 1

    def count_replica_event(self, event: str) -> None:
        with self._lock:
            self.replica_events[event] = \
                self.replica_events.get(event, 0) + 1

    def register_queue_depth(self, replica_id: str, fn) -> None:
        """``fn`` is sampled at render time — queue depth is a gauge, not
        a counter, so it is read where it lives instead of mirrored."""
        with self._lock:
            self._queue_depth_fns[replica_id] = fn

    def register_kv_stats(self, replica_id: str, fn) -> None:
        """``fn`` returns the replica engine's ``kv_stats()`` dict;
        sampled at render time like queue depth."""
        with self._lock:
            self._kv_stats_fns[replica_id] = fn

    # -- export -------------------------------------------------------------

    def _queue_depths(self) -> Dict[str, int]:
        # NEVER called under self._lock: the depth fns take the batchers'
        # locks, and an engine thread shedding under a batcher lock may
        # need self._lock (count_request) — sampling under self._lock
        # would be the other half of an AB/BA deadlock.
        with self._lock:
            fns = dict(self._queue_depth_fns)
        out = {}
        for rid, fn in fns.items():
            try:
                out[rid] = int(fn())
            except Exception:
                out[rid] = -1
        return out

    def _kv_stats(self) -> Dict[str, dict]:
        # Same locking discipline as _queue_depths: the stats fns take
        # the BlockManager's lock, never sample them under self._lock.
        with self._lock:
            fns = dict(self._kv_stats_fns)
        out = {}
        for rid, fn in fns.items():
            try:
                stats = fn()
            except Exception:
                stats = None
            if stats is not None:
                out[rid] = stats
        return out

    def _tenant_snapshot_locked(self) -> dict:
        # Caller holds self._lock.  {tenant: {"requests": {outcome: n},
        # "stage": {stage: hist dict}}} — the bench multitenant arm
        # reads per-tenant goodput (ok counts) off this.
        out: Dict[str, dict] = {}
        for (label, outcome), n in self.tenant_requests.items():
            out.setdefault(label, {"requests": {}, "stage": {}})
            out[label]["requests"][outcome] = n
        for (label, stage), h in self.tenant_stage_ms.items():
            out.setdefault(label, {"requests": {}, "stage": {}})
            out[label]["stage"][stage] = h.to_dict()
        return out

    def snapshot(self) -> dict:
        depths = self._queue_depths()
        kv = self._kv_stats()
        with self._lock:
            elapsed = max(time.monotonic() - self.started_at, 1e-9)
            occ_mean = (self.occupancy_sum / self.occupancy_samples
                        if self.occupancy_samples else 0.0)
            hit_tokens = sum(s.get("prefix_hit_tokens", 0)
                             for s in kv.values())
            lookup_tokens = sum(s.get("prefix_lookup_tokens", 0)
                                for s in kv.values())
            return {
                "tokens_total": self.tokens_total,
                "tokens_per_sec": round(self.tokens_total / elapsed, 2),
                "decode_steps": self.decode_steps_total,
                "prefills": self.prefills_total,
                "requests": dict(self.requests),
                "tenants": self._tenant_snapshot_locked(),
                "swap": {m: {"done": d, "total": t}
                         for m, (d, t) in self.swap_progress.items()},
                "warmup": {"ms": dict(self.warmup_ms),
                           "runs": dict(self.warmup_runs)},
                "replica_events": dict(self.replica_events),
                "brownout_level": self.brownout_level,
                "ctl_events": dict(self.ctl_events),
                "request_latency": {t: h.to_dict()
                                    for t, h in self.request_ms.items()},
                "preempt_poll_errors": self.preempt_poll_errors,
                "occupancy": {"last": self.occupancy_last,
                              "max": self.occupancy_max,
                              "mean": round(occ_mean, 3)},
                "queue_depth": depths,
                "ttft": self.ttft_ms.to_dict(),
                "token_step": self.token_step_ms.to_dict(),
                "stage": {s: h.to_dict()
                          for s, h in self.stage_ms.items()},
                "token_split": {
                    "prefill_tokens": self.prefill_tokens_total,
                    "decode_tokens": self.decode_tokens_total,
                    "iterations": self.iterations_total,
                },
                "spec": {
                    "drafted": self.spec_drafted_total,
                    "accepted": self.spec_accepted_total,
                    "rejected": self.spec_rejected_total,
                    "steps": self.spec_steps_total,
                    "acceptance_rate": round(
                        self.spec_accepted_total
                        / self.spec_drafted_total, 4)
                    if self.spec_drafted_total else 0.0,
                },
                "tier": {
                    "faults": self.tier_faults_total,
                    "fault_stall": self.tier_stall_ms.to_dict(),
                    "spill_bytes": self.tier_spill_bytes,
                    "promote_bytes": self.tier_promote_bytes,
                    "demote_bytes": self.tier_demote_bytes,
                    "migrations": self.tier_migrations_total,
                    "migrated_tokens": self.tier_migrated_tokens,
                },
                "seq_forks": sum(s.get("seq_forks", 0)
                                 for s in kv.values()),
                "kv_blocks": kv,
                "prefix_cache": {
                    "hit_tokens": hit_tokens,
                    "lookup_tokens": lookup_tokens,
                    "hit_rate": round(hit_tokens / lookup_tokens, 4)
                    if lookup_tokens else 0.0,
                },
            }

    def render(self) -> str:
        """Prometheus text exposition (version 0.0.4 format)."""
        depths = self._queue_depths()
        kv = self._kv_stats()
        with self._lock:
            lines = []

            def hist(name, h: Histogram, help_=None, labels=""):
                # ``labels`` (e.g. 'stage="queue"') prefixes every le
                # pair and suffixes _sum/_count — one rendering for the
                # plain and labeled histogram families.
                if help_ is not None:
                    lines.append(f"# HELP {name} {help_}")
                    lines.append(f"# TYPE {name} histogram")
                sep = labels + "," if labels else ""
                suffix = "{" + labels + "}" if labels else ""
                for bound, c in zip(h.bounds, h.counts):
                    lines.append(
                        f'{name}_bucket{{{sep}le="{bound:g}"}} {c}')
                lines.append(f'{name}_bucket{{{sep}le="+Inf"}} {h.count}')
                lines.append(f"{name}_sum{suffix} {h.sum:g}")
                lines.append(f"{name}_count{suffix} {h.count}")

            hist("hvd_serve_ttft_ms", self.ttft_ms,
                 "Time to first token (prefill wait + compute), ms")
            hist("hvd_serve_token_step_ms", self.token_step_ms,
                 "Decode step duration (per-output-token latency), ms")
            # Per-stage request-latency decomposition (one histogram per
            # stage label — the exact partition of each completed
            # request's end-to-end latency, docs/observability.md).
            lines.append("# HELP hvd_serve_stage_ms per-request latency "
                         "by lifecycle stage (queue|prefill|decode|"
                         "spec|retry), ms")
            lines.append("# TYPE hvd_serve_stage_ms histogram")
            for stage in sorted(self.stage_ms):
                # "stage|tier" keys (engine._complete's per-QoS-tier
                # emission) render as a two-label series; plain keys
                # stay the all-tiers aggregate the dashboards already
                # chart.
                if "|" in stage:
                    s, tier = stage.split("|", 1)
                    labels = f'stage="{s}",tier="{tier}"'
                else:
                    labels = f'stage="{stage}"'
                hist("hvd_serve_stage_ms", self.stage_ms[stage],
                     labels=labels)
            # Per-tenant stage decomposition (serve/tenancy.py): same
            # histogram family, tenant-labeled series (cardinality
            # capped at the accounting layer).
            for (label, stage) in sorted(self.tenant_stage_ms):
                hist("hvd_serve_stage_ms",
                     self.tenant_stage_ms[(label, stage)],
                     labels=f'stage="{stage}",tenant="{label}"')
            lines.append("# HELP hvd_serve_request_ms end-to-end "
                         "request latency by QoS tier, ms")
            lines.append("# TYPE hvd_serve_request_ms histogram")
            for tier in sorted(self.request_ms):
                hist("hvd_serve_request_ms", self.request_ms[tier],
                     labels=f'tier="{tier}"')
            lines.append("# TYPE hvd_serve_tokens_total counter")
            lines.append(f"hvd_serve_tokens_total {self.tokens_total}")
            lines.append("# TYPE hvd_serve_decode_steps_total counter")
            lines.append(
                f"hvd_serve_decode_steps_total {self.decode_steps_total}")
            lines.append("# TYPE hvd_serve_requests_total counter")
            for outcome, n in sorted(self.requests.items()):
                lines.append(
                    f'hvd_serve_requests_total{{outcome="{outcome}"}} {n}')
            lines.append("# TYPE hvd_serve_tenant_requests_total counter")
            for (label, outcome), n in sorted(
                    self.tenant_requests.items()):
                lines.append(
                    f'hvd_serve_tenant_requests_total{{tenant="{label}",'
                    f'outcome="{outcome}"}} {n}')
            # Hot-swap roll progress (serve/registry.py): fraction of
            # replicas serving the target version, per model.
            lines.append("# TYPE hvd_serve_swap_progress gauge")
            for model, (done, total) in sorted(
                    self.swap_progress.items()):
                frac = done / total if total else 0.0
                lines.append(
                    f'hvd_serve_swap_progress{{model="{model}"}} '
                    f'{frac:g}')
            # Warmup plane (engine.warmup): last pass duration + run
            # count per replica — runs increments on EVERY engine
            # (re)start, the mark_alive-rewarm regression surface.
            lines.append("# TYPE hvd_serve_warmup_ms gauge")
            for rid, ms in sorted(self.warmup_ms.items()):
                lines.append(
                    f'hvd_serve_warmup_ms{{replica="{rid}"}} {ms:g}')
            lines.append("# TYPE hvd_serve_warmup_runs_total counter")
            for rid, n in sorted(self.warmup_runs.items()):
                lines.append(
                    f'hvd_serve_warmup_runs_total{{replica="{rid}"}} '
                    f'{n}')
            lines.append(
                "# TYPE hvd_serve_preempt_poll_errors_total counter")
            lines.append(f"hvd_serve_preempt_poll_errors_total "
                         f"{self.preempt_poll_errors}")
            lines.append("# TYPE hvd_serve_replica_events_total counter")
            for event, n in sorted(self.replica_events.items()):
                lines.append(
                    f'hvd_serve_replica_events_total{{event="{event}"}} '
                    f'{n}')
            # Fleet-controller plane (serve/controller.py): the current
            # brownout rung and the controller's action tallies.
            lines.append("# TYPE hvd_serve_brownout_level gauge")
            lines.append(
                f"hvd_serve_brownout_level {self.brownout_level}")
            lines.append("# TYPE hvd_serve_ctl_events_total counter")
            for event, n in sorted(self.ctl_events.items()):
                lines.append(
                    f'hvd_serve_ctl_events_total{{event="{event}"}} {n}')
            lines.append("# TYPE hvd_serve_batch_occupancy gauge")
            lines.append(f"hvd_serve_batch_occupancy {self.occupancy_last}")
            lines.append("# TYPE hvd_serve_batch_occupancy_max gauge")
            lines.append(
                f"hvd_serve_batch_occupancy_max {self.occupancy_max}")
            occ_mean = (self.occupancy_sum / self.occupancy_samples
                        if self.occupancy_samples else 0.0)
            lines.append("# TYPE hvd_serve_batch_occupancy_mean gauge")
            lines.append(f"hvd_serve_batch_occupancy_mean {occ_mean:g}")
            lines.append("# TYPE hvd_serve_queue_depth gauge")
            for rid, depth in sorted(depths.items()):
                lines.append(
                    f'hvd_serve_queue_depth{{replica="{rid}"}} {depth}')
            lines.append("# TYPE hvd_serve_prefill_tokens_total counter")
            lines.append(
                f"hvd_serve_prefill_tokens_total "
                f"{self.prefill_tokens_total}")
            lines.append("# TYPE hvd_serve_decode_tokens_total counter")
            lines.append(
                f"hvd_serve_decode_tokens_total {self.decode_tokens_total}")
            # Paged-KV utilization + prefix cache (docs/serving.md).
            lines.append("# TYPE hvd_serve_kv_blocks gauge")
            for rid, s in sorted(kv.items()):
                for state in ("used", "free", "retained"):
                    lines.append(
                        f'hvd_serve_kv_blocks{{replica="{rid}",'
                        f'state="{state}"}} {s.get(state, 0)}')
            lines.append("# TYPE hvd_serve_kv_cow_copies_total counter")
            for rid, s in sorted(kv.items()):
                lines.append(
                    f'hvd_serve_kv_cow_copies_total{{replica="{rid}"}} '
                    f'{s.get("cow", 0)}')
            # n>1 parallel sampling: sequences forked off a shared
            # prompt through CoW block tables (engine.seq_forks — the
            # PR 4 CoW path's first real consumer, observable from the
            # first forked request) + the requests that forked.
            lines.append("# TYPE hvd_serve_cow_forks_total counter")
            for rid, s in sorted(kv.items()):
                lines.append(
                    f'hvd_serve_cow_forks_total{{replica="{rid}"}} '
                    f'{s.get("seq_forks", 0)}')
            lines.append("# TYPE hvd_serve_forked_requests_total counter")
            for rid, s in sorted(kv.items()):
                lines.append(
                    f'hvd_serve_forked_requests_total{{replica="{rid}"}} '
                    f'{s.get("forked_requests", 0)}')
            # Speculative decoding: drafted/accepted/rejected token
            # counters + the acceptance-rate gauge (docs/serving.md).
            lines.append("# TYPE hvd_serve_spec_tokens_total counter")
            for result, n in (("drafted", self.spec_drafted_total),
                              ("accepted", self.spec_accepted_total),
                              ("rejected", self.spec_rejected_total)):
                lines.append(
                    f'hvd_serve_spec_tokens_total{{result="{result}"}} '
                    f'{n}')
            lines.append("# TYPE hvd_serve_spec_steps_total counter")
            lines.append(
                f"hvd_serve_spec_steps_total {self.spec_steps_total}")
            lines.append("# TYPE hvd_serve_spec_acceptance_rate gauge")
            rate = (self.spec_accepted_total / self.spec_drafted_total
                    if self.spec_drafted_total else 0.0)
            lines.append(f"hvd_serve_spec_acceptance_rate {rate:g}")
            # Tiered-KV plane (serve/tiering.py): fault-stall histogram
            # (part of the inter-decode-step p99 contract), bytes moved
            # per direction, migration hits, and per-replica tier
            # occupancy gauges off the manager stats.
            hist("hvd_serve_tier_fault_stall_ms", self.tier_stall_ms,
                 "Engine-loop stall waiting on a tier fetch that lost "
                 "its prefetch race, ms")
            lines.append("# TYPE hvd_serve_tier_faults_total counter")
            lines.append(
                f"hvd_serve_tier_faults_total {self.tier_faults_total}")
            lines.append("# TYPE hvd_serve_tier_bytes_total counter")
            for direction, n in (("spill", self.tier_spill_bytes),
                                 ("promote", self.tier_promote_bytes),
                                 ("demote", self.tier_demote_bytes)):
                lines.append(
                    f'hvd_serve_tier_bytes_total{{direction='
                    f'"{direction}"}} {n}')
            lines.append("# TYPE hvd_serve_tier_migrations_total counter")
            lines.append(f"hvd_serve_tier_migrations_total "
                         f"{self.tier_migrations_total}")
            lines.append(
                "# TYPE hvd_serve_tier_migrated_tokens_total counter")
            lines.append(f"hvd_serve_tier_migrated_tokens_total "
                         f"{self.tier_migrated_tokens}")
            lines.append("# TYPE hvd_serve_tier_host_blocks gauge")
            for rid, s in sorted(kv.items()):
                t = s.get("tier")
                if t is not None:
                    lines.append(
                        f'hvd_serve_tier_host_blocks{{replica="{rid}"}} '
                        f'{t.get("host_blocks", 0)}')
            lines.append("# TYPE hvd_serve_prefix_cache_hit_rate gauge")
            for rid, s in sorted(kv.items()):
                lines.append(
                    f'hvd_serve_prefix_cache_hit_rate{{replica="{rid}"}} '
                    f'{s.get("prefix_hit_rate", 0.0):g}')
            # KV storage density + attention implementation per replica
            # (docs/serving.md paged-kernel section): bytes-per-token is
            # the quantized-KV win in one number; the impl/dtype info
            # gauges (constant 1, identity in the labels — Prometheus
            # *_info convention) make a fleet's gather-vs-kernel and
            # bf16-vs-int8 mix visible at a glance.
            lines.append("# TYPE hvd_serve_kv_bytes_per_token gauge")
            for rid, s in sorted(kv.items()):
                if "kv_bytes_per_token" in s:
                    lines.append(
                        f'hvd_serve_kv_bytes_per_token{{replica="{rid}"}} '
                        f'{s["kv_bytes_per_token"]:g}')
            # hvdmem pool-budget headroom (docs/serving.md kv_headroom):
            # budget − (pool + weights), negative = the HVD302 overshoot
            # condition; present only when a budget is known
            # (HVD_MEM_BUDGET_BYTES / probed HBM).
            lines.append("# TYPE hvd_serve_kv_headroom_bytes gauge")
            for rid, s in sorted(kv.items()):
                if "kv_headroom_bytes" in s:
                    lines.append(
                        f'hvd_serve_kv_headroom_bytes{{replica="{rid}"}} '
                        f'{s["kv_headroom_bytes"]}')
            lines.append("# TYPE hvd_serve_attention_impl gauge")
            for rid, s in sorted(kv.items()):
                if "attn_impl" in s:
                    lines.append(
                        f'hvd_serve_attention_impl{{replica="{rid}",'
                        f'impl="{s["attn_impl"]}"}} 1')
            lines.append("# TYPE hvd_serve_kv_dtype gauge")
            for rid, s in sorted(kv.items()):
                if "kv_dtype" in s:
                    lines.append(
                        f'hvd_serve_kv_dtype{{replica="{rid}",'
                        f'dtype="{s["kv_dtype"]}"}} 1')
            # Timeline writer-queue drop accounting (timeline.py bounded
            # queue): a truncated trace must be detectable from the
            # metrics plane too, not only from the trace trailer.
            if self._timeline is not None:
                try:
                    dropped = int(self._timeline.dropped_events)
                except Exception:
                    # An unreadable counter is OMITTED, not faked: a -1
                    # would be an invalid (negative, resetting) value
                    # for a Prometheus counter series.
                    dropped = None
                if dropped is not None:
                    lines.append("# TYPE hvd_timeline_dropped_events_"
                                 "total counter")
                    lines.append(
                        f"hvd_timeline_dropped_events_total {dropped}")
            elapsed = max(time.monotonic() - self.started_at, 1e-9)
            lines.append("# TYPE hvd_serve_tokens_per_sec gauge")
            lines.append(
                f"hvd_serve_tokens_per_sec {self.tokens_total / elapsed:g}")
            return "\n".join(lines) + "\n"

    # -- timeline bridge ----------------------------------------------------

    def set_timeline(self, timeline) -> None:
        """Register a ``timeline.Timeline``; subsequent decode steps emit
        SERVE/* counter events (rate-limited, see module docstring)."""
        with self._lock:
            self._timeline = timeline
            self._steps_since_emit = 0

    def maybe_emit_timeline(self, force: bool = False,
                            kv_stats: Optional[dict] = None) -> None:
        """Rate-limited SERVE/* counter emission.  ``kv_stats`` (a
        BlockManager ``stats()`` dict, passed by the paged engine) adds
        block-utilization / prefix-hit-rate / token-split counters."""
        with self._lock:
            tl = self._timeline
            if tl is None:
                return
            if not force and self._steps_since_emit < self._timeline_every:
                return
            self._steps_since_emit = 0
        depth = sum(max(d, 0) for d in self._queue_depths().values())
        with self._lock:
            occ_mean = (self.occupancy_sum / self.occupancy_samples
                        if self.occupancy_samples else 0.0)
            counters = {
                "tokens_total": self.tokens_total,
                "occupancy": self.occupancy_last,
                "occupancy_mean": round(occ_mean, 3),
                "queue_depth": depth,
                "ttft_p50_ms": self.ttft_ms.quantile(0.5),
                "token_step_p50_ms": self.token_step_ms.quantile(0.5),
                "prefill_tokens_total": self.prefill_tokens_total,
                "decode_tokens_total": self.decode_tokens_total,
            }
            if kv_stats is not None:
                counters["kv_blocks_used"] = kv_stats.get("used", 0)
                counters["kv_blocks_free"] = kv_stats.get("free", 0)
                counters["kv_blocks_retained"] = kv_stats.get("retained", 0)
                counters["prefix_hit_rate"] = round(
                    kv_stats.get("prefix_hit_rate", 0.0), 4)
        try:
            tl.serve_counter("engine", counters)
        except Exception:
            pass  # the metrics path must never take down the decode loop
