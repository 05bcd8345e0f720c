"""faultline unit suite (ISSUE 6): seeded plans, every injection point,
and the self-healing paths they exercise.

The chaos soak (tests/test_faultline_soak.py, ``slow``) proves the
multi-fault convergence story end to end; this file pins each piece in
isolation and fast enough for tier-1:

* plan determinism — identical seed → identical schedule → identical
  firing sequence (the acceptance artifact);
* engine injections (poison-step / slow-decode / pool-corrupt-block) and
  the recovery each must trigger;
* KV client retry/backoff — transient transport faults retried with the
  ``HVD_KV_RETRY_*`` budget, 4xx answered without a retry;
* deadline propagation — a doomed request is never prefilled, an
  in-flight request dies at its deadline and frees its slot + blocks;
* scale-up — ``mark_alive`` / ``report_rank_recovered`` /
  ``add_replica`` and the hardened ``watch_preemption`` loop that feeds
  them.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import faultline as fl
from horovod_tpu.faultline.plan import FaultInjected
from horovod_tpu.models import create_mlp
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serve import (DeadlineExceededError, DynamicBatcher,
                               InferenceEngine, MLPAdapter, Replica,
                               ReplicaScheduler, Request, ServeMetrics,
                               ServeServer, TransformerAdapter)

VOCAB = 31


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    fl.uninstall()
    yield
    fl.uninstall()


def _mlp_adapter(seed=3, vocab=VOCAB, max_len=128):
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return MLPAdapter(mlp, params, vocab_size=vocab, max_len=max_len)


class _SlowMLP(MLPAdapter):
    """MLP adapter with a visible per-decode-step cost, so a request can
    be held in flight long enough to fault deterministically."""

    delay_s = 0.02

    def decode_paged(self, cache, tokens, positions, tables):
        time.sleep(self.delay_s)
        return super().decode_paged(cache, tokens, positions, tables)


def _slow_adapter(seed=3, vocab=VOCAB):
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return _SlowMLP(mlp, params, vocab_size=vocab, max_len=256)


def _engine(adapter=None, replica_id="replica-f", **kw):
    kw.setdefault("max_batch", 4)
    return InferenceEngine(adapter or _mlp_adapter(),
                           metrics=ServeMetrics(),
                           replica_id=replica_id, **kw)


# -- plan: schedule, determinism, grammar, env -------------------------------

def _three_specs():
    return [fl.FaultSpec("poison-step", target="replica-0"),
            fl.FaultSpec("drop-kv-response", repeat=2),
            fl.FaultSpec("kill-rank", target="h0", repeat=3)]


def test_plan_same_seed_same_schedule_and_firing_sequence():
    p1 = fl.FaultPlan(_three_specs(), seed=7)
    p2 = fl.FaultPlan(_three_specs(), seed=7)
    assert p1.schedule() == p2.schedule()
    for p in (p1, p2):
        for _ in range(fl.HORIZON + 8):
            p.fire("engine.step", "replica-0")
            p.fire("kv.request", "a:1")
            p.fire("preempt.poll", "h0")
    assert p1.firing_sequence() == p2.firing_sequence()
    assert len(p1.firing_sequence()) == 1 + 2 + 3  # every window fired
    assert p1.exhausted() and p2.exhausted()


def test_plan_different_seed_different_schedule():
    # 3 specs over a 16-step horizon: seeds 0..9 all landing on seed 7's
    # exact schedule is ~(1/16)^3 per seed — astronomically unlikely.
    base = fl.FaultPlan(_three_specs(), seed=7).schedule()
    assert any(fl.FaultPlan(_three_specs(), seed=s).schedule() != base
               for s in range(10))


def test_plan_explicit_step_does_not_reshuffle_others():
    """The rng draw happens for every spec, so pinning one spec's step
    leaves the seeded steps of the rest untouched."""
    loose = fl.FaultPlan(_three_specs(), seed=3).schedule()
    specs = _three_specs()
    specs[0].step = 2
    pinned = fl.FaultPlan(specs, seed=3).schedule()
    assert pinned[0]["step"] == 2
    assert [s["step"] for s in pinned[1:]] == \
        [s["step"] for s in loose[1:]]


def test_plan_copies_specs_so_reuse_is_pure():
    """FaultPlan must not mutate the caller's FaultSpec objects: a spec
    list reused across plans (a repeat-soak harness) gets a fresh step
    assignment and fresh firing state each time."""
    specs = [fl.FaultSpec("poison-step", target="r0")]
    p1 = fl.FaultPlan(specs, seed=1)
    for _ in range(fl.HORIZON + 2):
        p1.fire("engine.step", "r0")
    assert p1.exhausted()
    assert specs[0].step is None and specs[0].fired == 0  # untouched
    p2 = fl.FaultPlan(specs, seed=1)
    assert not p2.exhausted()
    for _ in range(fl.HORIZON + 2):
        p2.fire("engine.step", "r0")
    assert p2.firing_sequence() == p1.firing_sequence()  # and re-fires


def test_plan_target_and_instance_filtering():
    plan = fl.FaultPlan([fl.FaultSpec("poison-step", step=1,
                                      target="replica-1")], seed=0)
    # replica-0's counter crossing index 1 must NOT fire replica-1's
    # fault (and must not consume it either).
    for _ in range(4):
        assert plan.fire("engine.step", "replica-0") == []
    assert plan.fire("engine.step", "replica-1") == []       # index 0
    assert [f.kind for f in plan.fire("engine.step", "replica-1")] == \
        ["poison-step"]                                      # index 1
    assert plan.fire("engine.step", "replica-1") == []       # exhausted


def test_parse_plan_grammar():
    plan = fl.parse_plan(
        "kill-rank:h3@4*3, drop-kv-response@1*2, slow-decode~0.05,"
        "poison-step:replica-1/replica.route", seed=1)
    d = plan.schedule()
    assert d[0] == {"kind": "kill-rank", "point": "preempt.poll",
                    "step": 4, "target": "h3", "repeat": 3, "param": 0.0,
                    "fired": 0}
    assert (d[1]["step"], d[1]["repeat"]) == (1, 2)
    assert d[2]["param"] == 0.05
    assert d[3]["point"] == "replica.route"
    # Suffix markers are order-insensitive (each at most once).
    flipped = fl.parse_spec("slow-decode~0.08@2").to_dict()
    assert (flipped["step"], flipped["param"]) == (2, 0.08)
    with pytest.raises(ValueError):
        fl.parse_spec("no-such-fault")
    with pytest.raises(ValueError):
        fl.parse_spec("poison-step/nowhere")
    with pytest.raises(ValueError):
        fl.parse_spec("slow-decode@1@2")


def test_env_bootstrap_installs_once(monkeypatch):
    import horovod_tpu.faultline.runtime as rt
    monkeypatch.setenv("HVD_FAULTLINE_PLAN", "poison-step:replica-9@2")
    monkeypatch.setenv("HVD_FAULTLINE_SEED", "5")
    monkeypatch.setattr(rt, "_env_checked", False)
    plan = fl.maybe_install_from_env()
    assert plan is not None and fl.active_plan() is plan
    assert fl.fire("engine.step", "replica-9") == []  # step 0
    assert fl.fire("engine.step", "replica-9") == []  # step 1
    assert [f.kind for f in fl.fire("engine.step", "replica-9")] == \
        ["poison-step"]
    # A second bootstrap never replaces the active plan.
    assert fl.maybe_install_from_env() is plan


def test_fault_firings_land_in_the_timeline(tmp_path):
    from horovod_tpu.timeline import Timeline
    path = str(tmp_path / "fault_trace.json")
    tl = Timeline(path)
    plan = fl.FaultPlan([fl.FaultSpec("slow-decode", step=0)], seed=0)
    plan.set_timeline(tl)
    plan.fire("engine.step", "replica-0")
    tl.close()
    events = json.load(open(path))
    (ev,) = [e for e in events
             if e.get("name", "").startswith("FAULTLINE/")]
    assert ev["name"] == "FAULTLINE/slow-decode"
    assert ev["args"] == {"point": "engine.step",
                          "instance": "replica-0", "step": 0}


# -- engine injection point --------------------------------------------------

def test_poison_step_fails_inflight_and_engine_recovers():
    eng = _engine(_slow_adapter()).start()
    try:
        victim = Request([3], max_new_tokens=200)
        eng.batcher.submit(victim)
        deadline = time.monotonic() + 30
        while eng.active_count == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert eng.active_count == 1
        # Installed mid-flight: the fault fires on the NEXT iteration, so
        # the victim is deterministically in the poisoned batch.
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("poison-step", step=0, target="replica-f")]))
        with pytest.raises(FaultInjected):
            victim.result(timeout=30)
        # One poisoned batch must not take the replica down.
        after = eng.generate([5], max_new_tokens=4, timeout_s=30)
        assert len(after) == 4
        snap = eng.metrics.snapshot()
        assert snap["requests"]["error"] == 1
        assert snap["requests"]["ok"] == 1
        assert fl.active_plan().firing_sequence() == \
            [("engine.step", 0, "poison-step")]
    finally:
        eng.stop()


def test_slow_decode_stalls_but_serves_correctly():
    eng = _engine().start()
    try:
        baseline = eng.generate([7], max_new_tokens=4, timeout_s=30)
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("slow-decode", step=0, target="replica-f",
                          param=0.15)]))
        t0 = time.monotonic()
        out = eng.generate([7], max_new_tokens=4, timeout_s=30)
        assert out == baseline           # a stall never changes tokens
        assert time.monotonic() - t0 >= 0.14  # the injected stall landed
        assert fl.active_plan().exhausted()
    finally:
        eng.stop()


_TINY = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=64, causal=True,
                          dtype=jnp.float32, scan_layers=False)


def test_pool_corrupt_block_scrubs_prefix_cache_and_stays_exact():
    model = Transformer(_TINY)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ad = TransformerAdapter(_TINY, params, block_tokens=8)
    eng = _engine(ad, prefill_chunk=16).start()
    try:
        prompt = list(range(1, 25))  # 3 full blocks of 8
        first = eng.generate(prompt, max_new_tokens=4, timeout_s=60)
        assert eng.kv_stats()["retained"] > 0  # prompt blocks cached
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("pool-corrupt-block", step=0,
                          target="replica-f", param=99)]))
        deadline = time.monotonic() + 30
        # Poll the OUTCOME (registry scrubbed), not just exhausted():
        # fire() marks the spec fired before the engine's handler runs
        # the scrub, so exhausted-then-check races the handler.
        while eng.kv_stats()["retained"] > 0 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert fl.active_plan().exhausted()
        assert eng.kv_stats()["retained"] == 0  # registry scrubbed
        # The same prompt re-prefills from scratch and matches exactly —
        # a corrupted block is DROPPED, never served stale.
        assert eng.generate(prompt, max_new_tokens=4,
                            timeout_s=60) == first
    finally:
        eng.stop()


def test_block_manager_invalidate_retained_skips_referenced_blocks():
    from horovod_tpu.serve import BlockManager, chain_hashes
    bm = BlockManager(8, 4, prefix_cache=True)
    held = bm.allocate(2)
    hashes = chain_hashes(list(range(8)), 4)
    bm.register(hashes[0], held[0])
    bm.register(hashes[1], held[1])
    bm.free(held[0])                  # retained (refcount 0, registered)
    assert bm.stats()["retained"] == 1
    assert bm.invalidate_retained(5) == 1   # only the retained one
    assert bm.stats()["retained"] == 0
    assert bm.refcount(held[1]) == 1        # live block untouched
    assert bm.lookup_prefix(list(range(8)),
                            hashes=hashes)[0] != [held[0]]


# -- deadline propagation ----------------------------------------------------

def test_doomed_request_is_never_prefilled():
    eng = _engine()
    doomed = Request([4], max_new_tokens=8, timeout_s=0.05)
    eng.batcher.submit(doomed)
    time.sleep(0.1)                # budget dies while queued
    eng.start()
    try:
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=10)
        snap = eng.metrics.snapshot()
        assert snap["prefills"] == 0          # never prefilled
        assert snap["requests"]["expired"] == 1
    finally:
        eng.stop()


def test_inflight_deadline_expires_and_frees_the_slot():
    eng = _engine(_slow_adapter()).start()
    try:
        r = Request([3], max_new_tokens=200, timeout_s=0.3)
        eng.batcher.submit(r)
        with pytest.raises(DeadlineExceededError) as ei:
            r.result(timeout=30)
        assert "mid-flight" in str(ei.value)
        assert 0 < len(r.generated) < 200    # really died mid-decode
        deadline = time.monotonic() + 10
        while eng.active_count and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.active_count == 0         # slot freed immediately
        assert eng.metrics.snapshot()["requests"]["expired"] == 1
        # The engine keeps serving within-budget requests.
        assert len(eng.generate([5], max_new_tokens=3,
                                timeout_s=30)) == 3
    finally:
        eng.stop()


def test_request_rejects_non_positive_timeout():
    with pytest.raises(ValueError):
        Request([1], timeout_s=0)
    with pytest.raises(ValueError):
        Request([1], timeout_s=-3)
    assert Request([1], timeout_s=5).remaining() <= 5.0
    assert Request([1]).remaining() is None


# -- HTTP deadline surface ---------------------------------------------------

def _post(port, payload, headers=()):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body, method="POST",
        headers=dict({"Content-Type": "application/json"}, **dict(headers)))
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def _two_replica_server(adapter_fn=_mlp_adapter):
    replicas = [Replica(f"replica-{i}", None,
                        _engine(adapter_fn(), replica_id=f"replica-{i}"))
                for i in range(2)]
    metrics = replicas[0].engine.metrics
    sched = ReplicaScheduler(replicas, metrics=metrics)
    server = ServeServer(sched, request_timeout_s=60)
    port = server.start(port=0, host="127.0.0.1")
    return server, sched, port


def test_http_non_positive_timeout_is_400_not_a_parked_handler():
    server, _, port = _two_replica_server()
    try:
        for bad in (0, -1, "0"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(port, {"tokens": [1, 2], "timeout_s": bad})
            assert ei.value.code == 400, bad
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"tokens": [1, 2]},
                  headers=[("X-Request-Timeout-S", "-2")])
        assert ei.value.code == 400
    finally:
        server.stop()


def test_http_header_timeout_propagates_and_504_carries_budget():
    server, _, port = _two_replica_server(_slow_adapter)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"tokens": [1, 2], "max_new_tokens": 200},
                  headers=[("X-Request-Timeout-S", "0.3")])
        assert ei.value.code == 504
        # The header reached Request.deadline (the engine killed it, not
        # the server-side 60 s cap) and the shed reports the spent budget.
        assert ei.value.headers["X-Deadline-Remaining-S"] == "0.000"
        assert ei.value.headers["Retry-After"] == "0"
    finally:
        server.stop()


def test_http_503_carries_remaining_budget_header():
    server, sched, port = _two_replica_server()
    try:
        sched.mark_dead("replica-0")
        sched.mark_dead("replica-1")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"tokens": [1], "timeout_s": 30})
        assert ei.value.code == 503
        # Retry-After stays the MINIMUM-wait availability hint (capped
        # by the budget — advertising the full budget there would make
        # a compliant client sleep it away); the exact budget rides the
        # X- header.
        assert ei.value.headers["Retry-After"] == "1"
        assert 25 < float(ei.value.headers["X-Deadline-Remaining-S"]) <= 30
        # Legacy flat hint without a client deadline.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"tokens": [1]})
        assert ei.value.headers["Retry-After"] == "1"
        assert "X-Deadline-Remaining-S" not in ei.value.headers
    finally:
        server.stop()


# -- scale-up: mark_alive / add_replica / recovered ranks --------------------

def test_mark_alive_reopens_batcher_and_rejoins_routing():
    server, sched, port = _two_replica_server()
    try:
        sched.mark_dead("replica-0", reason="test kill")
        health = sched.healthz()
        assert health["status"] == "degraded"
        out_degraded = _post(port, {"tokens": [3, 4]})
        assert out_degraded["replica"] == "replica-1"

        sched.mark_alive("replica-0", reason="test recovery")
        assert sched.healthz()["status"] == "ok"
        snap = sched.metrics.snapshot()
        assert snap["replica_events"] == {"mark_dead": 1, "mark_alive": 1}
        # The revived batcher accepts and its engine answers: load
        # replica-1 so least-loaded routing picks the empty revival.
        r1 = sched.replicas[1]
        blocker = Request([2] * 3, max_new_tokens=120)
        r1.engine.batcher.submit(blocker)
        out = _post(port, {"tokens": [3, 4]})
        assert out["replica"] == "replica-0"
        assert out["tokens"] == out_degraded["tokens"]  # exactness holds
        blocker.result(timeout=30)
        # Idempotent on a healthy replica.
        sched.mark_alive("replica-0")
        assert sched.metrics.snapshot()["replica_events"]["mark_alive"] == 1
    finally:
        server.stop()


def test_add_replica_scales_the_fleet_up():
    server, sched, port = _two_replica_server()
    try:
        new = Replica("replica-2", None,
                      _engine(_mlp_adapter(), replica_id="replica-2"))
        sched.add_replica(new)
        health = sched.healthz()
        assert health["total"] == 3 and health["status"] == "ok"
        # The new engine was started (scheduler already running) and
        # serves through the normal routing path.
        for r in sched.replicas[:2]:
            r.engine.batcher.submit(Request([2] * 3, max_new_tokens=120))
        out = _post(port, {"tokens": [5]})
        assert out["replica"] == "replica-2"
        with pytest.raises(ValueError):
            sched.add_replica(Replica("replica-2", None, _engine()))
    finally:
        server.stop()


def test_report_rank_recovered_maps_rank_to_dead_replica():
    import types
    replicas = [Replica(f"replica-{i}",
                        types.SimpleNamespace(ranks=[2 * i, 2 * i + 1],
                                              size=lambda: 2),
                        _engine(replica_id=f"replica-{i}"))
                for i in range(2)]
    sched = ReplicaScheduler(replicas,
                             metrics=replicas[0].engine.metrics).start()
    try:
        assert sched.report_rank_lost(3) == "replica-1"
        assert sched.healthz()["status"] == "degraded"
        assert sched.report_rank_recovered(5) is None  # no such replica
        assert sched.report_rank_recovered(2) == "replica-1"
        assert sched.healthz()["status"] == "ok"
    finally:
        sched.stop()


# -- hardened preemption watcher ---------------------------------------------

class _ScriptedKV:
    """scan() plays a script: exceptions raise, dicts return; the last
    entry repeats forever."""

    def __init__(self, script):
        self.script = list(script)

    def scan(self, scope):
        item = self.script.pop(0) if len(self.script) > 1 \
            else self.script[0]
        if isinstance(item, Exception):
            raise item
        return item


def test_watcher_survives_kv_errors_counts_them_and_heals_the_fleet():
    import types
    replicas = [Replica(f"replica-{i}",
                        types.SimpleNamespace(ranks=[i], size=lambda: 1),
                        _engine(replica_id=f"replica-{i}"))
                for i in range(2)]
    sched = ReplicaScheduler(replicas,
                             metrics=replicas[0].engine.metrics).start()
    kv = _ScriptedKV([OSError("flake 1"), OSError("flake 2"),
                      {"h0": b"TERMINATE"}, {"h0": b"TERMINATE"}, {}])
    try:
        sched.watch_preemption(kv, {"h0": [0]}, poll_s=0.01)
        deadline = time.monotonic() + 30
        # Poll the monotonic transition counters to their final values —
        # not the transient "degraded" status (the scripted clearance
        # re-heals within ~2 polls, so a loaded box can miss the window)
        # and not state flags (mark_alive flips state before counting).
        want = {"mark_dead": 1, "mark_alive": 1}
        while sched.metrics.snapshot()["replica_events"] != want \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        while sched.healthz()["status"] != "ok" \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sched.healthz()["status"] == "ok"
        snap = sched.metrics.snapshot()
        assert snap["preempt_poll_errors"] == 2
        assert snap["replica_events"] == want
        metrics_text = sched.metrics.render()
        assert "hvd_serve_preempt_poll_errors_total 2" in metrics_text
        assert ('hvd_serve_replica_events_total{event="mark_alive"} 1'
                in metrics_text)
    finally:
        sched.stop()


# -- KV client retry/backoff -------------------------------------------------

@pytest.fixture()
def kv_world(monkeypatch):
    from horovod_tpu.runner.http_server import KVStoreClient, KVStoreServer
    monkeypatch.setenv("HVD_TPU_KV_SERVER", "python")
    monkeypatch.setenv("HVD_KV_RETRY_MAX", "3")
    monkeypatch.setenv("HVD_KV_RETRY_BASE_MS", "1")
    monkeypatch.setenv("HVD_KV_RETRY_CAP_MS", "5")
    server = KVStoreServer()
    port = server.start(0)
    client = KVStoreClient("127.0.0.1", port)
    yield server, client
    server.stop()


def test_kv_retry_survives_a_drop_train_within_budget(kv_world):
    _, client = kv_world
    assert client.retry_max == 3
    plan = fl.install(fl.FaultPlan(
        [fl.FaultSpec("drop-kv-response", step=1, repeat=2)]))
    client.put("s", "k", b"v")                 # attempt 0: clean
    assert client.get("s", "k") == b"v"        # attempts 1,2 dropped;
    assert plan.exhausted()                    # 3rd succeeds


def test_kv_retry_exhaustion_raises_the_transport_error(kv_world):
    _, client = kv_world
    fl.install(fl.FaultPlan(
        [fl.FaultSpec("drop-kv-response", step=0, repeat=3)]))
    with pytest.raises(ConnectionError):
        client.get("s", "nope")
    # The drop train consumed the whole retry budget: 3 attempts.
    assert fl.active_plan().count("kv.request", "127.0.0.1:"
                                  + str(client.port)) == 3
    # The next request reconnects and works (poisoned socket dropped).
    client.put("s", "k2", b"w")
    assert client.get("s", "k2") == b"w"


def test_kv_4xx_is_fatal_not_retried(kv_world):
    _, client = kv_world
    plan = fl.install(fl.FaultPlan([]))  # counters only
    status, _ = client._request("POST", "/scope", body=b"{not json")
    assert status == 400                       # server answered
    assert plan.count("kv.request",
                      f"127.0.0.1:{client.port}") == 1  # no retry


def test_kv_delay_fault_slows_but_succeeds(kv_world):
    _, client = kv_world
    fl.install(fl.FaultPlan(
        [fl.FaultSpec("delay-kv", step=0, param=0.1)]))
    t0 = time.monotonic()
    client.put("s", "k", b"v")              # the delay lands here
    assert time.monotonic() - t0 >= 0.1
    assert client.get("s", "k") == b"v"     # ...and nothing broke
    assert fl.active_plan().exhausted()


def test_kv_backoff_is_capped_and_jittered(monkeypatch):
    from horovod_tpu.runner.http_server import KVStoreClient
    monkeypatch.setenv("HVD_KV_RETRY_MAX", "5")
    monkeypatch.setenv("HVD_KV_RETRY_BASE_MS", "8")
    monkeypatch.setenv("HVD_KV_RETRY_CAP_MS", "20")
    client = KVStoreClient("127.0.0.1", 1)
    for attempt in range(1, 8):
        d = client._retry_backoff_s(attempt)
        assert 0.004 <= d <= 0.020  # jitter in [0.5, 1) x capped base


# -- replica.route injection point -------------------------------------------

def test_route_kill_rank_fault_kills_named_replica_and_fails_over():
    server, sched, port = _two_replica_server()
    try:
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("kill-rank", point="replica.route", step=0,
                          target="replica-0")]))
        out = _post(port, {"tokens": [2, 3]})  # triggers + fails over
        assert out["replica"] == "replica-1"
        assert sched.healthz()["status"] == "degraded"
        assert [r["state"] for r in sched.healthz()["replicas"]] == \
            ["dead", "healthy"]
    finally:
        server.stop()


# -- preempt.poll injection point (sentinel marker publication) --------------

def test_sentinel_publishes_and_clears_marker_under_kill_rank_fault(
        kv_world, monkeypatch):
    from horovod_tpu.elastic.preemption import (PREEMPT_SCOPE,
                                                PreemptionSentinel)
    _, client = kv_world
    # Unreachable metadata endpoint: with a plan installed the sentinel
    # reads that as "NONE", so the post-fault clear path works hermetically.
    monkeypatch.setenv("HVD_TPU_MAINTENANCE_URL",
                       "http://127.0.0.1:9/never")
    plan = fl.install(fl.FaultPlan(
        [fl.FaultSpec("kill-rank", step=2, target="chaos-host",
                      repeat=2)]))
    sentinel = PreemptionSentinel(client, hostname="chaos-host",
                                  poll_interval_s=0.01)
    for _ in range(2):
        sentinel.step()                       # steps 0-1: no fault
    assert client.scan(PREEMPT_SCOPE) == {}
    sentinel.step()                           # step 2: fault fires
    assert client.scan(PREEMPT_SCOPE) == {"chaos-host": b"FAULTLINE_PREEMPT"}
    sentinel.step()                           # step 3: still in window
    sentinel.step()                           # step 4: window over -> clear
    assert client.scan(PREEMPT_SCOPE) == {}
    assert plan.firing_sequence() == [("preempt.poll", 2, "kill-rank"),
                                      ("preempt.poll", 3, "kill-rank")]
