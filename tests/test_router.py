"""ISSUE 18: hvdroute — fault-tolerant prefix-affinity front door.

Pins the router's contracts without sockets (``Router._transport`` is
the monkeypatch seam) plus the HTTP-layer satellites over real
listeners:

* consistent-hash ring — insertion-order independent, distinct
  preference order, removal only remaps the removed endpoint's keys;
* affinity key — fixed-depth chain hash stays stable as a session's
  transcript grows append-only; model salt separates fleets;
* bounded-load / brownout power-of-two fallback;
* passive health — consecutive-failure ejection, half-open probe,
  readmission, and the no-candidate probe-window wait (zero-lost);
* deadline-bounded retries — 502 on retry exhaustion, 504 on budget
  exhaustion, 503 honored as backpressure with Retry-After clamped to
  the remaining client budget on pass-through;
* tail hedging — slow primary raced against the next candidate, first
  definitive winner used;
* faultline — ``drop-route`` / ``slow-route`` / ``blackhole-endpoint``
  / ``kill-rank`` at ``router.forward``, including ejection counters
  reconciling with the backend scheduler's ``replica_events`` during a
  concurrent scale-down (the ISSUE 18 chaos satellite);
* drain — ServeServer and RouterServer refuse new work with 503 +
  ``Connection: close`` while in-flight requests finish, and the
  drain-refusal Retry-After is clamped by the header-borne client
  budget even though no Request object exists yet (the ISSUE 18 clamp
  satellite).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from horovod_tpu import faultline as fl
from horovod_tpu.models import create_mlp
from horovod_tpu.serve import (MLPAdapter, Router, RouterConfig,
                               RouterServer, ServeMetrics, ServeServer,
                               build_replicas)
from horovod_tpu.serve.router import _HashRing

VOCAB = 31

EP0, EP1 = "10.0.0.1:8000", "10.0.0.2:8000"

_OK_BODY = json.dumps({"tokens": [1, 2, 3]}).encode()


def _mlp_adapter(seed=3):
    mlp = create_mlp(features=(16, VOCAB))
    params = mlp.init(jax.random.PRNGKey(seed),
                      np.zeros((1, VOCAB), np.float32))["params"]
    return MLPAdapter(mlp, params, vocab_size=VOCAB, max_len=128)


def _fast_config(**overrides):
    base = dict(retry_base_s=0.001, retry_cap_s=0.005, probe_s=0.05,
                eject_failures=2, block_tokens=4)
    base.update(overrides)
    return RouterConfig(**base)


def _stub(router, behavior, calls=None):
    """Replace the transport seam: ``behavior[name]`` is a response
    tuple, an Exception to raise, or a callable returning either."""
    calls = [] if calls is None else calls

    def transport(host, port, method, path, body, headers, timeout_s):
        name = f"{host}:{port}"
        calls.append(name)
        out = behavior[name]
        if callable(out):
            out = out()
        if isinstance(out, Exception):
            raise out
        return out

    router._transport = transport
    return calls


def _key_for(router, target, want_second=None):
    """A token prompt whose ring preference order starts at ``target``
    (and, optionally, whose failover candidate is ``want_second``)."""
    for s in range(4096):
        p = [(7 * s + j) % VOCAB for j in range(12)]
        order = router._ring.lookup(router.affinity_key(p))
        if order[0] == target and \
                (want_second is None or order[1] == want_second):
            return p
    raise AssertionError(f"no prompt routes to {target}")


def _body(tokens, **extra):
    return json.dumps(dict({"tokens": tokens}, **extra)).encode()


# ---------------------------------------------------------------------------
# ring + affinity key
# ---------------------------------------------------------------------------

def test_ring_order_independent_and_distinct():
    names = [f"10.0.0.{i}:80" for i in range(5)]
    a, b = _HashRing(vnodes=32), _HashRing(vnodes=32)
    for n in names:
        a.add(n)
    for n in reversed(names):
        b.add(n)
    for key in range(50):
        assert a.lookup(key) == b.lookup(key)
        order = a.lookup(key)
        assert sorted(order) == sorted(names)  # all endpoints, no dups


def test_ring_removal_only_remaps_victims_keys():
    names = [f"10.0.0.{i}:80" for i in range(5)]
    ring = _HashRing(vnodes=32)
    for n in names:
        ring.add(n)
    before = {key: ring.lookup(key)[0] for key in range(200)}
    ring.remove(names[2])
    for key, first in before.items():
        if first == names[2]:
            assert ring.lookup(key)[0] != names[2]
        else:
            assert ring.lookup(key)[0] == first  # undisturbed


def test_affinity_key_stable_as_transcript_grows():
    r = Router([EP0, EP1], config=_fast_config(affinity_blocks=2))
    tokens = list(range(1, 13))  # 3 full 4-token blocks
    key = r.affinity_key(tokens)
    # Append-only growth (multi-turn session): key must not move.
    assert r.affinity_key(tokens + [5, 6, 7, 8, 9]) == key
    # A different leading block is a different session.
    assert r.affinity_key([9] + tokens[1:]) != key
    # Model salt separates fleets sharing a router.
    assert r.affinity_key(tokens, model="m1") != key
    # Sub-block prompts still key deterministically.
    assert r.affinity_key([1, 2]) == r.affinity_key([1, 2])


def test_bounded_load_and_brownout_fallback():
    r = Router([EP0, EP1], config=_fast_config(bounded_load=2.0))
    p = _key_for(r, EP0, want_second=EP1)
    key = r.affinity_key(p)
    affinity, avail = r._candidates(key)
    assert affinity == EP0 and avail[0] == EP0
    # Hot affinity target: power-of-two falls back to the next candidate.
    r._endpoints[EP0].inflight = 10
    _, avail = r._candidates(key)
    assert avail[0] == EP1
    # Browned-out target is treated as hot even when idle.
    r._endpoints[EP0].inflight = 0
    r._endpoints[EP0].brownout_level = 1
    _, avail = r._candidates(key)
    assert avail[0] == EP1


# ---------------------------------------------------------------------------
# retries / health / backpressure / hedging (stubbed transport)
# ---------------------------------------------------------------------------

def test_failover_ejection_half_open_readmission():
    r = Router([EP0, EP1], config=_fast_config())
    behavior = {EP0: ConnectionError("down"), EP1: (200, {}, _OK_BODY)}
    calls = _stub(r, behavior)
    body = _body(_key_for(r, EP0, want_second=EP1))
    # Two failed attempts at EP0 (eject_failures=2) → ejected; both
    # requests still answer from EP1 (zero lost).
    for _ in range(2):
        status, _, out = r.handle(body, {})
        assert status == 200 and out == _OK_BODY
    snap = r.metrics.snapshot()
    assert snap["ejections"] == 1 and snap["retries"] >= 2
    assert not r._endpoints[EP0].admitted
    # While ejected (inside the probe window) EP0 is never routed to.
    calls.clear()
    status, _, _ = r.handle(body, {})
    assert status == 200 and EP0 not in calls
    # Probe window opens, the endpoint recovers: one half-open probe
    # readmits it.
    behavior[EP0] = (200, {}, _OK_BODY)
    time.sleep(r.config.probe_s + 0.01)
    status, _, _ = r.handle(body, {})
    assert status == 200
    snap = r.metrics.snapshot()
    assert snap["readmissions"] == 1
    assert r._endpoints[EP0].admitted


def test_retry_exhaustion_returns_502():
    r = Router([EP0, EP1], config=_fast_config(retry_max=3))
    _stub(r, {EP0: ConnectionError("x"), EP1: ConnectionError("x")})
    status, _, body = r.handle(_body([1, 2, 3], timeout_s=5.0), {})
    assert status == 502
    assert b"forward attempt(s) failed" in body
    assert r.metrics.snapshot()["requests"]["error"] == 1


def test_budget_exhaustion_returns_504_with_deadline_header():
    r = Router([EP0, EP1],
               config=_fast_config(retry_max=1000, retry_base_s=0.02,
                                   retry_cap_s=0.02,
                                   eject_failures=1000))
    _stub(r, {EP0: ConnectionError("x"), EP1: ConnectionError("x")})
    t0 = time.monotonic()
    status, headers, _ = r.handle(
        _body([1, 2, 3]), {"X-Request-Timeout-S": "0.15"})
    assert status == 504
    assert time.monotonic() - t0 < 2.0  # bounded by the budget, not retries
    assert dict(headers).get("X-Deadline-Remaining-S") is not None
    assert r.metrics.snapshot()["requests"]["expired"] == 1


def test_503_passthrough_clamps_retry_after_to_budget():
    r = Router([EP0, EP1], config=_fast_config(retry_max=2))
    shed = (503, {"Retry-After": "60"}, b'{"error": "shed"}')
    _stub(r, {EP0: shed, EP1: shed})
    status, headers, _ = r.handle(
        _body([1, 2, 3]), {"X-Request-Timeout-S": "1.0"})
    assert status == 503
    ra = dict(headers).get("Retry-After")
    # The backend advertised 60s; the client only has ~1s — a compliant
    # client must never be told to sleep its whole budget away.
    assert ra is not None and float(ra) <= 1.0
    # Backpressure is not failure: nobody got ejected.
    assert r.metrics.snapshot()["ejections"] == 0


def test_hedging_beats_slow_primary():
    r = Router([EP0, EP1], config=_fast_config(hedge_s=0.02))
    slow_body = json.dumps({"tokens": [9, 9, 9]}).encode()

    def slow():
        time.sleep(0.3)
        return 200, {}, slow_body

    _stub(r, {EP0: slow, EP1: (200, {}, _OK_BODY)})
    body = _body(_key_for(r, EP0, want_second=EP1))
    t0 = time.monotonic()
    status, _, out = r.handle(body, {})
    dt = time.monotonic() - t0
    assert status == 200 and out == _OK_BODY  # the hedge's answer
    assert dt < 0.3  # did not wait for the slow primary
    snap = r.metrics.snapshot()
    assert snap["hedges"] == 1 and snap["hedges_won"] == 1


def test_no_candidate_waits_for_probe_window_instead_of_shedding():
    """Zero-lost discipline: a fully-ejected fleet is transient — when
    the client budget covers the next half-open window, the router waits
    and retries instead of shedding."""
    r = Router([EP0], config=_fast_config(eject_failures=1, retry_max=50))
    flips = {"n": 0}

    def flaky():
        flips["n"] += 1
        if flips["n"] <= 1:
            return ConnectionError("first attempt dies")
        return 200, {}, _OK_BODY

    _stub(r, {EP0: flaky})
    status, _, out = r.handle(
        _body([1, 2, 3]), {"X-Request-Timeout-S": "5"})
    assert status == 200 and out == _OK_BODY
    snap = r.metrics.snapshot()
    assert snap["ejections"] == 1 and snap["readmissions"] == 1


# ---------------------------------------------------------------------------
# faultline at router.forward
# ---------------------------------------------------------------------------

def test_faultline_drop_and_slow_route():
    r = Router([EP0, EP1], config=_fast_config(eject_failures=5))
    calls = _stub(r, {EP0: (200, {}, _OK_BODY), EP1: (200, {}, _OK_BODY)})
    body = _body(_key_for(r, EP0, want_second=EP1))
    plan = fl.install(fl.parse_plan(
        f"drop-route:{EP0}@0*1/router.forward,"
        f"slow-route:{EP1}@0*1~0.1/router.forward"))
    try:
        t0 = time.monotonic()
        status, _, _ = r.handle(body, {})
        dt = time.monotonic() - t0
    finally:
        fl.uninstall()
    # The drop killed the EP0 attempt before transport; the failover to
    # EP1 ate the slow-route stall; the request still answered.
    assert status == 200
    assert calls == [EP1]
    assert dt >= 0.1
    assert [e["kind"] for e in plan.log] == ["drop-route", "slow-route"]
    assert r.metrics.snapshot()["retries"] == 1


def test_faultline_blackhole_endpoint():
    r = Router([EP0, EP1], config=_fast_config(eject_failures=5))
    calls = _stub(r, {EP0: (200, {}, _OK_BODY), EP1: (200, {}, _OK_BODY)})
    body = _body(_key_for(r, EP0, want_second=EP1))
    fl.install(fl.parse_plan(
        f"blackhole-endpoint:{EP0}@0*1~0.2/router.forward"))
    try:
        status, _, _ = r.handle(body, {})
    finally:
        fl.uninstall()
    # The blackhole gate fires before the transport: EP0 is never
    # actually contacted, and the request fails over.
    assert status == 200 and calls == [EP1]
    assert r._endpoints[EP0].blackholed_until > time.monotonic() - 0.2


def test_faultline_kill_rank_with_scale_down_reconciles():
    """ISSUE 18 chaos satellite: kill-rank at router.forward concurrent
    with a backend scale-down — the drained replica is never routed to
    while ejected, and the router's ejection/readmission counters
    reconcile with the scheduler's ``replica_events``."""
    adapter = _mlp_adapter()
    sched = build_replicas(lambda: adapter, num_replicas=2,
                           metrics=ServeMetrics())
    r = Router([EP0, EP1], config=_fast_config())
    behavior = {EP0: (200, {}, _OK_BODY), EP1: (200, {}, _OK_BODY)}
    calls = _stub(r, behavior)
    body = _body(_key_for(r, EP0, want_second=EP1))
    # The backend control plane scales replica-0 out...
    sched.mark_dead("replica-0", reason="scale-down")
    # ...while the router independently detects the loss at forward time.
    fl.install(fl.parse_plan(f"kill-rank:{EP0}@0*1/router.forward"))
    try:
        status, _, _ = r.handle(body, {})
    finally:
        fl.uninstall()
    assert status == 200  # failover absorbed the kill
    assert not r._endpoints[EP0].admitted
    # While ejected, EP0 receives no traffic at all.
    calls.clear()
    status, _, _ = r.handle(body, {})
    assert status == 200 and EP0 not in calls
    # Recovery on both planes: scheduler readmits the replica, the
    # router's half-open probe readmits the endpoint.
    sched.mark_alive("replica-0", reason="scale-up")
    time.sleep(r.config.probe_s + 0.01)
    status, _, _ = r.handle(body, {})
    assert status == 200
    rsnap = r.metrics.snapshot()
    events = sched.metrics.snapshot()["replica_events"]
    assert rsnap["ejections"] == events["mark_dead"] == 1
    assert rsnap["readmissions"] == events["mark_alive"] == 1


# ---------------------------------------------------------------------------
# drain satellites (real HTTP listeners)
# ---------------------------------------------------------------------------

class _SlowPrefillAdapter(MLPAdapter):
    """Holds each request in flight long enough for the drain tests to
    observe it."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.entered = threading.Event()    # a request is in its prefill

    def prefill_chunk(self, cache, chunks, starts, tables):
        self.entered.set()
        time.sleep(0.4)
        return super().prefill_chunk(cache, chunks, starts, tables)


def _post(port, payload, headers=(), timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(payload).encode(),
        headers=dict({"Content-Type": "application/json"}, **dict(headers)))
    return urllib.request.urlopen(req, timeout=timeout)


def test_serve_server_drains_gracefully():
    """ISSUE 18 satellite: SIGTERM-path drain — in-flight requests
    finish, new ones are refused with 503 + ``Connection: close`` and a
    header-budget-clamped Retry-After, and ``drain()`` reports a clean
    exit."""
    mlp = create_mlp(features=(16, VOCAB))
    params = mlp.init(jax.random.PRNGKey(3),
                      np.zeros((1, VOCAB), np.float32))["params"]
    adapter = _SlowPrefillAdapter(mlp, params, vocab_size=VOCAB,
                                  max_len=128)
    sched = build_replicas(lambda: adapter, num_replicas=1,
                           metrics=ServeMetrics())
    server = ServeServer(sched)
    port = server.start(port=0, host="127.0.0.1")
    results = {}

    def inflight():
        with _post(port, {"tokens": [3, 1], "max_new_tokens": 2}) as resp:
            results["status"] = resp.status
            results["body"] = json.loads(resp.read())

    t = threading.Thread(target=inflight, daemon=True)
    t.start()
    # The request is inside the slow prefill (on a loaded machine a fixed
    # sleep let the drain begin before the request had arrived).
    assert adapter.entered.wait(10)
    server.httpd.begin_drain()
    # New work is refused — with the drain contract's exact headers,
    # Retry-After clamped by the header budget even though no Request
    # object was ever constructed (the clamp satellite).
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, {"tokens": [3, 1], "max_new_tokens": 2},
              headers={"X-Request-Timeout-S": "2"})
    assert e.value.code == 503
    assert e.value.headers.get("Connection") == "close"
    assert float(e.value.headers.get("Retry-After")) <= 2.0
    assert e.value.headers.get("X-Deadline-Remaining-S") is not None
    # The in-flight request still completes, then drain reports clean.
    assert server.drain(grace_s=10) is True
    t.join(timeout=10)
    assert results["status"] == 200
    assert results["body"]["tokens"]


def test_router_server_drain_refusal_clamps_retry_after():
    """Same drain contract one tier up: a draining hvdroute refuses with
    503 + ``Connection: close``, Retry-After clamped by the header
    budget, and counts the refusal."""
    r = Router([EP0], config=_fast_config(probe_s=30.0))
    server = RouterServer(r)
    port = server.start(port=0, host="127.0.0.1")
    try:
        server.httpd.begin_drain()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(port, {"tokens": [1, 2, 3]},
                  headers={"X-Request-Timeout-S": "2"})
        assert e.value.code == 503
        assert e.value.headers.get("Connection") == "close"
        # probe_s would hint 30s; the client only has 2.
        assert float(e.value.headers.get("Retry-After")) <= 2.0
        assert r.metrics.snapshot()["requests"]["refused"] == 1
        # /healthz keeps answering during drain and reports it.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            health = json.loads(resp.read())
        assert health["draining"] is True
    finally:
        server.stop()


def test_router_server_routes_and_exports_metrics():
    """End-to-end over real sockets: RouterServer → Router → a stubbed
    transport standing in for the backend fleet."""
    r = Router([EP0, EP1], config=_fast_config())
    _stub(r, {EP0: (200, {}, _OK_BODY), EP1: (200, {}, _OK_BODY)})
    server = RouterServer(r)
    port = server.start(port=0, host="127.0.0.1")
    try:
        with _post(port, {"tokens": [1, 2, 3],
                          "max_new_tokens": 2}) as resp:
            assert resp.status == 200
            assert json.loads(resp.read())["tokens"] == [1, 2, 3]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert 'hvd_route_requests_total{outcome="ok"} 1' in text
        assert "hvd_route_endpoint_admitted" in text
    finally:
        server.stop()
