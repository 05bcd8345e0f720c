"""ISSUE 19: hvdstream — token streaming over SSE/chunked transfer.

Pins the tentpole layer by layer:

* wire helpers — SSE encode/parse roundtrip, chunk framing, the
  ``stream`` opt-in (body flag / Accept header), error→status mapping;
* TokenStream — position-keyed dedupe (failover replay invisible),
  bounded-queue coalescing that never drops, first-terminal-wins;
* HTTP server — streamed == buffered bit-exactness across pow2 prompt
  buckets (greedy AND sampled), mid-stream deadline expiry as a
  terminal ``error`` event, client disconnect aborting the sequence in
  the engine (``client_gone`` counted, slot freed);
* faultline — the new ``stream-disconnect`` / ``slow-client`` kinds at
  the ``stream.emit`` point;
* root span — every POST outcome (buffered, streamed, 404, drain
  refusal) emits exactly one ``http-handle`` root span with its final
  status (the ISSUE 19 bugfix satellite);
* router — SSE pass-through without buffering, pre-first-byte failover
  preserved, post-first-byte failure surfacing as a terminal error
  event (never a silent retry), hedging claimed at first byte with the
  loser closing its own connection;
* controller — the env-gated TTFT windowed-p99 pressure term;
* soak (``slow``) — a 4-replica streamed storm with a replica killed
  mid-stream: every client sees its exact sequence once (zero lost,
  zero duplicated tokens).
"""

import http.client
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
from horovod_tpu.obs import merge as mg
from horovod_tpu.obs import tracing as tr
from horovod_tpu.serve import (ControllerConfig, ControllerState,
                               DeadlineExceededError, FleetSnapshot,
                               InferenceEngine, MLPAdapter, QueueFullError,
                               Replica, ReplicaScheduler, Request, Router,
                               RouterConfig, ServeMetrics, ServeServer)
from horovod_tpu.serve.controller import decide
from horovod_tpu.serve.streaming import (CHUNK_TERMINATOR, TokenStream,
                                         chunk_frame, encode_sse,
                                         error_status_for, parse_sse,
                                         wants_stream)
from horovod_tpu.models import create_mlp

VOCAB = 31


@pytest.fixture(autouse=True)
def _clean_world():
    """No leaked faultline plan or tracer across tests (the faultline /
    obs suites' discipline)."""
    fl.uninstall()
    tr.uninstall()
    yield
    fl.uninstall()
    tr.uninstall()


# -- shared harness ----------------------------------------------------------

def _mlp_adapter(seed=3, vocab=VOCAB, max_len=512):
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return MLPAdapter(mlp, params, vocab_size=vocab, max_len=max_len)


def _mlp_chain(adapter, prompt, n):
    """Ground truth for the MLP Markov chain (greedy)."""
    seq, tok = [], prompt[-1]
    for _ in range(n):
        tok = int(adapter._apply(np.asarray([tok], np.int32))[0])
        seq.append(tok)
    return seq


class _SlowMLP(MLPAdapter):
    """Visible per-decode-step cost so a stream stays open long enough
    to fault (deadline expiry, disconnect, kill) deterministically."""

    delay_s = 0.02

    def decode_paged(self, cache, tokens, positions, tables):
        time.sleep(self.delay_s)
        return super().decode_paged(cache, tokens, positions, tables)


def _slow_adapter(seed=3, vocab=VOCAB):
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return _SlowMLP(mlp, params, vocab_size=vocab, max_len=512)


def _fleet_server(adapter_fn=_mlp_adapter, n=1, request_timeout_s=60,
                  **engine_kw):
    engine_kw.setdefault("max_batch", 4)
    replicas = [Replica(f"replica-{i}", None,
                        InferenceEngine(adapter_fn(),
                                        metrics=ServeMetrics(),
                                        replica_id=f"replica-{i}",
                                        **engine_kw))
                for i in range(n)]
    sched = ReplicaScheduler(replicas, metrics=replicas[0].engine.metrics)
    server = ServeServer(sched, request_timeout_s=request_timeout_s)
    port = server.start(port=0, host="127.0.0.1")
    return server, sched, port


def _post(port, payload, headers=(), path="/generate", timeout=30):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method="POST",
        headers=dict({"Content-Type": "application/json"},
                     **dict(headers)))
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _stream_post(port, payload, headers=(), hangup_after=None, timeout=30):
    """POST /generate and consume the SSE stream incrementally.

    Returns ``(status, resp_headers, buffered_body_or_None, events)``;
    a non-stream answer (pre-first-byte shed/400) comes back buffered
    with ``events is None``.  ``hangup_after=k`` slams the connection
    shut after the k-th token event (the client-disconnect probe)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(payload).encode(),
                     headers=dict({"Content-Type": "application/json"},
                                  **dict(headers)))
        resp = conn.getresponse()
        ctype = resp.getheader("Content-Type") or ""
        if resp.status != 200 or "text/event-stream" not in ctype:
            data = resp.read()
            return (resp.status, dict(resp.getheaders()),
                    json.loads(data or b"{}"), None)
        raw = b""
        events = []
        while True:
            try:
                chunk = resp.read1(8192)
            except (http.client.HTTPException, OSError):
                break  # server hung up mid-stream (faultline disconnect)
            if not chunk:
                break
            raw += chunk
            # Parse only COMPLETE events: read1 can fragment below the
            # SSE block boundary.
            cut = raw.rfind(b"\n\n")
            events = parse_sse(raw[:cut + 2]) if cut >= 0 else []
            ntok = sum(1 for e in events if e[0] == "token")
            if hangup_after is not None and ntok >= hangup_after:
                resp.close()  # drops the last socket ref: FIN to server
                return resp.status, dict(resp.getheaders()), None, events
            if events and events[-1][0] in ("done", "error"):
                break
        return resp.status, dict(resp.getheaders()), None, events
    finally:
        conn.close()


def _stream_tokens(events):
    return [t for e in events if e[0] == "token" for t in e[1]["tokens"]]


# ---------------------------------------------------------------------------
# wire helpers
# ---------------------------------------------------------------------------

def test_sse_roundtrip_and_chunk_framing():
    evs = [("token", {"index": 0, "tokens": [5, 7]}),
           ("done", {"request_id": "r-1", "usage": {"total_tokens": 9}})]
    raw = b"".join(encode_sse(k, d) for k, d in evs)
    assert parse_sse(raw) == evs
    framed = chunk_frame(b"hello")
    assert framed == b"5\r\nhello\r\n"
    assert CHUNK_TERMINATOR == b"0\r\n\r\n"
    # Frame length is hex.
    assert chunk_frame(b"x" * 26).startswith(b"1a\r\n")


def test_wants_stream_body_flag_and_accept_header():
    assert wants_stream({"stream": True}, {})
    assert not wants_stream({"stream": False}, {})
    assert not wants_stream({}, {})
    assert wants_stream({}, {"Accept": "text/event-stream"})
    assert not wants_stream({}, {"Accept": "application/json"})


def test_error_status_mapping_mirrors_buffered_path():
    from horovod_tpu.serve import NoHealthyReplicaError
    assert error_status_for(QueueFullError("full")) == 503
    assert error_status_for(NoHealthyReplicaError("none")) == 503
    assert error_status_for(DeadlineExceededError("late")) == 504
    assert error_status_for(TimeoutError("cap")) == 504
    assert error_status_for(ValueError("bad")) == 400
    assert error_status_for(RuntimeError("boom")) == 500


# ---------------------------------------------------------------------------
# TokenStream
# ---------------------------------------------------------------------------

def test_token_stream_delivers_in_order_and_finish_flushes_tail():
    s = TokenStream(maxlen=64)
    s.publish(0, 11)
    s.publish(1, 12)
    # finish() flushes the unpublished tail (positions 2, 3) before the
    # terminal — concatenated == buffered is structural, not a race.
    s.finish([11, 12, 13, 14])
    got, events = [], []
    while True:
        ev = s.next_event(timeout=1.0)
        events.append(ev)
        if ev[0] != "token":
            break
        got.extend(ev[1]["tokens"])
    assert got == [11, 12, 13, 14]
    assert events[-1] == ("done", None)
    assert s.counters() == {"published": 4, "coalesced": 0,
                            "duplicates": 0}
    # The terminal is sticky: consumers that poll again still see it.
    assert s.next_event(timeout=0.1) == ("done", None)


def test_token_stream_dedupes_failover_replay():
    s = TokenStream(maxlen=64)
    for pos, tok in enumerate([4, 5, 6]):
        s.publish(pos, tok)
    # Failover replay: the survivor re-decodes from position 0 and
    # re-publishes the same (seeded-identical) tokens.
    for pos, tok in enumerate([4, 5, 6, 7]):
        s.publish(pos, tok)
    s.finish([4, 5, 6, 7])
    got = []
    while True:
        ev = s.next_event(timeout=1.0)
        if ev[0] != "token":
            break
        got.extend(ev[1]["tokens"])
    assert got == [4, 5, 6, 7]  # exactly once, no gap, no duplicate
    assert s.counters()["duplicates"] == 3
    assert s.counters()["published"] == 4


def test_token_stream_bounded_queue_coalesces_never_drops():
    s = TokenStream(maxlen=2)
    toks = list(range(10, 20))
    for pos, tok in enumerate(toks):
        s.publish(pos, tok)
    # Nothing consumed: the queue held at most maxlen events by
    # coalescing into the newest — and no token was lost.
    assert s.counters()["coalesced"] == len(toks) - 2
    s.finish(toks)
    got, n_events = [], 0
    while True:
        ev = s.next_event(timeout=1.0)
        if ev[0] != "token":
            break
        n_events += 1
        got.extend(ev[1]["tokens"])
    assert got == toks
    assert n_events == 2


def test_token_stream_first_terminal_wins_and_abort_is_idempotent():
    s = TokenStream(maxlen=4)
    s.publish(0, 1)
    exc = DeadlineExceededError("expired mid-stream")
    s.abort(exc)
    s.abort(RuntimeError("second terminal must lose"))
    s.finish([1, 2, 3])  # post-abort finish must not override
    assert s.next_event(timeout=1.0) == ("token", {"index": 0,
                                                  "tokens": [1]})
    kind, err = s.next_event(timeout=1.0)
    assert kind == "error" and err is exc
    # Post-terminal publishes are dropped outright.
    s.publish(5, 9)
    assert s.next_event(timeout=0.1) == ("error", exc)


def test_token_stream_next_event_times_out_empty():
    s = TokenStream(maxlen=4)
    t0 = time.monotonic()
    assert s.next_event(timeout=0.05) is None
    assert time.monotonic() - t0 < 1.0


def test_token_stream_logprobs_ride_token_events():
    s = TokenStream(maxlen=64, logprobs=True)
    s.publish(0, 3, {"token": 3, "logprob": -0.5})
    s.finish([3], [{"token": 3, "logprob": -0.5}])
    kind, data = s.next_event(timeout=1.0)
    assert kind == "token"
    assert data["logprobs"] == [{"token": 3, "logprob": -0.5}]


# ---------------------------------------------------------------------------
# HTTP: streamed == buffered
# ---------------------------------------------------------------------------

def test_stream_matches_buffered_across_pow2_buckets():
    server, sched, port = _fleet_server()
    ad = sched.replicas[0].engine.adapter
    try:
        # Prompt lengths straddling pow2 bucket edges, greedy.
        for plen in (3, 8, 9, 17):
            prompt = [(5 * plen + j) % VOCAB for j in range(plen)]
            payload = {"tokens": prompt, "max_new_tokens": 12}
            _, buffered = _post(port, payload)
            status, _, _, events = _stream_post(
                port, dict(payload, stream=True))
            assert status == 200
            assert events[-1][0] == "done"
            assert _stream_tokens(events) == buffered["tokens"]
            assert buffered["tokens"] == _mlp_chain(ad, prompt, 12)
            # The done event carries the buffered body's outcome fields
            # verbatim (one builder) plus the stream counters.
            done = events[-1][1]
            for key in ("request_id", "finish_reason", "usage", "seed",
                        "qos", "tenant"):
                assert key in done, key
            assert done["usage"] == buffered["usage"]
            assert done["stream"]["published"] == len(buffered["tokens"])
            assert done["stream"]["duplicates"] == 0
        # Sampled: same seed -> streamed tokens == buffered tokens.
        sampled = {"tokens": [1, 2, 3], "max_new_tokens": 10,
                   "temperature": 0.8, "seed": 123}
        _, buf = _post(port, sampled)
        _, _, _, events = _stream_post(port, dict(sampled, stream=True))
        assert _stream_tokens(events) == buf["tokens"]
        assert events[-1][1]["seed"] == buf["seed"] == 123
    finally:
        server.stop()


def test_accept_header_opts_into_streaming_without_body_flag():
    server, _, port = _fleet_server()
    try:
        status, hdrs, _, events = _stream_post(
            port, {"tokens": [1, 2, 3], "max_new_tokens": 4},
            headers=[("Accept", "text/event-stream")])
        assert status == 200
        assert "text/event-stream" in hdrs.get("Content-Type", "")
        assert events[-1][0] == "done"
        assert len(_stream_tokens(events)) == 4
    finally:
        server.stop()


def test_stream_pre_first_byte_error_answers_buffered_400():
    server, _, port = _fleet_server()
    try:
        # schema without eos_id: rejected before admission — the client
        # sees an ordinary buffered 400, not a broken stream.
        status, _, body, events = _stream_post(
            port, {"tokens": [1], "stream": True,
                   "schema": {"type": "boolean"}})
        assert status == 400 and events is None
        assert "eos_id" in body["error"]
    finally:
        server.stop()


def test_mid_stream_deadline_ends_with_terminal_504_error_event():
    server, sched, port = _fleet_server(_slow_adapter)
    eng = sched.replicas[0].engine
    try:
        status, _, _, events = _stream_post(
            port, {"tokens": [1, 2], "max_new_tokens": 400,
                   "timeout_s": 0.5, "stream": True}, timeout=30)
        assert status == 200  # headers were sent before the expiry
        kinds = [e[0] for e in events]
        assert kinds.count("token") >= 1, events
        assert kinds[-1] == "error"
        err = events[-1][1]
        assert err["code"] == 504
        assert "expired" in err["error"] or "deadline" in err["error"]
        # The engine reaped the sequence (slot freed).
        deadline = time.monotonic() + 10
        while eng.active_count and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.active_count == 0
    finally:
        server.stop()


def test_client_disconnect_aborts_sequence_and_counts_client_gone():
    server, sched, port = _fleet_server(_slow_adapter)
    eng = sched.replicas[0].engine
    try:
        status, _, _, events = _stream_post(
            port, {"tokens": [3, 4], "max_new_tokens": 400,
                   "stream": True}, hangup_after=1)
        assert status == 200
        assert len(_stream_tokens(events)) >= 1
        # The engine observes the hangup at its next write and reaps
        # the still-decoding sequence; the outcome is client_gone.
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if (eng.active_count == 0 and eng.metrics.snapshot()
                    ["requests"].get("client_gone", 0) >= 1):
                break
            time.sleep(0.02)
        assert eng.active_count == 0
        assert eng.metrics.snapshot()["requests"]["client_gone"] >= 1
        assert eng.kv_stats()["used"] == 0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# faultline: stream-disconnect / slow-client at stream.emit
# ---------------------------------------------------------------------------

def test_faultline_stream_kinds_parse_with_default_point():
    plan = fl.parse_plan("stream-disconnect@3,slow-client*5~0.04", seed=9)
    disc, slow = plan.specs
    assert disc.kind == "stream-disconnect"
    assert disc.point == "stream.emit" and disc.step == 3
    assert slow.kind == "slow-client" and slow.point == "stream.emit"
    assert slow.repeat == 5 and slow.param == pytest.approx(0.04)
    # Round-trips through the spec grammar used by HVD_FAULTLINE_PLAN.
    assert fl.parse_plan("stream-disconnect/stream.emit",
                         seed=1).specs[0].point == "stream.emit"


def test_faultline_stream_disconnect_reaps_sequence():
    server, sched, port = _fleet_server(_slow_adapter)
    eng = sched.replicas[0].engine
    fl.install(fl.FaultPlan(
        [fl.FaultSpec("stream-disconnect", step=2)], seed=1))
    try:
        status, _, _, events = _stream_post(
            port, {"tokens": [5, 6], "max_new_tokens": 400,
                   "stream": True}, timeout=30)
        # The injected BrokenPipeError truncates the stream: no
        # terminal event reached the client.
        assert status == 200
        assert [e[0] for e in events].count("token") >= 1
        assert not events or events[-1][0] == "token"
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if (eng.active_count == 0 and eng.metrics.snapshot()
                    ["requests"].get("client_gone", 0) >= 1):
                break
            time.sleep(0.02)
        assert eng.active_count == 0
        assert eng.metrics.snapshot()["requests"]["client_gone"] >= 1
    finally:
        server.stop()


def test_faultline_slow_client_coalesces_bounded_queue(monkeypatch):
    monkeypatch.setenv("HVD_SERVE_STREAM_QUEUE", "2")
    server, _, port = _fleet_server()
    try:
        payload = {"tokens": [7, 8], "max_new_tokens": 30}
        _, buffered = _post(port, payload)
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("slow-client", step=0, repeat=1000,
                          param=0.03)], seed=1))
        _, _, _, events = _stream_post(port, dict(payload, stream=True),
                                       timeout=60)
        assert events[-1][0] == "done"
        # Stalled handler + bounded queue: tokens coalesced into fewer,
        # fatter events — and the concatenation still matches buffered
        # bit-for-bit (never dropped).
        assert _stream_tokens(events) == buffered["tokens"]
        n_token_events = sum(1 for e in events if e[0] == "token")
        assert n_token_events < 30
        assert events[-1][1]["stream"]["coalesced"] > 0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# root span: every POST outcome emits exactly one http-handle root
# ---------------------------------------------------------------------------

def test_every_post_outcome_emits_one_http_handle_root_span(tmp_path):
    shard_dir = tmp_path / "shards"
    tr.install(tr.Tracer(sample=1.0, shard_dir=str(shard_dir)))
    server, _, port = _fleet_server()
    tids = {"buffered": "aaaaaaaaaaaaaa01", "streamed": "aaaaaaaaaaaaaa02",
            "notfound": "aaaaaaaaaaaaaa03", "drained": "aaaaaaaaaaaaaa04"}
    try:
        status, _ = _post(port, {"tokens": [1, 2], "max_new_tokens": 3},
                          headers=[("X-Trace-Id", tids["buffered"])])
        assert status == 200
        status, _, _, events = _stream_post(
            port, {"tokens": [1, 2], "max_new_tokens": 3, "stream": True},
            headers=[("X-Trace-Id", tids["streamed"])])
        assert status == 200 and events[-1][0] == "done"
        status, _ = _post(port, {"tokens": [1]}, path="/nope",
                          headers=[("X-Trace-Id", tids["notfound"])])
        assert status == 404
        # Drain refusal: the regression this pins — the refusal used to
        # answer before the span machinery and left traced sheds
        # rootless.
        server.httpd.begin_drain()
        status, _ = _post(port, {"tokens": [1]},
                          headers=[("X-Trace-Id", tids["drained"])])
        assert status == 503
    finally:
        server.stop()
    tr.uninstall()
    traces = mg.spans_by_trace(mg.load_shards(str(shard_dir)))
    expect = {"buffered": 200, "streamed": 200,
              "notfound": 404, "drained": 503}
    for label, want_status in expect.items():
        spans = [s for s in traces.get(tids[label], [])
                 if s["type"] == "span" and s["name"] == "http-handle"]
        assert len(spans) == 1, (label, spans)
        root = spans[0]
        assert root["args"]["status"] == want_status, label
    # The streamed request's root covers the route hop beneath it.
    streamed = [s for s in traces[tids["streamed"]]
                if s["type"] == "span"]
    assert any(s["name"] == "route" for s in streamed)


# ---------------------------------------------------------------------------
# router: SSE pass-through, first-byte hedging, terminal error events
# ---------------------------------------------------------------------------

EP0, EP1 = "10.0.0.1:8000", "10.0.0.2:8000"


class _FakeReader:
    """Stands in for router._StreamReader: canned chunks, optional
    mid-stream failure, close tracking with the on_close contract."""

    def __init__(self, chunks, fail_after=None):
        self.chunks = list(chunks)
        self.fail_after = fail_after
        self.reads = 0
        self.closed = False
        self.on_close = None

    def read1(self, n=8192):
        if self.fail_after is not None and self.reads >= self.fail_after:
            raise OSError("backend died mid-stream")
        self.reads += 1
        return self.chunks.pop(0) if self.chunks else b""

    def close(self):
        if self.closed:
            return
        self.closed = True
        if self.on_close is not None:
            self.on_close()


class _FakeClient:
    """Downstream side of Router.handle(stream=...): records frames;
    ``gone_after`` flips write() to False (client hangup)."""

    def __init__(self, gone_after=None):
        self.status = None
        self.headers = None
        self.frames = []
        self.terminated = 0
        self.gone_after = gone_after

    def begin(self, status, headers):
        self.status, self.headers = status, list(headers)

        def write(data):
            if data is None:
                self.terminated += 1
                return True
            if self.gone_after is not None \
                    and len(self.frames) >= self.gone_after:
                return False
            self.frames.append(data)
            return True
        return write


def _fast_config(**overrides):
    base = dict(retry_base_s=0.001, retry_cap_s=0.005, probe_s=0.05,
                eject_failures=2, block_tokens=4)
    base.update(overrides)
    return RouterConfig(**base)


def _stub_stream(router, behavior, calls=None):
    """Replace the STREAMING transport seam; behavior[name] is a
    4-tuple, an Exception, or a callable returning either."""
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

    router._transport_stream = transport
    return calls


def _key_for(router, target, want_second=None):
    for s in range(4096):
        p = [(7 * s + j) % VOCAB for j in range(12)]
        order = router._ring.lookup(router.affinity_key(p))
        if order[0] == target and \
                (want_second is None or order[1] == want_second):
            return p
    raise AssertionError(f"no prompt routes to {target}")


def _sse_chunks(tokens):
    frames = [encode_sse("token", {"index": i, "tokens": [t]})
              for i, t in enumerate(tokens)]
    frames.append(encode_sse("done", {"request_id": "r-1",
                                      "finish_reason": "length"}))
    return frames


def _stream_body(tokens):
    return json.dumps({"tokens": tokens, "stream": True,
                       "max_new_tokens": 4}).encode()


def test_router_stream_passthrough_pipes_without_buffering():
    router = Router([EP0, EP1], config=_fast_config())
    prompt = _key_for(router, EP0)
    chunks = _sse_chunks([9, 8, 7])
    reader = _FakeReader(chunks)
    calls = _stub_stream(router, {
        EP0: (200, {"Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                    "X-Backend-Secret": "must-not-forward"},
              None, reader)})
    client = _FakeClient()
    out = router.handle(_stream_body(prompt), {}, stream=client.begin)
    assert out == (200, None, None)  # body already piped
    assert calls == [EP0]
    assert client.status == 200
    assert b"".join(client.frames) == b"".join(chunks)
    assert client.terminated == 1  # exactly one end-of-body
    # Hop-by-hop / backend-internal headers are not forwarded.
    names = [k.lower() for k, _ in client.headers]
    assert "x-backend-secret" not in names
    assert "content-type" in names
    # The reader was closed and the inflight gauge released.
    assert reader.closed
    assert router._endpoints[EP0].inflight == 0
    assert router.metrics.snapshot()["requests"]["ok"] == 1


def test_router_stream_post_first_byte_failure_is_terminal_not_retried():
    router = Router([EP0, EP1], config=_fast_config())
    prompt = _key_for(router, EP0, want_second=EP1)
    first = encode_sse("token", {"index": 0, "tokens": [9]})
    reader = _FakeReader([first], fail_after=1)
    calls = _stub_stream(router, {
        EP0: (200, {"Content-Type": "text/event-stream"}, None, reader),
        EP1: (200, {"Content-Type": "text/event-stream"}, None,
              _FakeReader(_sse_chunks([1])))})
    client = _FakeClient()
    status, hdrs, body = router.handle(_stream_body(prompt), {},
                                       stream=client.begin)
    # The client already consumed EP0's first token — a silent retry on
    # EP1 would re-send it.  The failure surfaces as a terminal SSE
    # error event instead.
    assert calls == [EP0]
    assert status == 200 and hdrs is None and body is None
    events = parse_sse(b"".join(client.frames))
    assert events[0] == ("token", {"index": 0, "tokens": [9]})
    assert events[-1][0] == "error"
    assert events[-1][1]["code"] == 502
    assert EP0 in events[-1][1]["error"]
    assert reader.closed
    assert router.metrics.snapshot()["requests"]["error"] == 1


def test_router_stream_client_gone_closes_backend_connection():
    router = Router([EP0, EP1], config=_fast_config())
    prompt = _key_for(router, EP0)
    reader = _FakeReader(_sse_chunks([1, 2, 3]))
    _stub_stream(router, {
        EP0: (200, {"Content-Type": "text/event-stream"}, None, reader)})
    client = _FakeClient(gone_after=1)
    status, hdrs, body = router.handle(_stream_body(prompt), {},
                                       stream=client.begin)
    assert (status, hdrs, body) == (200, None, None)
    # Backend connection closed -> the engine there sees the hangup and
    # aborts the sequence; no terminator was written downstream.
    assert reader.closed
    assert client.terminated == 0
    assert router._endpoints[EP0].inflight == 0
    assert router.metrics.snapshot()["requests"]["client_gone"] == 1


def test_router_stream_pre_first_byte_failure_still_fails_over():
    router = Router([EP0, EP1], config=_fast_config())
    prompt = _key_for(router, EP0, want_second=EP1)
    chunks = _sse_chunks([4, 5])
    calls = _stub_stream(router, {
        EP0: ConnectionError("connect refused"),
        EP1: (200, {"Content-Type": "text/event-stream"}, None,
              _FakeReader(chunks))})
    client = _FakeClient()
    status, hdrs, body = router.handle(_stream_body(prompt), {},
                                       stream=client.begin)
    # Before the first byte the buffered retry/failover machinery is
    # intact: the stream is served whole from the next candidate.
    assert calls == [EP0, EP1]
    assert (status, hdrs, body) == (200, None, None)
    assert b"".join(client.frames) == b"".join(chunks)
    assert router.metrics.snapshot()["requests"]["ok"] == 1


def test_router_stream_non_sse_answer_passes_through_buffered():
    router = Router([EP0, EP1], config=_fast_config())
    prompt = _key_for(router, EP0)
    err = json.dumps({"error": "schema requires eos_id"}).encode()
    calls = _stub_stream(router, {
        EP0: (400, {"Content-Type": "application/json"}, err, None)})
    client = _FakeClient()
    status, hdrs, body = router.handle(_stream_body(prompt), {},
                                       stream=client.begin)
    # Backend declined to stream (400 before the first token): the
    # router answers buffered, exactly like the non-streamed path.
    assert calls == [EP0]  # definitive — no retry
    assert status == 400 and body == err
    assert client.status is None  # stream callback never engaged


def test_router_stream_hedge_winner_claimed_at_first_byte():
    router = Router([EP0, EP1], config=_fast_config(hedge_s=0.02))
    prompt = _key_for(router, EP0, want_second=EP1)
    loser = _FakeReader(_sse_chunks([1, 1, 1]))
    winner_chunks = _sse_chunks([2, 2])
    winner = _FakeReader(winner_chunks)

    def slow_primary():
        time.sleep(0.25)
        return (200, {"Content-Type": "text/event-stream"}, None, loser)

    calls = _stub_stream(router, {
        EP0: slow_primary,
        EP1: (200, {"Content-Type": "text/event-stream"}, None, winner)})
    client = _FakeClient()
    status, hdrs, body = router.handle(_stream_body(prompt), {},
                                       stream=client.begin)
    assert (status, hdrs, body) == (200, None, None)
    # The hedge fired and the secondary was claimed at headers-received
    # (before any body byte): the client sees ONE backend's stream.
    assert sorted(calls) == [EP0, EP1]
    assert b"".join(client.frames) == b"".join(winner_chunks)
    snap = router.metrics.snapshot()
    assert snap["hedges"] == 1 and snap["hedges_won"] == 1
    # The loser's attempt thread closes its own connection when the
    # slow response finally lands — its backend aborts the duplicate.
    deadline = time.monotonic() + 5
    while not loser.closed and time.monotonic() < deadline:
        time.sleep(0.01)
    assert loser.closed
    assert router._endpoints[EP0].inflight == 0
    assert router._endpoints[EP1].inflight == 0


def test_router_buffered_handle_unchanged_without_stream_callback():
    router = Router([EP0, EP1], config=_fast_config())
    prompt = _key_for(router, EP0)
    ok = json.dumps({"tokens": [1, 2]}).encode()

    def transport(host, port, method, path, body, headers, timeout_s):
        return 200, {"Content-Type": "application/json"}, ok

    router._transport = transport
    # A streamed payload with NO downstream stream callback (legacy
    # caller) takes the buffered path end to end.
    status, hdrs, body = router.handle(_stream_body(prompt), {})
    assert status == 200 and body == ok


# ---------------------------------------------------------------------------
# controller: TTFT windowed-p99 pressure term
# ---------------------------------------------------------------------------

def _ctl_cfg(**kw):
    base = dict(poll_s=0.1, min_replicas=1, max_replicas=8,
                queue_high=8.0, queue_low=1.0, up_polls=1, down_polls=4,
                up_cooldown_s=0.0, down_cooldown_s=0.0,
                brownout_polls=2, brownout_clear_polls=3)
    base.update(kw)
    return ControllerConfig(**base).validate()


def test_ttft_slo_breach_is_a_pressure_source():
    cfg = _ctl_cfg(ttft_slo_ms=250.0)
    state = ControllerState()
    snap = FleetSnapshot(healthy=2, spares=1, queued=0,
                         ttft_p99_ms=400.0)
    assert decide(cfg, state, snap, 0.0) == ["scale_up"]


def test_ttft_term_below_slo_or_unobserved_is_quiet():
    cfg = _ctl_cfg(ttft_slo_ms=250.0)
    for snap in (FleetSnapshot(healthy=2, spares=1, queued=0,
                               ttft_p99_ms=100.0),
                 FleetSnapshot(healthy=2, spares=1, queued=0,
                               ttft_p99_ms=None)):
        assert decide(cfg, ControllerState(), snap, 0.0) == []


def test_ttft_term_disabled_by_default():
    cfg = _ctl_cfg()  # ttft_slo_ms defaults to 0 = off
    snap = FleetSnapshot(healthy=2, spares=1, queued=0,
                         ttft_p99_ms=1e9)
    assert decide(cfg, ControllerState(), snap, 0.0) == []


def test_ttft_slo_from_env(monkeypatch):
    monkeypatch.setenv("HVD_SERVE_CTL_TTFT_SLO_MS", "325")
    assert ControllerConfig.from_env().ttft_slo_ms == 325.0
    monkeypatch.delenv("HVD_SERVE_CTL_TTFT_SLO_MS")
    assert ControllerConfig.from_env().ttft_slo_ms == 0.0


def test_serve_metrics_ttft_window_diffs_cleanly():
    m = ServeMetrics()
    for ms in (10, 20, 500):
        m.observe_ttft(ms)
    bounds, counts, total = m.ttft_window()
    # Cumulative histogram export, the windowed_p99 input shape.
    assert total == 3
    assert counts == sorted(counts) and counts[-1] == 3
    assert len(bounds) == len(counts)
    from horovod_tpu.serve.controller import windowed_p99
    p99 = windowed_p99(bounds, None, counts, 0, total)
    assert p99 is not None and p99 >= 20.0


# ---------------------------------------------------------------------------
# soak: streamed storm with a replica killed mid-stream (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_streamed_storm_kill_replica_zero_lost_or_duplicated_tokens():
    """4 replicas, 12 concurrent streamed sessions, one replica killed
    while its sequences are mid-stream.  Failover re-decodes from
    position 0 on a survivor; the sink's position dedupe makes the
    replay invisible — every client's concatenation equals the greedy
    ground truth exactly once."""
    n_sessions, new_tokens = 12, 60
    server, sched, port = _fleet_server(_slow_adapter, n=4, max_batch=4)
    ref = _mlp_adapter()  # same seed: the shared ground-truth chain
    prompts = [[(13 * s + j) % VOCAB for j in range(6 + s % 5)]
               for s in range(n_sessions)]
    results = [None] * n_sessions
    errors = []

    def run(i):
        try:
            results[i] = _stream_post(
                port, {"tokens": prompts[i], "max_new_tokens": new_tokens,
                       "stream": True}, timeout=120)
        except Exception as e:  # pragma: no cover - diagnostic
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_sessions)]
    try:
        for t in threads:
            t.start()
        # Kill a replica once it is actually decoding streams.
        victim = None
        deadline = time.monotonic() + 60
        while victim is None and time.monotonic() < deadline:
            for r in sched.replicas:
                if r.engine.active_count > 0:
                    victim = r
                    break
            time.sleep(0.005)
        assert victim is not None, "no replica ever got load"
        time.sleep(0.1)  # let some tokens flow first
        sched.mark_dead(victim.replica_id, "storm kill")
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        requeued = 0
        for i, res in enumerate(results):
            status, _, _, events = res
            assert status == 200, (i, res)
            assert events[-1][0] == "done", (i, events[-1])
            want = _mlp_chain(ref, prompts[i], new_tokens)
            assert _stream_tokens(events) == want, i  # exactly once
            done = events[-1][1]
            if done["requeues"] > 0:
                requeued += 1
                # The replayed prefix was deduped, not re-delivered.
                assert done["stream"]["published"] == new_tokens
        assert requeued > 0, "kill landed after every stream finished"
    finally:
        server.stop()
