"""serve/ end-to-end acceptance (ISSUE 4): the HTTP server over a
multi-replica process-set world, on CPU, under real concurrent load.

Pins the three acceptance properties in one scenario:

(a) batched decode output EXACTLY matches single-request decode — greedy
    decoding over a masked cache is batch-composition-invariant
    (engine.py module doc), so 64 concurrent requests answer identically
    to the same prompts served alone;
(b) continuous batching actually batched: /metrics reports max batch
    occupancy > 1;
(c) losing one replica's rank mid-load (a preemption marker in the same
    rendezvous-KV ``preempt`` scope the elastic driver consumes) requeues
    only that replica's in-flight work onto survivors, every response
    stays correct, and /healthz flips to degraded.
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

import horovod_tpu as hvd
from horovod_tpu.elastic.preemption import PREEMPT_SCOPE
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.runner.http_server import KVStoreClient, KVStoreServer
from horovod_tpu.serve import ServeServer, TransformerAdapter, build_replicas

# Serialize with the other heavy e2e files (conftest loadgroup policy):
# this test runs 4 engines + an HTTP thread pool on the shared core.
pytestmark = pytest.mark.xdist_group("heavy_e2e")

CFG = TransformerConfig(vocab_size=89, num_layers=2, num_heads=2,
                        d_model=32, d_ff=64, max_len=96, causal=True,
                        dtype=jnp.float32, scan_layers=False)
NEW_TOKENS = 12
N_REQUESTS = 64


def _gen(port, prompt, n=NEW_TOKENS, timeout=120):
    body = json.dumps({"tokens": prompt, "max_new_tokens": n}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as resp:
        return resp.read().decode()


def _metric_value(text, name):
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{name} not in /metrics:\n{text}")


@pytest.mark.slow  # ~30s concurrent-load soak
def test_serving_e2e_concurrent_load_and_replica_loss(hvd8):
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sched = build_replicas(lambda: TransformerAdapter(CFG, params),
                           num_replicas=4, max_batch=4)
    assert [r.ranks for r in sched.replicas] == \
        [[0, 1], [2, 3], [4, 5], [6, 7]]  # process-set world, >= 2 replicas

    server = ServeServer(sched)
    port = server.start(port=0, host="127.0.0.1")
    kv = KVStoreServer()
    kv_port = kv.start(0)
    try:
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, CFG.vocab_size,
                               size=(int(rng.randint(3, 24)),)).tolist()
                   for _ in range(N_REQUESTS)]
        # (a) reference pass: every distinct prompt served ALONE (the
        # engine decodes it at occupancy 1).  Also warms every prefill
        # bucket so the storm below is steady-state.
        singles = [_gen(port, p)["tokens"] for p in prompts[:8]]
        for got, p in zip(singles, prompts[:8]):
            assert len(got) == NEW_TOKENS, (got, p)

        # Preemption watcher wired to the SAME KV scope the elastic
        # driver's PreemptionAwareDiscovery consumes.
        client = KVStoreClient("127.0.0.1", kv_port)
        victim = sched.replicas[0]
        host_ranks = {"preempt-host": list(victim.ranks)}
        sched.watch_preemption(client, host_ranks, poll_s=0.05)

        # The 64-request storm.
        results = [None] * N_REQUESTS
        errors = []

        def run(i):
            try:
                results[i] = _gen(port, prompts[i])
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:
            t.start()
        # (c) kill one replica's rank mid-load: wait until the victim
        # demonstrably has in-flight sequences, then publish the marker.
        deadline = time.monotonic() + 60
        while victim.engine.active_count == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert victim.engine.active_count > 0, "victim never got load"
        client.put(PREEMPT_SCOPE, "preempt-host",
                   b"TERMINATE_ON_HOST_MAINTENANCE")
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors

        # (a) exactness: batched == single for every request.  All 64
        # responses must match the occupancy-1 reference for their
        # prompt — including the requeued ones.
        expected = {tuple(p): s for p, s in zip(prompts[:8], singles)}
        for p, r in zip(prompts, results):
            key = tuple(p)
            if key not in expected:
                expected[key] = _gen(port, p)["tokens"]  # fresh reference
            assert r["tokens"] == expected[key], (p, r)

        # (c) only the dead replica's work moved, onto survivors.
        requeued = [r for r in results if r["requeues"] > 0]
        assert requeued, "no in-flight requests were requeued"
        assert all(r["replica"] != victim.replica_id for r in requeued)
        health = json.loads(_get(port, "/healthz"))
        assert health["status"] == "degraded"
        assert sum(1 for r in health["replicas"]
                   if r["state"] == "dead") == 1

        # (b) the engine really batched: occupancy > 1 observed.
        metrics_text = _get(port, "/metrics")
        assert _metric_value(metrics_text,
                             "hvd_serve_batch_occupancy_max") > 1
        requeued_total = _metric_value(
            metrics_text, 'hvd_serve_requests_total{outcome="requeued"}')
        assert requeued_total == len(requeued)
        assert _metric_value(metrics_text, "hvd_serve_tokens_total") >= \
            N_REQUESTS * NEW_TOKENS
        # Latency histograms populated (TTFT + per-token).
        assert _metric_value(metrics_text, "hvd_serve_ttft_ms_count") > 0
        assert _metric_value(metrics_text,
                             "hvd_serve_token_step_ms_count") > 0
    finally:
        server.stop()
        kv.stop()


@pytest.mark.integration
def test_hvdserve_cli_starts_and_answers(tmp_path):
    """The console entry (`python -m horovod_tpu.serve`, = the hvdserve
    script target) boots a replica world and answers /generate."""
    import os
    import re
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.serve", "--model", "mlp",
         "--replicas", "2", "--port", "0", "--vocab-size", "32"],
        cwd=repo, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        port = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            m = re.search(r"listening on :(\d+)", line or "")
            if m:
                port = int(m.group(1))
                break
        assert port, "hvdserve never reported its port"
        out = _gen(port, [3, 4], n=4)
        assert len(out["tokens"]) == 4
        health = json.loads(_get(port, "/healthz"))
        assert health["status"] == "ok" and health["total"] == 2
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_serving_http_surfaces(hvd8):
    """Status-code contract: 400 malformed, 404 unknown, 503 + Retry-After
    when unserving, /healthz 503 once every replica is dead."""
    model = Transformer(CFG)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sched = build_replicas(lambda: TransformerAdapter(CFG, params),
                           num_replicas=2, max_batch=2)
    server = ServeServer(sched)
    port = server.start(port=0, host="127.0.0.1")
    try:
        out = _gen(port, [1, 2, 3], n=2)
        assert len(out["tokens"]) == 2 and out["ttft_ms"] is not None

        with pytest.raises(urllib.error.HTTPError) as ei:
            _gen(port, [])
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/nosuch")
        assert ei.value.code == 404

        sched.mark_dead("replica-0")
        sched.mark_dead("replica-1")
        with pytest.raises(urllib.error.HTTPError) as ei:
            _gen(port, [1])
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") == "1"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "unserving"
    finally:
        server.stop()
