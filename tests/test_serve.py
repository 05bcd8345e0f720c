"""serve/ unit tests: batcher triggers/backpressure, engine continuous
batching + KV-cache exactness, replica routing/failover, metrics.

The e2e acceptance path (HTTP server over a multi-replica process-set
world, preemption-marker failover under concurrent load) lives in
tests/test_serve_e2e.py; this file pins each layer in isolation.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import create_mlp
from horovod_tpu.models.transformer import (Transformer, TransformerConfig,
                                            stack_block_params)
from horovod_tpu.serve import (DeadlineExceededError, DynamicBatcher,
                               Histogram, InferenceEngine, MLPAdapter,
                               ModelAdapter, NoHealthyReplicaError,
                               QueueFullError, Replica, ReplicaScheduler,
                               Request, ServeMetrics, TransformerAdapter,
                               prompt_bucket)

VOCAB = 31


# -- shared tiny models ------------------------------------------------------

def _mlp_adapter(seed=3, vocab=VOCAB, max_len=128, cls=MLPAdapter):
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return cls(mlp, params, vocab_size=vocab, max_len=max_len)


def _mlp_chain(adapter, prompt, n):
    """Ground truth for the MLP Markov chain."""
    seq = []
    tok = prompt[-1]
    for _ in range(n):
        tok = int(adapter._apply(np.asarray([tok], np.int32))[0])
        seq.append(tok)
    return seq


_TINY = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=64, causal=True,
                          dtype=jnp.float32, scan_layers=False)


def _tiny_transformer(seed=0):
    model = Transformer(_TINY)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


# -- batcher -----------------------------------------------------------------

def test_prompt_bucketing_pow2_with_floor_and_cap():
    assert prompt_bucket(1, floor=8) == 8
    assert prompt_bucket(8, floor=8) == 8
    assert prompt_bucket(9, floor=8) == 16
    assert prompt_bucket(100, floor=8, cap=64) == 64
    assert [prompt_bucket(n, floor=8) for n in (3, 8, 9, 30)] == \
        [8, 8, 16, 32]


def test_batcher_backpressure_sheds_at_capacity():
    b = DynamicBatcher(max_queue=2, max_wait_ms=1000)
    b.submit(Request([1]))
    b.submit(Request([2]))
    with pytest.raises(QueueFullError):
        b.submit(Request([3]))
    assert b.depth() == 2


def test_batcher_size_trigger_fires_immediately():
    b = DynamicBatcher(max_queue=16, max_wait_ms=10_000)
    for i in range(4):
        b.submit(Request([i + 1]))
    t0 = time.monotonic()
    got = b.get_admission(4, block_s=5.0)
    assert len(got) == 4
    assert time.monotonic() - t0 < 1.0  # did not wait out max_wait


def test_batcher_deadline_trigger_returns_partial_batch():
    b = DynamicBatcher(max_queue=16, max_wait_ms=30)
    b.submit(Request([1]))
    t0 = time.monotonic()
    got = b.get_admission(8, block_s=5.0)  # size trigger can't fire
    waited = time.monotonic() - t0
    assert [len(r.prompt) for r in got] == [1]
    assert 0.01 < waited < 2.0  # released by the deadline trigger


def test_batcher_expired_requests_are_shed_not_returned():
    shed = []
    b = DynamicBatcher(max_queue=16, max_wait_ms=1,
                       on_shed=lambda r, why: shed.append(why))
    r = Request([1], timeout_s=0.01)
    b.submit(r)
    time.sleep(0.05)
    assert b.get_admission(4, block_s=0.0) == []
    with pytest.raises(DeadlineExceededError):
        r.result(timeout=1)
    assert shed == ["expired"]


def test_batcher_requeue_front_bypasses_bound_and_orders_first():
    b = DynamicBatcher(max_queue=1, max_wait_ms=0)
    b.submit(Request([1]))
    drained = [Request([7]), Request([8])]
    b.requeue_front(drained)  # over capacity on purpose
    got = b.get_admission(3, block_s=0.0)
    assert [r.prompt for r in got] == [[7], [8], [1]]


# -- metrics -----------------------------------------------------------------

def test_histogram_quantiles_and_render():
    h = Histogram(buckets_ms=(1.0, 10.0, 100.0))
    for v in (0.5, 5, 5, 50):
        h.observe(v)
    assert h.count == 4 and h.quantile(0.5) == 10.0
    m = ServeMetrics()
    m.observe_ttft(12.0)
    m.observe_decode_step(3.0, occupancy=5, new_tokens=5)
    m.count_request("ok")
    text = m.render()
    assert "hvd_serve_ttft_ms_bucket" in text
    assert "hvd_serve_batch_occupancy_max 5" in text
    assert 'hvd_serve_requests_total{outcome="ok"} 1' in text
    snap = m.snapshot()
    # 5 decode-step tokens + the prefill's first token (observe_ttft).
    assert snap["tokens_total"] == 6 and snap["occupancy"]["max"] == 5


def test_metrics_timeline_counters(tmp_path):
    import json
    from horovod_tpu.timeline import Timeline
    path = str(tmp_path / "serve_trace.json")
    tl = Timeline(path)
    m = ServeMetrics()
    m.set_timeline(tl)
    m.observe_decode_step(2.0, occupancy=3, new_tokens=3)
    m.maybe_emit_timeline(force=True)
    tl.close()
    events = json.load(open(path))
    serve = [e for e in events if e.get("name", "").startswith("SERVE/")]
    assert serve and serve[0]["ph"] == "C"
    assert serve[0]["args"]["occupancy"] == 3
    assert serve[0]["args"]["tokens_total"] == 3


# -- engine (MLP adapter: pure mechanics) ------------------------------------

def test_engine_generate_matches_markov_chain():
    ad = _mlp_adapter()
    eng = InferenceEngine(ad, max_batch=4, replica_id="t").start()
    try:
        out = eng.generate([5, 9], max_new_tokens=10)
        assert out == _mlp_chain(ad, [5, 9], 10)
    finally:
        eng.stop()


def test_engine_eos_stops_generation():
    ad = _mlp_adapter()
    chain = _mlp_chain(ad, [5], 10)
    eos = chain[3]
    eng = InferenceEngine(ad, max_batch=2, replica_id="t").start()
    try:
        out = eng.generate([5], max_new_tokens=10, eos_id=eos)
        # Stops AT the eos token's first occurrence, inclusive (the chain
        # may visit it before step 3).
        assert out == chain[:chain.index(eos) + 1]
    finally:
        eng.stop()


def test_engine_batched_equals_single_and_occupancy_exceeds_one():
    ad = _mlp_adapter()
    eng = InferenceEngine(ad, max_batch=8, replica_id="t").start()
    try:
        prompts = [[(i * 7) % VOCAB or 1] for i in range(16)]
        singles = [eng.generate(p, max_new_tokens=12) for p in prompts]
        results = [None] * 16

        def run(i):
            results[i] = eng.generate(prompts[i], max_new_tokens=12)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == singles
        assert eng.metrics.snapshot()["occupancy"]["max"] > 1
    finally:
        eng.stop()


class _SlowAdapter(MLPAdapter):
    """MLP adapter whose decode steps take ~5 ms — keeps requests
    demonstrably in-flight for drain/failover tests."""

    delay_s = 0.005

    def decode_paged(self, cache, tokens, positions, tables):
        time.sleep(self.delay_s)
        return super().decode_paged(cache, tokens, positions, tables)


def test_engine_drain_returns_inflight_with_cleared_progress():
    ad = _mlp_adapter(cls=_SlowAdapter)
    eng = InferenceEngine(ad, max_batch=4, replica_id="t").start()
    reqs = [Request([3], max_new_tokens=120) for _ in range(3)]
    for r in reqs:
        eng.batcher.submit(r)
    deadline = time.monotonic() + 10
    while eng.active_count < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    drained = eng.drain()
    assert sorted(r.request_id for r in drained) == \
        sorted(r.request_id for r in reqs)
    for r in drained:
        assert r.generated == [] and r.requeues == 1 and not r.done
    assert eng.active_count == 0


def test_engine_survives_poisoned_batch():
    """An adapter exception mid-step must FAIL the in-flight requests
    with the real error (not hang them to client timeout) and leave the
    engine serving — one poisoned batch must not take the replica down."""

    class _PoisonOnce(_SlowAdapter):
        delay_s = 0.0
        armed = True

        def decode_paged(self, cache, tokens, positions, tables):
            if self.armed:
                self.armed = False
                raise RuntimeError("simulated device fault")
            return super().decode_paged(cache, tokens, positions, tables)

    ad = _mlp_adapter(cls=_PoisonOnce)
    eng = InferenceEngine(ad, max_batch=2, replica_id="t").start()
    try:
        doomed = Request([5], max_new_tokens=8)
        eng.batcher.submit(doomed)
        with pytest.raises(RuntimeError, match="simulated device fault"):
            doomed.result(timeout=30)
        # The loop recovered: a fresh request completes correctly.
        out = eng.generate([5], max_new_tokens=8)
        assert out == _mlp_chain(_mlp_adapter(), [5], 8)
        assert eng.metrics.snapshot()["requests"]["error"] == 1
    finally:
        eng.stop()


def test_engine_refuses_an_adapter_without_the_paged_trio():
    """No second cache layout to fall back to: the constructor names the
    members of the paged interface that are missing."""

    class _HalfAdapter(ModelAdapter):
        vocab_size, max_len = VOCAB, 16

        def init_paged_cache(self, num_blocks, max_batch):
            return ()

    with pytest.raises(TypeError) as e:
        InferenceEngine(_HalfAdapter(), max_batch=2)
    assert "_HalfAdapter" in str(e.value)
    assert "prefill_chunk, decode_paged" in str(e.value)
    assert "init_paged_cache" not in str(e.value)


@pytest.mark.parametrize("gone", ["kv_mode", "sp_ranks"])
def test_engine_has_no_cache_layout_or_emulated_rank_option(gone):
    with pytest.raises(TypeError, match=gone):
        InferenceEngine(_mlp_adapter(), max_batch=2, **{gone: None})


def test_engine_rejects_overlong_request():
    ad = _mlp_adapter(max_len=16)
    eng = InferenceEngine(ad, max_batch=2, replica_id="t").start()
    try:
        r = Request([1] * 10, max_new_tokens=10)  # 20 > max_len 16
        eng.batcher.submit(r)
        with pytest.raises(ValueError, match="exceeds max_len"):
            r.result(timeout=10)
    finally:
        eng.stop()


# -- transformer adapter -----------------------------------------------------

def test_transformer_prefill_matches_flax_apply():
    model, params = _tiny_transformer()
    ad = TransformerAdapter(_TINY, params, block_tokens=8)
    cache = ad.init_paged_cache(4, 4)
    tokens = np.random.RandomState(0).randint(0, 61, (1, 12))
    ref = model.apply({"params": params},
                      jnp.asarray(tokens, jnp.int32))  # [1, 12, V]
    cache, first = ad.prefill_chunk(cache, [tokens[0].tolist()], [0],
                                    [[2, 0]])
    assert int(first[0]) == int(jnp.argmax(ref[0, -1]))


def test_transformer_decode_matches_full_recompute_greedy():
    model, params = _tiny_transformer()

    def flax_greedy(prompt, n):
        seq = list(prompt)
        for _ in range(n):
            lg = model.apply({"params": params},
                             jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(lg[0, -1])))
        return seq[len(prompt):]

    eng = InferenceEngine(TransformerAdapter(_TINY, params),
                          max_batch=4, replica_id="t").start()
    try:
        for seed in (0, 1):
            prompt = np.random.RandomState(seed).randint(
                0, 61, (5 + seed * 7,)).tolist()
            assert eng.generate(prompt, max_new_tokens=6) == \
                flax_greedy(prompt, 6)
    finally:
        eng.stop()


def test_transformer_adapter_accepts_scan_layers_checkpoints():
    """A scan_layers (stacked blocks/block) checkpoint is unstacked at
    load and decodes identically to the unrolled layout."""
    _, params = _tiny_transformer()
    stacked = stack_block_params(params, _TINY.num_layers)
    e1 = InferenceEngine(TransformerAdapter(_TINY, params),
                         max_batch=2, replica_id="a").start()
    e2 = InferenceEngine(TransformerAdapter(_TINY, stacked),
                         max_batch=2, replica_id="b").start()
    try:
        prompt = [3, 17, 42, 9]
        assert e1.generate(prompt, max_new_tokens=5) == \
            e2.generate(prompt, max_new_tokens=5)
    finally:
        e1.stop()
        e2.stop()


def test_transformer_adapter_rejects_training_mesh_configs():
    import dataclasses
    _, params = _tiny_transformer()
    with pytest.raises(ValueError, match="data-parallel"):
        TransformerAdapter(dataclasses.replace(_TINY, seq_parallel="ring"),
                           params)


def test_transformer_prefill_compile_cache_buckets():
    """Same-bucket shapes reuse the compiled prefill; only new (count,
    length) buckets compile — steady-state serving never recompiles.
    The third key is the pool's size, which the program bakes in."""
    _, params = _tiny_transformer()
    ad = TransformerAdapter(_TINY, params, block_tokens=16)
    cache = ad.init_paged_cache(8, 8)
    cache, _ = ad.prefill_chunk(cache, [[1, 2, 3]], [0], [[0]])
    assert set(ad._chunk_cache) == {(1, 8, 8)}
    cache, _ = ad.prefill_chunk(cache, [[4] * 7], [0], [[1]])  # same
    assert set(ad._chunk_cache) == {(1, 8, 8)}
    cache, _ = ad.prefill_chunk(cache, [[5] * 9], [0], [[2]])  # longer
    assert set(ad._chunk_cache) == {(1, 8, 8), (1, 16, 8)}
    cache, _ = ad.prefill_chunk(cache, [[6]] * 3, [0] * 3,
                                [[3], [4], [5]])  # wider count bucket
    assert set(ad._chunk_cache) == {(1, 8, 8), (1, 16, 8), (4, 8, 8)}


# -- process-set partitioning ------------------------------------------------

def test_partition_process_sets_even_and_ragged(hvd8):
    sets = hvd.partition_process_sets(4)
    assert [s.ranks for s in sets] == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert all(s.process_set_id is not None for s in sets)
    ragged = hvd.partition_process_sets(3)
    assert [s.ranks for s in ragged] == [[0, 1, 2], [3, 4, 5], [6, 7]]
    with pytest.raises(ValueError):
        hvd.partition_process_sets(9)
    with pytest.raises(ValueError):
        hvd.partition_process_sets(0)


# -- replica scheduler -------------------------------------------------------

def _two_replica_sched():
    replicas = []
    metrics = ServeMetrics()
    for i in range(2):
        eng = InferenceEngine(_mlp_adapter(), max_batch=4,
                              metrics=metrics, replica_id=f"replica-{i}")
        replicas.append(Replica(f"replica-{i}", None, eng))
    return ReplicaScheduler(replicas, metrics=metrics).start()


def test_scheduler_routes_least_loaded():
    sched = _two_replica_sched()
    try:
        # Saturate replica-0's queue by hand; new work must go to 1.
        sched.replicas[0].engine.stop()  # freeze so load stays put
        for _ in range(5):
            sched.replicas[0].engine.batcher.submit(
                Request([1], max_new_tokens=1))
        r = Request([2], max_new_tokens=1)
        target = sched.submit(r)
        assert target.replica_id == "replica-1"
        assert r.result(timeout=30) == _mlp_chain(_mlp_adapter(), [2], 1)
    finally:
        sched.stop()


def test_scheduler_mark_dead_requeues_to_survivor():
    replicas, metrics = [], ServeMetrics()
    for i in range(2):
        eng = InferenceEngine(_mlp_adapter(cls=_SlowAdapter), max_batch=4,
                              metrics=metrics, replica_id=f"replica-{i}")
        replicas.append(Replica(f"replica-{i}", None, eng))
    sched = ReplicaScheduler(replicas, metrics=metrics).start()
    try:
        victim = sched.replicas[0]
        reqs = [Request([3], max_new_tokens=100) for _ in range(3)]
        for r in reqs:
            victim.engine.batcher.submit(r)
        deadline = time.monotonic() + 10
        while victim.engine.active_count < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        sched.mark_dead("replica-0", reason="test")
        assert sched.healthz()["status"] == "degraded"
        chain = _mlp_chain(_mlp_adapter(), [3], 100)
        for r in reqs:
            assert r.result(timeout=60) == chain
            assert r.replica_id == "replica-1" and r.requeues == 1
        assert sched.metrics.snapshot()["requests"]["requeued"] == 3
    finally:
        sched.stop()


def test_mark_dead_requeues_past_full_survivor_queue():
    """Review finding: drained work must bypass the survivors' capacity
    bound (requeue_front), never shed — a replica loss with full queues
    must not turn accepted requests into 503s."""
    metrics = ServeMetrics()
    replicas = []
    for i in range(2):
        eng = InferenceEngine(_mlp_adapter(cls=_SlowAdapter),
                              batcher=DynamicBatcher(max_queue=1),
                              max_batch=2, metrics=metrics,
                              replica_id=f"replica-{i}")
        replicas.append(Replica(f"replica-{i}", None, eng))
    sched = ReplicaScheduler(replicas, metrics=metrics).start()
    try:
        victim = sched.replicas[0]
        survivor = sched.replicas[1]
        # Fill the survivor's queue to its (tiny) capacity.
        survivor.engine.batcher.submit(Request([9], max_new_tokens=30))
        reqs = [Request([3], max_new_tokens=30) for _ in range(3)]
        for r in reqs:
            victim.engine.batcher.requeue_front([r])  # direct: bypass route
        deadline = time.monotonic() + 10
        while victim.engine.active_count == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        sched.mark_dead("replica-0", reason="test")
        chain = _mlp_chain(_mlp_adapter(), [3], 30)
        for r in reqs:  # every accepted request completes, none shed
            assert r.result(timeout=60) == chain
        assert metrics.snapshot()["requests"]["shed"] == 0
        assert metrics.snapshot()["requests"]["requeued"] == 3
    finally:
        sched.stop()


def test_scheduler_stop_fails_inflight_promptly():
    """Review finding: stop() must fail in-flight requests immediately —
    not leave their waiters parked until the request timeout."""
    metrics = ServeMetrics()
    eng = InferenceEngine(_mlp_adapter(cls=_SlowAdapter), max_batch=2,
                          metrics=metrics, replica_id="replica-0")
    sched = ReplicaScheduler([Replica("replica-0", None, eng)],
                             metrics=metrics).start()
    r = Request([5], max_new_tokens=120)
    sched.submit(r)
    deadline = time.monotonic() + 10
    while eng.active_count == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    t0 = time.monotonic()
    sched.stop()
    with pytest.raises(NoHealthyReplicaError, match="shutting down"):
        r.result(timeout=5)
    assert time.monotonic() - t0 < 5.0


def test_engine_counts_expired_requests_in_metrics():
    """Review finding: deadline sheds inside the engine's own batcher
    must surface as the 'expired' outcome."""
    eng = InferenceEngine(_mlp_adapter(), max_batch=2, replica_id="t")
    r = Request([5], max_new_tokens=4, timeout_s=0.01)
    eng.batcher.submit(r)
    time.sleep(0.05)
    eng.start()
    try:
        with pytest.raises(DeadlineExceededError):
            r.result(timeout=10)
        deadline = time.monotonic() + 5
        while eng.metrics.snapshot()["requests"]["expired"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.metrics.snapshot()["requests"]["expired"] == 1
    finally:
        eng.stop()


def test_metrics_scrape_during_expiry_storm_no_deadlock():
    """Review finding: /metrics sampling queue depth (metrics lock →
    batcher lock) while the engine sheds expired requests (batcher lock →
    metrics lock via on_shed) was an AB/BA deadlock.  Hammer both sides
    concurrently; everything must settle well inside the budget."""
    eng = InferenceEngine(_mlp_adapter(), max_batch=2, replica_id="t")
    eng.metrics.register_queue_depth("t", eng.batcher.depth)
    eng.start()
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            eng.metrics.render()
            eng.metrics.snapshot()
            eng.metrics.maybe_emit_timeline(force=True)

    scrapers = [threading.Thread(target=scraper) for _ in range(2)]
    for t in scrapers:
        t.start()
    try:
        reqs = []
        for i in range(60):
            r = Request([5], max_new_tokens=2,
                        timeout_s=0.001 if i % 2 else None)
            try:
                eng.batcher.submit(r)
                reqs.append(r)
            except QueueFullError:
                pass
        deadline = time.monotonic() + 30
        done = [False] * len(reqs)
        for i, r in enumerate(reqs):
            try:
                r.result(timeout=max(deadline - time.monotonic(), 0.1))
                done[i] = True
            except DeadlineExceededError:
                done[i] = True  # expired — also a settled outcome
        assert all(done)
        assert time.monotonic() < deadline
    finally:
        stop.set()
        for t in scrapers:
            t.join(timeout=10)
        eng.stop()


def test_request_rejects_nonpositive_max_new_tokens():
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request([1], max_new_tokens=0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request([1], max_new_tokens=-3)


def test_scheduler_unserving_when_all_dead():
    sched = _two_replica_sched()
    try:
        sched.mark_dead("replica-0")
        sched.mark_dead("replica-1")
        assert sched.healthz()["status"] == "unserving"
        with pytest.raises(NoHealthyReplicaError):
            sched.submit(Request([1]))
    finally:
        sched.stop()


def test_report_rank_lost_maps_rank_to_replica(hvd8):
    from horovod_tpu.serve import build_replicas
    sched = build_replicas(_mlp_adapter, num_replicas=4).start()
    try:
        assert [r.ranks for r in sched.replicas] == \
            [[0, 1], [2, 3], [4, 5], [6, 7]]
        assert sched.report_rank_lost(5) == "replica-2"
        assert sched.report_rank_lost(99) is None
        # Second loss of the same replica's other rank: already dead.
        assert sched.report_rank_lost(4) is None
        health = sched.healthz()
        assert health["status"] == "degraded" and health["healthy"] == 3
    finally:
        sched.stop()
