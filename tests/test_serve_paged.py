"""ISSUE 5: paged KV cache, chunked prefill, and prefix reuse.

Pins the tentpole's contracts layer by layer:

* BlockManager — refcounted pool, full-block prefix registry with LRU
  retention/eviction, copy-on-write;
* batcher — admission accounts free BLOCKS (budget/cost/hard_cap), FIFO
  preserved;
* engine — batched==single bit-exactness under paged cache + chunked
  prefill across bucket transitions and block-boundary prompt lengths
  (k*block, k*block±1), decode interleaving while a max_len prompt
  prefills in chunks (token_step p99 bounded vs the unchunked engine),
  shared-prefix requests allocating fewer fresh blocks with identical
  output, poisoned-batch recovery freeing only the failed iteration's
  blocks, pool-exhaustion preemption;
* metrics — kv-block utilization / prefix hit rate / prefill-vs-decode
  token split in snapshot, /metrics exposition, and SERVE/* timeline
  counters.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import create_mlp
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serve import (BlockManager, DynamicBatcher,
                               InferenceEngine, MLPAdapter,
                               NoFreeBlocksError, Request, ServeMetrics,
                               TransformerAdapter, chain_hashes)

BT = 8  # block_tokens used throughout (small, so boundaries are cheap)

_TINY = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=64, causal=True,
                          dtype=jnp.float32, scan_layers=False)


def _tiny():
    model = Transformer(_TINY)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _flax_greedy(model, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        lg = model.apply({"params": params}, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(lg[0, -1])))
    return seq[len(prompt):]


def _paged_engine(params, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("prefill_chunk", 5)  # deliberately unaligned with BT
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    return InferenceEngine(ad, replica_id="paged-t", **kw)


# -- BlockManager ------------------------------------------------------------

def test_block_manager_alloc_free_refcount():
    bm = BlockManager(4, BT)
    a, b = bm.allocate(2)
    assert bm.stats()["used"] == 2 and bm.stats()["free"] == 2
    bm.ref(a)
    bm.free(a)
    assert bm.refcount(a) == 1  # still held once
    bm.free(a)
    bm.free(b)
    assert bm.stats()["used"] == 0 and bm.stats()["free"] == 4
    with pytest.raises(ValueError, match="double free"):
        bm.free(b)
    with pytest.raises(NoFreeBlocksError):
        bm.allocate(5)


def test_block_manager_prefix_register_lookup_and_retention():
    bm = BlockManager(8, BT)
    prompt = list(range(2 * BT + 3))
    hashes = chain_hashes(prompt, BT)
    assert len(hashes) == 2
    blocks = bm.allocate(2)
    for h, bid in zip(hashes, blocks):
        bm.register(h, bid)
    # Owner releases: registered blocks are RETAINED, not freed.
    bm.free_table(blocks)
    assert bm.stats()["retained"] == 2 and bm.stats()["used"] == 0
    # A same-prefix lookup claims both full blocks back.
    ids, matched = bm.lookup_prefix(prompt)
    assert ids == blocks and matched == 2 * BT
    assert bm.stats()["retained"] == 0 and bm.stats()["used"] == 2
    # A fully-cached prompt reuses all but its FINAL block (the prefill
    # must run the last token to produce the first output's logits).
    bm.free_table(ids)
    ids, matched = bm.lookup_prefix(prompt[:2 * BT])
    assert len(ids) == 1 and matched == BT
    bm.free_table(ids)
    # Divergence below block granularity = different chain hash = miss.
    other = list(prompt)
    other[1] = 60
    ids, matched = bm.lookup_prefix(other)
    assert ids == [] and matched == 0
    stats = bm.stats()
    assert stats["prefix_hit_rate"] < 1.0
    assert stats["prefix_hit_tokens"] > 0


def test_block_manager_lru_eviction_under_pressure():
    bm = BlockManager(2, BT)
    blocks = bm.allocate(2)
    h1, h2 = chain_hashes(list(range(2 * BT)), BT)
    bm.register(h1, blocks[0])
    bm.register(h2, blocks[1])
    bm.free(blocks[0])  # LRU
    bm.free(blocks[1])
    fresh = bm.allocate(1)  # must evict the LRU retained block
    assert fresh == [blocks[0]]
    assert bm.stats()["evictions"] == 1
    # Its registry entry is gone; the other survives.
    ids, matched = bm.lookup_prefix(list(range(BT + 1)))
    assert ids == [] and matched == 0


def test_block_manager_copy_on_write():
    bm = BlockManager(4, BT)
    (shared,) = bm.allocate(1)
    bm.ref(shared)  # two holders
    bid, copied = bm.ensure_writable(shared)
    assert copied and bid != shared
    # The old reference is NOT moved: the caller frees it only after
    # the device copy succeeds (a failed copy must not double-free).
    assert bm.refcount(shared) == 2 and bm.refcount(bid) == 1
    bm.free(shared)  # the caller's post-copy release
    assert bm.refcount(shared) == 1
    assert bm.stats()["cow"] == 1
    # Private unregistered block: written in place.
    bid2, copied2 = bm.ensure_writable(bid)
    assert bid2 == bid and not copied2
    # Registered (published) block must fork even with one holder: its
    # hash has to keep matching its contents.
    bm.register(chain_hashes(list(range(BT)), BT)[0], bid)
    bid3, copied3 = bm.ensure_writable(bid)
    assert copied3 and bid3 != bid


def test_prefix_cache_disabled_never_registers():
    bm = BlockManager(4, BT, prefix_cache=False)
    (bid,) = bm.allocate(1)
    bm.register(chain_hashes(list(range(BT)), BT)[0], bid)
    bm.free(bid)
    assert bm.stats()["retained"] == 0  # straight back to the free list
    assert bm.lookup_prefix(list(range(2 * BT))) == ([], 0)


# -- batcher block-budget admission ------------------------------------------

def test_batcher_admission_accounts_block_budget():
    b = DynamicBatcher(max_queue=16, max_wait_ms=0)
    for n in (4, 4, 4):
        b.submit(Request([1] * n))
    cost = lambda r: len(r.prompt)  # noqa: E731
    got = b.get_admission(8, block_s=0.0, budget=9, cost=cost, hard_cap=99)
    assert [len(r.prompt) for r in got] == [4, 4]  # third exceeds budget
    assert b.depth() == 1


def test_batcher_budget_stops_at_head_preserving_fifo():
    """A cheap late request must NOT jump an expensive head (head-of-line
    order is the fairness contract)."""
    b = DynamicBatcher(max_queue=16, max_wait_ms=0)
    b.submit(Request([1] * 8))
    b.submit(Request([1]))
    got = b.get_admission(4, block_s=0.0, budget=2,
                          cost=lambda r: len(r.prompt), hard_cap=99)
    assert got == []
    assert b.depth() == 2


def test_batcher_hard_cap_pops_impossible_requests():
    """A request no budget could ever cover pops anyway — the engine
    fails it loudly instead of letting it wedge the queue head."""
    b = DynamicBatcher(max_queue=16, max_wait_ms=0)
    b.submit(Request([1] * 8))
    b.submit(Request([1]))
    got = b.get_admission(4, block_s=0.0, budget=2,
                          cost=lambda r: len(r.prompt), hard_cap=4)
    assert [len(r.prompt) for r in got] == [8, 1]


# -- engine: exactness under paged + chunked ---------------------------------

@pytest.mark.slow  # ~13s; non-chunked flax parity stays in tier-1
def test_paged_chunked_matches_flax_at_block_boundaries():
    """Greedy decode through the paged cache with a chunk budget that is
    deliberately unaligned with the block size must match the full
    recompute exactly at k*block, k*block±1 prompt lengths (and across
    prompt-length buckets)."""
    model, params = _tiny()
    eng = _paged_engine(params).start()
    try:
        for plen in (BT - 1, BT, BT + 1, 2 * BT - 1, 2 * BT, 2 * BT + 1,
                     3, 30):
            prompt = np.random.RandomState(plen).randint(
                0, 61, (plen,)).tolist()
            assert eng.generate(prompt, max_new_tokens=6) == \
                _flax_greedy(model, params, prompt, 6), f"plen={plen}"
    finally:
        eng.stop()


def test_paged_batched_equals_single():
    """The exactness pin: a concurrent storm on the engine == the same
    prompts served alone."""
    model, params = _tiny()
    eng = _paged_engine(params).start()
    try:
        prompts = [np.random.RandomState(i).randint(
            0, 61, (3 + (i * 5) % (3 * BT),)).tolist() for i in range(12)]
        singles = [eng.generate(p, max_new_tokens=8) for p in prompts]
        results = [None] * len(prompts)

        def run(i):
            results[i] = eng.generate(prompts[i], max_new_tokens=8)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == singles
        assert eng.metrics.snapshot()["occupancy"]["max"] > 1
    finally:
        eng.stop()


def test_paged_engine_eos_and_requeue_semantics():
    model, params = _tiny()
    eng = _paged_engine(params).start()
    try:
        prompt = [3, 17, 42, 9]
        chain = _flax_greedy(model, params, prompt, 8)
        eos = chain[3]
        # Stops AT the first eos occurrence, inclusive.
        assert eng.generate(prompt, max_new_tokens=8, eos_id=eos) == \
            chain[:chain.index(eos) + 1]
    finally:
        eng.stop()
    # drain() releases every block reference — nothing leaks.
    assert eng.kv_stats()["used"] == 0


# -- chunked prefill interference --------------------------------------------

class _CostedAdapter:
    """Delegates to a TransformerAdapter but makes prefill cost visibly
    proportional to chunk tokens (1 ms/token), so the chunked-vs-
    unchunked token_step comparison is deterministic on any machine."""

    def __init__(self, inner, ms_per_token=1.0):
        self._inner = inner
        self._ms = ms_per_token
        for attr in ("vocab_size", "max_len", "block_tokens",
                     "kv_token_cost"):
            setattr(self, attr, getattr(inner, attr))

    @property
    def max_blocks_per_seq(self):
        return self._inner.max_blocks_per_seq

    def init_paged_cache(self, num_blocks, max_batch):
        return self._inner.init_paged_cache(num_blocks, max_batch)

    def prefill_chunk(self, cache, chunks, starts, tables):
        time.sleep(sum(len(c) for c in chunks) * self._ms / 1e3)
        return self._inner.prefill_chunk(cache, chunks, starts, tables)

    def decode_paged(self, cache, tokens, positions, tables):
        return self._inner.decode_paged(cache, tokens, positions, tables)

    def copy_block(self, cache, src, dst):
        return self._inner.copy_block(cache, src, dst)


def _interference_run(params, prefill_chunk):
    # The adapter (and its jit caches) is shared between a warm pass and
    # the measured pass — compile gaps land in the warm engine's
    # histogram, not the measured one (same discipline as bench.py).
    ad = _CostedAdapter(TransformerAdapter(_TINY, params, block_tokens=BT),
                        ms_per_token=2.0)

    def run():
        eng = InferenceEngine(ad, max_batch=4,
                              prefill_chunk=prefill_chunk,
                              metrics=ServeMetrics(),
                              replica_id="interf").start()
        bg = Request([5, 9, 2], max_new_tokens=40)
        eng.batcher.submit(bg)
        deadline = time.monotonic() + 30
        while eng.metrics.snapshot()["decode_steps"] < 3 \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        steps_before = eng.metrics.snapshot()["decode_steps"]
        long_prompt = np.random.RandomState(0).randint(
            0, 61, (_TINY.max_len - 8,)).tolist()
        long_req = Request(long_prompt, max_new_tokens=2)
        eng.batcher.submit(long_req)
        long_out = long_req.result(timeout=120)
        steps_during = eng.metrics.snapshot()["decode_steps"] - steps_before
        bg_out = bg.result(timeout=120)
        # Snapshot AFTER stop(): request completion fires mid-iteration,
        # before the loop thread records that iteration's metrics.
        eng.stop()
        snap = eng.metrics.snapshot()
        return bg_out, long_out, steps_during, snap

    run()  # warm: compile every bucket this config hits
    return run()


@pytest.mark.slow  # ~33s latency soak
def test_chunked_prefill_keeps_decode_flowing_and_p99_bounded():
    """ISSUE 5 acceptance: while a ~max_len prompt prefills in chunks,
    in-flight decodes keep stepping between chunks (structural proof) and
    decode token_step p99 stays strictly below the unchunked engine's
    (the whole-prompt prefill lands in one inter-decode gap)."""
    model, params = _tiny()
    chunk_bg, chunk_long, chunk_steps, chunk_snap = \
        _interference_run(params, prefill_chunk=8)
    whole_bg, whole_long, _, whole_snap = \
        _interference_run(params, prefill_chunk=0)
    # Exactness is preserved in both modes (and across them).
    assert chunk_bg == whole_bg == _flax_greedy(model, params,
                                                [5, 9, 2], 40)
    assert chunk_long == whole_long
    # Structural: the 56-token prompt took ceil(56/8) = 7 chunk
    # iterations, and the background sequence decoded through them.
    assert chunk_steps >= 5
    # Latency: the unchunked engine's single ~112 ms prefill (costed 2
    # ms/token) lands inside one decode gap; the chunked engine's gaps
    # are bounded by the 8-token (~16 ms) budget.
    chunk_p99 = chunk_snap["token_step"]["p99_ms"]
    whole_p99 = whole_snap["token_step"]["p99_ms"]
    assert chunk_p99 < whole_p99, (chunk_p99, whole_p99)
    # The per-iteration token split saw prefill and decode share
    # iterations in the chunked run.
    assert chunk_snap["token_split"]["prefill_tokens"] >= 56
    assert chunk_snap["token_split"]["decode_tokens"] >= 40


# -- prefix reuse ------------------------------------------------------------

def test_prefix_reuse_allocates_fewer_blocks_and_matches_single():
    model, params = _tiny()
    eng = _paged_engine(params, prefill_chunk=64).start()
    try:
        shared = np.random.RandomState(7).randint(
            0, 61, (3 * BT,)).tolist()
        p1 = shared + [5, 9]
        p2 = shared + [11, 3]
        ref1 = _flax_greedy(model, params, p1, 6)
        ref2 = _flax_greedy(model, params, p2, 6)
        out1 = eng.generate(p1, max_new_tokens=6)
        s1 = eng.kv_stats()
        out2 = eng.generate(p2, max_new_tokens=6)
        s2 = eng.kv_stats()
        assert out1 == ref1 and out2 == ref2
        # Request 2 mapped the 3 shared full blocks instead of
        # allocating fresh ones: hit tokens jumped by 3*BT.
        assert s2["prefix_hit_tokens"] - s1["prefix_hit_tokens"] == 3 * BT
        assert s2["prefix_hit_rate"] > 0
        # And a third identical-prefix request served ALONE still equals
        # the no-cache reference — cached K/V is bit-equal by content.
        cold = InferenceEngine(
            TransformerAdapter(_TINY, params, block_tokens=BT),
            max_batch=8, prefix_cache=False,
            replica_id="cold").start()
        try:
            assert cold.generate(p2, max_new_tokens=6) == ref2
        finally:
            cold.stop()
    finally:
        eng.stop()


def test_prefix_cache_toggle_off_no_hits():
    _, params = _tiny()
    eng = _paged_engine(params, prefix_cache=False).start()
    try:
        p = list(range(2 * BT)) + [7]
        a = eng.generate(p, max_new_tokens=4)
        b = eng.generate(p, max_new_tokens=4)
        assert a == b
        stats = eng.kv_stats()
        assert stats["prefix_hit_tokens"] == 0
        assert stats["retained"] == 0
    finally:
        eng.stop()


# -- recovery / preemption ---------------------------------------------------

def test_paged_poisoned_batch_frees_only_failed_blocks():
    """Recovery must fail the in-flight requests and release ONLY their
    block references — the pool arrays and the prefix registry survive,
    so a same-prefix request after recovery still hits the cache."""
    _, params = _tiny()

    class _PoisonOnce(_CostedAdapter):
        def __init__(self, inner):
            super().__init__(inner, ms_per_token=0.0)
            self.armed = False

        def decode_paged(self, cache, tokens, positions, tables):
            if self.armed:
                self.armed = False
                raise RuntimeError("simulated device fault")
            return super().decode_paged(cache, tokens, positions, tables)

    ad = _PoisonOnce(TransformerAdapter(_TINY, params, block_tokens=BT))
    eng = InferenceEngine(ad, max_batch=4,
                          prefill_chunk=64, replica_id="poison").start()
    try:
        shared = list(range(2 * BT))
        warm = eng.generate(shared + [3], max_new_tokens=4)  # seeds cache
        hits0 = eng.kv_stats()["prefix_hit_tokens"]
        ad.armed = True
        doomed = Request(shared + [9], max_new_tokens=8)
        eng.batcher.submit(doomed)
        with pytest.raises(RuntimeError, match="simulated device fault"):
            doomed.result(timeout=30)
        stats = eng.kv_stats()
        # The failed sequence's references are gone (its prefix blocks
        # drop back to retained, private ones to free) — nothing leaks.
        assert stats["used"] == 0
        assert stats["retained"] > 0  # registry survived the failure
        # A post-recovery same-prefix request still hits the cache AND
        # still answers exactly.
        again = eng.generate(shared + [3], max_new_tokens=4)
        assert again == warm
        assert eng.kv_stats()["prefix_hit_tokens"] > hits0
        assert eng.metrics.snapshot()["requests"]["error"] == 1
    finally:
        eng.stop()


def test_pool_exhaustion_preempts_youngest_and_requeues():
    """The defensive decode-time path: a sequence whose table does not
    cover its next write (possible only if admission over-promised, e.g.
    operator-shrunk pools) preempts the YOUNGEST sequence — requeued at
    the front of the engine's own queue, counted, never corrupted."""
    _, params = _tiny()
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    eng = InferenceEngine(ad, max_batch=4, num_blocks=2,
                          prefill_chunk=64, replica_id="exhaust")
    from horovod_tpu.serve.engine import _Seq
    # Hand-build two decoding sequences that together exceed the 2-block
    # pool: the old one owns both blocks; the young one owns none and
    # needs one for its first decode write.
    old_req = Request([1] * BT, max_new_tokens=4)
    old_req.generated = [5]
    young_req = Request([2] * BT, max_new_tokens=4)
    young_req.generated = [7]
    old = _Seq(old_req, 0, eng.blocks.allocate(2), [], admit_seq=0)
    old.length = BT
    old.prompt_pos = BT
    young = _Seq(young_req, 0, [], [], admit_seq=1)
    young.length = BT
    young.prompt_pos = BT
    eng._slots[0] = old
    eng._slots[1] = young
    eng._decode_once()
    # The youngest lost its slot and sits at the front of the queue with
    # progress reset; the old sequence decoded on.
    assert eng._slots[1] is None
    assert young_req.generated == [] and young_req.requeues == 1
    assert eng.batcher.depth() == 1
    assert eng.metrics.snapshot()["requests"]["preempted"] == 1
    assert len(old_req.generated) == 2


# -- steady-state compile discipline -----------------------------------------

def test_paged_steady_state_never_recompiles():
    _, params = _tiny()
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    eng = InferenceEngine(ad, max_batch=4,
                          prefill_chunk=8, replica_id="compile").start()
    try:
        for i in range(3):
            eng.generate([1 + i, 2, 3], max_new_tokens=4)
        eng.generate(list(range(1, 20)), max_new_tokens=4)
        chunk_keys = set(ad._chunk_cache)
        assert len(ad._paged_decode_fns) == 1
        decode_fns = dict(ad._paged_decode_fns)
        # Steady state: same-bucket traffic reuses every program.
        for i in range(3):
            eng.generate([7 + i, 2, 3], max_new_tokens=4)
        eng.generate(list(range(2, 21)), max_new_tokens=4)
        assert set(ad._chunk_cache) == chunk_keys
        assert ad._paged_decode_fns == decode_fns
    finally:
        eng.stop()


def test_shared_adapter_across_pool_sizes_stays_exact():
    """Review finding: the paged programs bake the pool's OOB hole
    sentinel (= num_blocks) into their closures, so an adapter SHARED by
    engines with different pool sizes (the bench's warm-engine pattern)
    must compile per pool geometry — a stale sentinel would scatter
    pad-tail K/V into a real block of the bigger pool."""
    model, params = _tiny()
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    prompt = np.random.RandomState(3).randint(0, 61, (2 * BT + 3,)).tolist()
    ref = _flax_greedy(model, params, prompt, 6)
    # INTERLEAVED engines on one adapter: geometry must come from each
    # call's own cache, not from whichever engine initialized last.
    engines = [InferenceEngine(ad, max_batch=4,
                               num_blocks=nb, prefill_chunk=5,
                               replica_id=f"pool-{nb}").start()
               for nb in (16, 48)]
    try:
        for eng in engines + engines[::-1]:
            assert eng.generate(prompt, max_new_tokens=6) == ref, \
                eng.replica_id
    finally:
        for eng in engines:
            eng.stop()
    # One program set per pool geometry.
    assert {k[2] for k in ad._chunk_cache} == {16, 48}
    assert set(ad._paged_decode_fns) == {(16, 4), (48, 4)}


def test_recovery_rebuilds_pool_when_donated_cache_was_consumed():
    """Review finding: a runtime failure AFTER jit donation leaves the
    pool arrays deleted — recovery must detect that, rebuild the pool,
    and reset the prefix registry (retained hashes must never describe
    zeroed blocks); the replica keeps serving exactly."""
    model, params = _tiny()
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    eng = InferenceEngine(ad, max_batch=4,
                          prefill_chunk=64, replica_id="donated").start()
    try:
        shared = list(range(2 * BT))
        ref = _flax_greedy(model, params, shared + [3], 4)
        assert eng.generate(shared + [3], max_new_tokens=4) == ref
        assert eng.kv_stats()["retained"] > 0
        # Simulate the donated-buffer loss + the step failure together.
        orig = ad.decode_paged

        def poisoned(cache, tokens, positions, tables):
            ad.decode_paged = orig
            for arr in cache.values():
                arr.delete()
            raise RuntimeError("xla runtime failure after donation")

        ad.decode_paged = poisoned
        doomed = Request(shared + [9], max_new_tokens=4)
        eng.batcher.submit(doomed)
        with pytest.raises(RuntimeError, match="after donation"):
            doomed.result(timeout=30)
        # Pool rebuilt, registry reset (no stale hashes over zeroed
        # blocks) — the request fails BEFORE the rebuild finishes, so
        # poll for it — and the replica still answers exactly.
        deadline = time.monotonic() + 10
        while eng.kv_stats()["retained"] != 0 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        stats = eng.kv_stats()
        assert stats["used"] == 0 and stats["retained"] == 0
        assert eng.generate(shared + [3], max_new_tokens=4) == ref
    finally:
        eng.stop()


def test_prefix_registration_is_watermarked_not_quadratic():
    """Review finding: each chunk must register only the blocks IT
    completed — re-walking from block 0 every chunk is quadratic in
    prompt length."""
    _, params = _tiny()
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    eng = InferenceEngine(ad, max_batch=4,
                          prefill_chunk=BT, replica_id="wm").start()
    calls = []
    orig = eng.blocks.register
    eng.blocks.register = \
        lambda h, b, salt=0: (calls.append(b), orig(h, b, salt))[1]
    try:
        prompt = list(range(6 * BT))  # 6 full blocks, 6 chunks
        eng.generate(prompt, max_new_tokens=2)
        # 5 registerable full blocks (the final block re-prefills the
        # last token and is allowed one registration too) — but never
        # the quadratic 1+2+...+6 = 21 walk.
        assert len(calls) <= 6, calls
        assert len(calls) == len(set(calls))  # each block at most once
    finally:
        eng.stop()


# -- metrics surfaces --------------------------------------------------------

def test_metrics_expose_kv_blocks_prefix_and_token_split():
    _, params = _tiny()
    eng = _paged_engine(params).start()
    eng.metrics.register_kv_stats("paged-t", eng.kv_stats)
    try:
        p = list(range(2 * BT)) + [7]
        eng.generate(p, max_new_tokens=4)
        eng.generate(p, max_new_tokens=4)
        snap = eng.metrics.snapshot()
        assert snap["kv_blocks"]["paged-t"]["total"] == \
            eng.blocks.capacity
        assert snap["prefix_cache"]["hit_tokens"] > 0
        assert 0 < snap["prefix_cache"]["hit_rate"] <= 1
        assert snap["token_split"]["prefill_tokens"] > 0
        assert snap["token_split"]["decode_tokens"] > 0
        text = eng.metrics.render()
        assert 'hvd_serve_kv_blocks{replica="paged-t",state="used"}' \
            in text
        assert 'hvd_serve_prefix_cache_hit_rate{replica="paged-t"}' in text
        assert "hvd_serve_prefill_tokens_total" in text
        assert "hvd_serve_decode_tokens_total" in text
    finally:
        eng.stop()


def test_timeline_counters_carry_kv_stats(tmp_path):
    import json
    from horovod_tpu.timeline import Timeline
    path = str(tmp_path / "paged_trace.json")
    tl = Timeline(path)
    m = ServeMetrics()
    m.set_timeline(tl)
    m.observe_iteration(8, 3)
    m.observe_decode_step(2.0, occupancy=3, new_tokens=3)
    m.maybe_emit_timeline(force=True,
                          kv_stats={"used": 5, "free": 11, "retained": 2,
                                    "prefix_hit_rate": 0.25})
    tl.close()
    events = json.load(open(path))
    serve = [e for e in events if e.get("name", "").startswith("SERVE/")]
    assert serve
    args = serve[-1]["args"]
    assert args["kv_blocks_used"] == 5
    assert args["kv_blocks_free"] == 11
    assert args["prefix_hit_rate"] == 0.25
    assert args["prefill_tokens_total"] == 8
    assert args["decode_tokens_total"] == 3


# -- replica / build_replicas integration ------------------------------------

def test_replica_to_dict_and_build_replicas_kwargs(hvd8):
    from horovod_tpu.serve import build_replicas
    mlp = create_mlp(features=(16, 31))
    mp = mlp.init(jax.random.PRNGKey(3), jnp.zeros((1, 31)))["params"]
    _, params = _tiny()
    sched = build_replicas(
        lambda: TransformerAdapter(_TINY, params, block_tokens=BT),
        num_replicas=2, max_batch=4, num_blocks=16, prefill_chunk=8)
    try:
        sched.start()
        for r in sched.replicas:
            assert r.engine.blocks.capacity == 16
            assert r.to_dict()["kv_blocks"]["total"] == 16
    finally:
        sched.stop()
    # MLP adapters serve with a zero-block footprint.
    meng = InferenceEngine(MLPAdapter(mlp, mp, vocab_size=31),
                           max_batch=4, replica_id="mlp")
    assert meng.kv_stats()["block_tokens"] == 1


# -- mark_dead during chunked prefill (ISSUE 6 satellite) --------------------

def test_mark_dead_during_chunked_prefill_requeues_and_frees_blocks():
    """A replica killed while a long prompt is MID-CHUNK must requeue the
    request with its already-prefilled blocks freed: the dead engine's
    pool reports used == 0 (no leak) and the survivor reproduces the
    answer exactly from the prompt."""
    from horovod_tpu.serve import Replica, ReplicaScheduler
    model, params = _tiny()
    metrics = ServeMetrics()
    # 5 ms/token chunk cost x 5-token chunks: a 40-token prompt spends
    # ~200 ms streaming through prefill — a wide, deterministic window to
    # kill inside.
    victim_eng = InferenceEngine(
        _CostedAdapter(TransformerAdapter(_TINY, params, block_tokens=BT),
                       ms_per_token=5.0),
        prefill_chunk=5, max_batch=8, metrics=metrics,
        replica_id="victim")
    survivor_eng = InferenceEngine(
        TransformerAdapter(_TINY, params, block_tokens=BT),
        prefill_chunk=5, max_batch=8, metrics=metrics,
        replica_id="survivor")
    sched = ReplicaScheduler(
        [Replica("victim", None, victim_eng),
         Replica("survivor", None, survivor_eng)], metrics=metrics).start()
    try:
        prompt = [int(t) for t in
                  np.random.RandomState(5).randint(0, 61, size=40)]
        r = Request(prompt, max_new_tokens=4)
        victim_eng.batcher.submit(r)  # pin the request to the victim

        def mid_chunk():
            with victim_eng._lock:
                return any(s is not None and 0 < s.prompt_pos < len(prompt)
                           for s in victim_eng._slots)

        deadline = time.monotonic() + 60
        while not mid_chunk() and time.monotonic() < deadline:
            time.sleep(0.002)
        assert mid_chunk(), "never observed a mid-chunk prefill"
        used_at_kill = victim_eng.kv_stats()["used"]
        assert used_at_kill > 0  # partially-prefilled blocks are held
        sched.mark_dead("victim", reason="mid-chunk race test")

        out = r.result(timeout=120)
        assert r.requeues >= 1
        assert r.replica_id == "survivor"
        assert out == _flax_greedy(model, params, prompt, 4)  # exact
        # No pool leak on the dead replica: every reference the partial
        # prefill held was released (full prompt blocks may be RETAINED —
        # refcount 0, still prefix-registered — never "used").
        assert victim_eng.kv_stats()["used"] == 0
        assert metrics.snapshot()["requests"]["requeued"] >= 1
    finally:
        sched.stop()


def test_mark_dead_idle_replica_refunds_reserves_and_requeues_nothing():
    """hvdctl's scale-down drain (controller._scale_down): marking an
    IDLE replica dead must be work-free — zero requests requeued onto
    survivors — and must leave the pool fully refunded: no used blocks
    and no outstanding fork-family reserves (an n>1 request's decode
    tails are RESERVED at admission, not allocated, so a leak here
    would silently shrink every later admission budget)."""
    from horovod_tpu.serve import Replica, ReplicaScheduler
    _, params = _tiny()
    metrics = ServeMetrics()
    victim_eng = InferenceEngine(
        TransformerAdapter(_TINY, params, block_tokens=BT),
        prefill_chunk=5, max_batch=8, metrics=metrics,
        replica_id="victim")
    survivor_eng = InferenceEngine(
        TransformerAdapter(_TINY, params, block_tokens=BT),
        prefill_chunk=5, max_batch=8, metrics=metrics,
        replica_id="survivor")
    sched = ReplicaScheduler(
        [Replica("victim", None, victim_eng),
         Replica("survivor", None, survivor_eng)], metrics=metrics).start()
    try:
        # Run a fork family (n=2 reserves decode tails) and a greedy
        # request through the victim, to completion.
        forked = Request([1, 2, 3, 4], max_new_tokens=6,
                         temperature=0.8, n=2, seed=11)
        plain = Request([5, 6, 7], max_new_tokens=4)
        victim_eng.batcher.submit(forked)
        victim_eng.batcher.submit(plain)
        assert len(forked.result(timeout=120)) == 6
        assert len(plain.result(timeout=120)) == 4
        deadline = time.monotonic() + 30
        while victim_eng.active_count > 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert victim_eng.active_count == 0 and \
            victim_eng.batcher.depth() == 0, "victim never went idle"

        requeued_before = metrics.snapshot()["requests"]["requeued"]
        sched.mark_dead("victim", reason="hvdctl: sustained idleness")

        # Work-free shrink: nothing moved to the survivor.
        assert metrics.snapshot()["requests"]["requeued"] == requeued_before
        assert survivor_eng.batcher.depth() == 0
        # Full refund: no used blocks (retained prefix blocks are fine —
        # refcount 0), no outstanding fork-family reserves.
        assert victim_eng.kv_stats()["used"] == 0
        assert victim_eng._reserved_blocks() == 0
    finally:
        sched.stop()
