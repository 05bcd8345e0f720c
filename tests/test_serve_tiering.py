"""ISSUE 16: hvdtier — tiered KV hierarchy (device → host RAM →
KV-server), ahead-of-decode prefetch, cross-replica prefix-block
migration.

Pins the tentpole's contracts layer by layer:

* payload codec — pack/unpack round-trips quantized payloads (int8
  values + float scale rows) bit-exactly;
* TieredBlockManager — pool pressure SPILLS cold retained blocks
  host-ward instead of evicting their bytes, a later same-prefix
  lookup promotes them back bit-identically, ``ensure_writable``
  faults staged payloads in BEFORE the CoW fork, and base retained-LRU
  eviction under the version-salted registry drops the fleet
  directory entry (the roll-mid-migration regression);
* engine — demote-over-preempt admission (in-flight strictly above the
  untiered baseline at the same pool bytes, outputs bit-identical),
  cross-replica migration == local prefill at k*BT±1 prompt tails,
  prefetch-race stalls counted + histogrammed as tier faults, and
  mark_dead unpublishing the dead holder's directory entries;
* faultline — ``delay-tier-fetch`` rides the KV retry backoff and
  merely slows the migration; a ``drop-tier-block`` train past the
  retry budget degrades to recompute with BIT-IDENTICAL output.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import faultline as fl
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.runner.http_server import KVStoreClient, KVStoreServer
from horovod_tpu.serve import (InferenceEngine, Request, TierClient,
                               TierConfig, TieredBlockManager,
                               TransformerAdapter, chain_hashes)
from horovod_tpu.serve.tiering import (HostTier, pack_payload,
                                       unpack_payload)

BT = 8

_TINY = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=64, causal=True,
                          dtype=jnp.float32, scan_layers=False)


@pytest.fixture(scope="module")
def tiny_params():
    model = Transformer(_TINY)
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture()
def kv_world(monkeypatch):
    monkeypatch.setenv("HVD_KV_RETRY_MAX", "3")
    monkeypatch.setenv("HVD_KV_RETRY_BASE_MS", "1")
    monkeypatch.setenv("HVD_KV_RETRY_CAP_MS", "5")
    server = KVStoreServer()
    port = server.start(0)
    yield server, port
    fl.uninstall()
    server.stop()


def _engine(params, rid, tier=None, client=None, **kw):
    kw.setdefault("max_batch", 8)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("num_blocks", 32)
    ad = TransformerAdapter(_TINY, params, block_tokens=BT,
                            kv_dtype=kw.pop("kv_dtype", None))
    return InferenceEngine(ad, replica_id=rid,
                           tiering=tier, tier_client=client, **kw)


def _tier_client(port, rid):
    return TierClient(KVStoreClient("127.0.0.1", port), replica_id=rid)


def _wait_published(eng, n, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.kv_stats()["tier"]["published"] >= n:
            return True
        time.sleep(0.02)
    return False


# -- payload codec ------------------------------------------------------------

def test_pack_unpack_payload_bit_exact_with_scale_rows():
    """The serialization a block crosses tiers through must be a byte
    identity — int8 value planes AND their float32 scale rows."""
    rng = np.random.RandomState(0)
    payload = {
        "k": rng.randint(-128, 128, (2, BT, 2, 16)).astype(np.int8),
        "v": rng.randint(-128, 128, (2, BT, 2, 16)).astype(np.int8),
        "k_scale": rng.rand(2, BT, 2).astype(np.float32),
        "v_scale": rng.rand(2, BT, 2).astype(np.float32),
    }
    back = unpack_payload(pack_payload(payload))
    assert sorted(back) == sorted(payload)
    for key in payload:
        assert back[key].dtype == payload[key].dtype
        assert back[key].shape == payload[key].shape
        assert np.array_equal(back[key], payload[key]), key


def test_host_tier_lru_capacity_and_salt_scoping():
    ht = HostTier(2)
    from horovod_tpu.serve.tiering import _HostEntry

    def entry(salt):
        return _HostEntry({"k": np.zeros((1,), np.int8)}, salt, step=0)

    ht.put(1, entry(7))
    ht.put(2, entry(7))
    ht.put(3, entry(9))           # capacity 2: hash 1 LRU-evicted
    assert not ht.contains(1) and ht.evictions == 1
    assert ht.contains(2) and ht.contains(3)
    ht.drop_salt(7)               # roll: only salt-7 copies go
    assert not ht.contains(2) and ht.contains(3)


# -- TieredBlockManager -------------------------------------------------------

def _fake_pool(nb, nkeys=2):
    """A host-side stand-in for the device pool: per-block payload dicts
    with int8 values + float32 scale rows, and extract/insert closures
    over it (what make_block_io wires for a real engine)."""
    rng = np.random.RandomState(1)
    pool = {bid: {"k": rng.randint(-128, 128, (2, BT, 4)).astype(np.int8),
                  "k_scale": rng.rand(2, BT).astype(np.float32)}
            for bid in range(nb)}

    def extract(bid):
        return {k: a.copy() for k, a in pool[bid].items()}

    def insert(bid, payload):
        pool[bid] = {k: a.copy() for k, a in payload.items()}

    return pool, extract, insert


def test_spill_then_promote_round_trips_bit_exact():
    """Under pool pressure the coldest retained prefix block spills
    host-ward (payload + scale rows) instead of losing its bytes; the
    next same-prefix lookup promotes it back bit-identically and the
    chain hash survives the round trip."""
    bm = TieredBlockManager(4, BT, TierConfig())
    pool, extract, insert = _fake_pool(4)
    bm.set_device_io(extract, insert)
    prompt = list(range(4 * BT))
    hashes = chain_hashes(prompt, BT)
    blocks = bm.allocate(3)
    for h, bid in zip(hashes, blocks):
        bm.register(h, bid, salt=5)
    golden = [extract(bid) for bid in blocks]
    bm.free_table(blocks)                   # retained, not freed
    taken = bm.allocate(4)                  # pressure: all 3 spill
    st = bm.stats()["tier"]
    assert st["spills"] == 3 and st["host_blocks"] == 3
    assert st["spill_bytes"] > 0
    bm.free_table(taken)
    ids, matched = bm.lookup_prefix(prompt, hashes=hashes)
    assert matched == 3 * BT and len(ids) == 3
    for want, bid in zip(golden, ids):
        got = extract(bid)
        for key in want:
            assert np.array_equal(got[key], want[key]), key
    assert bm.stats()["tier"]["promotes"] == 3
    assert bm.stats()["tier"]["host_blocks"] == 0


def test_ensure_writable_faults_staged_payload_in_before_fork():
    """A spilled-and-refetched block whose payload is still STAGED must
    be applied to the device before a CoW fork copies it — otherwise
    the fork would duplicate stale zeros, not the real K/V."""
    bm = TieredBlockManager(4, BT, TierConfig())
    pool, extract, insert = _fake_pool(4)
    bm.set_device_io(extract, insert)
    bid = bm.allocate(1)[0]
    staged = {"k": np.full((2, BT, 4), 7, np.int8),
              "k_scale": np.ones((2, BT), np.float32)}
    bm.note_pending(bid, staged)
    bm.ref(bid)                              # shared → fork must copy
    new_bid, copied = bm.ensure_writable(bid)
    assert copied and new_bid != bid
    # The staged bytes landed on the ORIGINAL block before the fork
    # decision; a fork then copies real contents.
    assert np.array_equal(pool[bid]["k"], staged["k"])
    assert bm.apply_pending(bid) is False    # consumed exactly once


def test_retained_eviction_drops_directory_entry(kv_world):
    """Satellite bugfix: base retained-LRU eviction under the
    version-salted registry must retract the fleet directory entry —
    a peer resolving the evicted hash would otherwise fetch bytes the
    holder no longer has (or worse, rolled-weights bytes)."""
    _, port = kv_world
    client = _tier_client(port, "evict-t")
    bm = TieredBlockManager(2, BT, TierConfig(), client=client)
    prompt = list(range(2 * BT))
    h = chain_hashes(prompt, BT)[0]
    bid = bm.allocate(1)[0]
    bm.register(h, bid, salt=3)
    assert bm.mark_publishing(h)
    assert client.publish(h, 3, pack_payload(
        {"k": np.zeros((1, BT), np.int8)}))
    bm.note_published(h, 3, True)
    assert client.lookup(h) is not None
    bm.free(bid)                             # → retained
    # Corruption scrub takes the base eviction path (no extract wired):
    # the hash leaves the registry AND the fleet directory.
    assert bm.invalidate_retained(1) == 1
    assert client.lookup(h) is None
    peer = TieredBlockManager(2, BT, TierConfig(),
                              client=_tier_client(port, "evict-peer"))
    assert peer.remote_hits([h]) == 0


@pytest.mark.slow  # ~9s
def test_roll_mid_migration_misses_and_degrades(kv_world, tiny_params):
    """unpublish_salt (the weight-roll hook) mid-migration: the peer's
    directory probe of the OLD version's chain must miss — it
    re-prefills under its own weights instead of importing stale K/V."""
    _, port = kv_world
    ea = _engine(tiny_params, "roll-a", TierConfig(),
                 _tier_client(port, "roll-a")).start()
    eb = _engine(tiny_params, "roll-b", TierConfig(),
                 _tier_client(port, "roll-b")).start()
    base = _engine(tiny_params, "roll-base").start()
    try:
        shared = list(range(1, 3 * BT + 2))
        ref = base.generate(shared, max_new_tokens=4)
        assert ea.generate(shared, max_new_tokens=4) == ref
        assert _wait_published(ea, 3)
        # The roll retracts every entry published under the old salt.
        salt = ea._prefix_salt(None)
        assert ea.blocks.unpublish_salt(salt) == 3
        got = eb.generate(shared, max_new_tokens=4)
        assert got == ref                    # recompute, bit-identical
        assert eb.kv_stats()["tier"]["migrated_tokens"] == 0
    finally:
        ea.stop(); eb.stop(); base.stop()


# -- engine: demote-over-preempt ---------------------------------------------

@pytest.mark.slow  # ~18s capacity comparison
def test_demote_over_preempt_admits_more_at_same_pool_bytes(tiny_params):
    """The tentpole's perf claim at unit scale: with an identical device
    pool, the tiered engine keeps strictly more requests IN FLIGHT than
    the untiered baseline (which preempts its youngest), and the storm
    is bit-identical to the solo baseline."""
    base = _engine(tiny_params, "dop-base", max_batch=12,
                   num_blocks=16).start()
    tiered = _engine(tiny_params, "dop-tier",
                     TierConfig(oversub=4.0, quantum=2),
                     max_batch=12, num_blocks=16).start()
    try:
        prompts = [np.random.RandomState(100 + i).randint(
            0, 61, (10,)).tolist() for i in range(10)]
        singles = [base.generate(p, max_new_tokens=20) for p in prompts]

        def storm(eng):
            out = [None] * len(prompts)

            def run(i):
                out[i] = eng.generate(prompts[i], max_new_tokens=20)

            ts = [threading.Thread(target=run, args=(i,))
                  for i in range(len(prompts))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return out

        # Baseline first: its peak concurrency is bounded by the pool.
        base_peak = [0]

        def watch():
            while any(r is None for r in base_out):
                with base._lock:
                    live = len({id(s.request) for s in base._slots
                                if s is not None})
                base_peak[0] = max(base_peak[0], live)
                time.sleep(0.001)

        base_out = [None] * len(prompts)

        def run_base(i):
            base_out[i] = base.generate(prompts[i], max_new_tokens=20)

        w = threading.Thread(target=watch)
        ts = [threading.Thread(target=run_base, args=(i,))
              for i in range(len(prompts))]
        w.start()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        w.join()
        assert base_out == singles
        got = storm(tiered)
        assert got == singles                # outputs_match
        st = tiered.kv_stats()["tier"]
        assert st["inflight_peak"] > base_peak[0], \
            (st["inflight_peak"], base_peak[0])
        assert st["swapped_out_seqs"] > 0 and st["swapped_in_seqs"] > 0
    finally:
        base.stop(); tiered.stop()


# -- engine: cross-replica migration -----------------------------------------

@pytest.mark.slow  # ~11s block-boundary sweep
def test_migration_matches_local_prefill_at_block_boundaries(
        kv_world, tiny_params):
    """Follower outputs through migrated prefix blocks == local
    recompute at k*BT-1, k*BT, k*BT+1 prompt tails, and the migrated
    token count lands in the stats."""
    _, port = kv_world
    base = _engine(tiny_params, "mig-base").start()
    ea = _engine(tiny_params, "mig-a", TierConfig(),
                 _tier_client(port, "mig-a")).start()
    eb = _engine(tiny_params, "mig-b", TierConfig(),
                 _tier_client(port, "mig-b")).start()
    try:
        shared = list(range(1, 3 * BT + 2))  # 3 full blocks + tail
        assert ea.generate(shared + [40], max_new_tokens=6) == \
            base.generate(shared + [40], max_new_tokens=6)
        assert _wait_published(ea, 3)
        for tail in ([], [41], [41, 42]):
            p = shared + tail
            assert eb.generate(p, max_new_tokens=6) == \
                base.generate(p, max_new_tokens=6), f"tail={tail}"
        st = eb.kv_stats()["tier"]
        assert st["migrated_tokens"] >= 3 * BT
        assert st["migration_failures"] == 0
        # Migrated tokens count as prefix hits — the same currency as
        # local prefix-cache reuse.
        assert eb.blocks.stats()["prefix_hit_tokens"] >= 3 * BT
    finally:
        base.stop(); ea.stop(); eb.stop()


@pytest.mark.slow  # ~7s
def test_prefetch_race_stall_is_counted_and_histogrammed(
        kv_world, tiny_params):
    """A delayed tier fetch the decode loop has to WAIT on is exactly
    one tier fault: counted, stall-histogrammed (the p99 contract
    surface), and harmless to the output."""
    _, port = kv_world
    base = _engine(tiny_params, "pf-base").start()
    ea = _engine(tiny_params, "pf-a", TierConfig(),
                 _tier_client(port, "pf-a")).start()
    eb = _engine(tiny_params, "pf-b", TierConfig(),
                 _tier_client(port, "pf-b")).start()
    try:
        shared = list(range(1, 3 * BT + 2))
        ref = base.generate(shared, max_new_tokens=4)
        assert ea.generate(shared, max_new_tokens=4) == ref
        assert _wait_published(ea, 3)
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("delay-tier-fetch", step=0, repeat=3,
                          param=0.05)]))
        assert eb.generate(shared, max_new_tokens=4) == ref
        snap = eb.metrics.snapshot()["tier"]
        assert eb.kv_stats()["tier"]["faults"] >= 1
        assert snap["faults"] >= 1
        assert snap["fault_stall"]["count"] >= 1
        assert snap["fault_stall"]["p50_ms"] > 0
    finally:
        fl.uninstall()
        base.stop(); ea.stop(); eb.stop()


@pytest.mark.slow  # ~8s
def test_drop_tier_block_train_degrades_to_recompute_bit_identical(
        kv_world, tiny_params):
    """Satellite soak: a drop train longer than the KV retry budget
    kills the migration fetch — the follower recomputes the prefix
    locally and the answer is BIT-IDENTICAL to the never-migrated
    run."""
    _, port = kv_world
    base = _engine(tiny_params, "drop-base").start()
    ea = _engine(tiny_params, "drop-a", TierConfig(),
                 _tier_client(port, "drop-a")).start()
    eb = _engine(tiny_params, "drop-b", TierConfig(),
                 _tier_client(port, "drop-b")).start()
    try:
        shared = list(range(1, 3 * BT + 2))
        ref = base.generate(shared, max_new_tokens=6)
        assert ea.generate(shared, max_new_tokens=6) == ref
        assert _wait_published(ea, 3)
        # retry_max=3 (kv_world): a train of 9 exhausts every block's
        # budget however the fetches interleave.
        fl.install(fl.FaultPlan(
            [fl.FaultSpec("drop-tier-block", step=0, repeat=9)]))
        assert eb.generate(shared, max_new_tokens=6) == ref
        st = eb.kv_stats()["tier"]
        assert st["migration_failures"] >= 1
        assert st["fetch_drops"] >= 3
        assert st["migrated_tokens"] == 0
    finally:
        fl.uninstall()
        base.stop(); ea.stop(); eb.stop()


def test_mark_dead_unpublishes_directory_entries(kv_world, tiny_params):
    """A dead replica's directory entries must not outlive it: after
    the mark_dead hook runs, a peer's fleet probe misses and admission
    plans NO migration toward the dead holder."""
    _, port = kv_world
    ea = _engine(tiny_params, "dead-a", TierConfig(),
                 _tier_client(port, "dead-a")).start()
    try:
        shared = list(range(1, 3 * BT + 2))
        ea.generate(shared, max_new_tokens=4)
        assert _wait_published(ea, 3)
        hashes = chain_hashes(shared, BT, salt=ea._prefix_salt(None))
        peer = TieredBlockManager(4, BT, TierConfig(),
                                  client=_tier_client(port, "dead-peer"))
        assert peer.remote_hits(hashes[:3]) == 3
        assert ea.tier_unpublish() == 3      # the mark_dead hook
        fresh = TieredBlockManager(4, BT, TierConfig(),
                                   client=_tier_client(port, "dead-p2"))
        assert fresh.remote_hits(hashes[:3]) == 0
    finally:
        ea.stop()


# -- batcher / surfaces -------------------------------------------------------

def test_batcher_peek_is_nonconsuming_and_copies(tiny_params):
    eng = _engine(tiny_params, "peek-t")
    b = eng.batcher
    b.submit(Request([1, 2, 3], max_new_tokens=1))
    b.submit(Request([4, 5], max_new_tokens=1))
    head = b.peek(8)
    assert [p for p, _ in head] == [[1, 2, 3], [4, 5]]
    head[0][0][0] = 99                       # caller mutation is local
    again = b.peek(1)
    assert again[0][0] == [1, 2, 3]
    assert len(b.drain()) == 2               # nothing was consumed


def test_tier_metrics_exposition(kv_world, tiny_params):
    _, port = kv_world
    eng = _engine(tiny_params, "met-t", TierConfig(),
                  _tier_client(port, "met-t")).start()
    try:
        eng.generate(list(range(1, 2 * BT + 2)), max_new_tokens=4)
        snap = eng.metrics.snapshot()
        assert "tier" in snap
        for key in ("faults", "fault_stall", "spill_bytes",
                    "promote_bytes", "demote_bytes", "migrations",
                    "migrated_tokens"):
            assert key in snap["tier"], key
        text = eng.metrics.render()
        for needle in ("hvd_serve_tier_fault_stall_ms",
                       "hvd_serve_tier_faults_total",
                       "hvd_serve_tier_bytes_total",
                       "hvd_serve_tier_migrations_total"):
            assert needle in text, needle
        stats = eng.kv_stats()
        assert stats["tier"]["published"] >= 0
        assert "inflight_peak" in stats["tier"]
    finally:
        eng.stop()
