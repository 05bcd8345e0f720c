"""Seeded sampling, speculative decoding, and CoW-forked n-best
(ISSUE 11): the serve engine's decode-algorithm layer.

Pins the revised exactness contract — **batched == single given the same
key** — and the four decode-algorithm properties the tentpole is judged
on:

* per-request seeded sampling is bit-reproducible at any batch
  composition, block-boundary prompt length, and replay;
* an n>1 request prefills its prompt ONCE and forks through the
  BlockManager's copy-on-write tables (shared prompt blocks counted
  once, fork count == n-1, zero leaked refs at completion);
* greedy speculative decoding is bit-identical to non-speculative
  greedy (and rolls rejected-draft block state back without leaks);
* sampled speculative decoding matches the target filtered distribution
  statistically (chi-square on a tiny vocab) — the Leviathan/Chen
  rejection-sampling guarantee.

HTTP-surface validation (per-field 400s, seed echo, n-best completions,
fork counters on /metrics + healthz) rides the same file.
"""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import create_mlp
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serve import (InferenceEngine, MLPAdapter, Replica,
                               ReplicaScheduler, Request, ServeMetrics,
                               ServeServer, TransformerAdapter)
from horovod_tpu.serve import sampling

BT = 8  # block_tokens used throughout (small, so boundaries are cheap)

_TINY = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=64, causal=True,
                          dtype=jnp.float32, scan_layers=False)


def _tiny(seed=0):
    model = Transformer(_TINY)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


_SHARED = {}


def _shared_adapter():
    """One draft-capable adapter shared by every default-params engine
    in this file: the per-bucket compile caches live on the adapter, so
    sharing it keeps the file's transformer compile cost to one set
    (a draft_layers=1 adapter serves plain greedy identically — the
    draft programs only run when an engine enables spec_k)."""
    if "ad" not in _SHARED:
        _, params = _tiny()
        _SHARED["params"] = params
        _SHARED["ad"] = TransformerAdapter(_TINY, params, block_tokens=BT,
                                           draft_layers=1)
    return _SHARED["ad"]


def _mlp_adapter(seed=3, vocab=13, max_len=128):
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return MLPAdapter(mlp, params, vocab_size=vocab, max_len=max_len)


def _engine(params=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 5)  # deliberately unaligned with BT
    kw.setdefault("metrics", ServeMetrics())
    draft = kw.pop("draft_layers", None)
    ad = kw.pop("adapter", None)
    if ad is None:
        ad = (TransformerAdapter(_TINY, params, block_tokens=BT,
                                 draft_layers=draft)
              if params is not None else _shared_adapter())
    kw.setdefault("replica_id", "sampling-t")
    return InferenceEngine(ad, **kw)


# -- validation (the /generate payload contract) -----------------------------

def test_validate_params_per_field_rejections():
    ok = sampling.validate_params(0.7, 5, 0.9, 2, 11)
    assert ok == (0.7, 5, 0.9, 2, 11)
    for bad in [(-0.1, None, 1.0, 1, None),       # temperature < 0
                (float("nan"), None, 1.0, 1, None),
                (0.5, 0, 1.0, 1, None),           # top_k < 1
                (0.5, -3, 1.0, 1, None),
                (0.5, 2.5, 1.0, 1, None),         # non-int top_k
                (0.5, None, 0.0, 1, None),        # top_p out of (0, 1]
                (0.5, None, 1.5, 1, None),
                (0.5, None, 1.0, 0, None),        # n < 1
                (0.5, None, 1.0, 1.5, None),      # non-int n
                (0.5, None, 1.0, 1, "abc"),       # non-int seed
                (0.5, None, 1.0, 1, 1.5),
                (0.5, None, 1.0, 1, True),        # bool is not a seed
                (True, None, 1.0, 1, None),       # ...nor a temperature
                (0.5, True, 1.0, 1, None),        # ...nor a top_k
                (0.5, None, True, 1, None),       # ...nor a top_p
                (0.5, None, 1.0, True, None)]:    # ...nor an n
        with pytest.raises((ValueError, TypeError)):
            sampling.validate_params(*bad)
    # A missing seed is ASSIGNED (the reproducibility handle is always
    # echoed), greedy stays the default.
    t, k, p, n, seed = sampling.validate_params(0.0, None, 1.0, 1, None)
    assert (t, k, p, n) == (0.0, None, 1.0, 1)
    assert isinstance(seed, int) and seed >= 0
    r = Request([1, 2], temperature=0.0)
    assert not r.sampled and isinstance(r.seed, int)
    assert r.samples is None  # n == 1 keeps the legacy surface
    assert Request([1, 2], temperature=0.3, n=2).samples == [None, None]


def test_filtered_probs_host_matches_traced_filter():
    """The host filter (speculative accept/resample reference) and the
    in-jit filter (sampled decode programs) must describe the SAME
    distribution — support and probabilities."""
    rng = np.random.RandomState(0)
    for temp, tk, tp in [(0.7, None, 1.0), (1.3, 4, 1.0),
                         (0.9, None, 0.6), (1.0, 5, 0.8)]:
        logits = rng.randn(17).astype(np.float32) * 2
        host = sampling.filtered_probs(logits, temp, tk, tp)
        traced = np.asarray(jax.nn.softmax(
            sampling._filter_logits_jnp(jnp.asarray(logits),
                                        jnp.float32(temp),
                                        jnp.int32(tk or 0),
                                        jnp.float32(tp))))
        assert (host > 0).tolist() == (traced > 1e-9).tolist()
        np.testing.assert_allclose(host, traced, atol=1e-5)


def test_spec_accept_resample_preserves_target_distribution():
    """Leviathan rejection with a point-mass (greedy) draft: accept the
    draft d with probability p[d], else draw the residual — the marginal
    must be exactly the filtered target distribution p.  Chi-square on a
    tiny vocab over many positions (deterministic: fixed seed keys)."""
    rng = np.random.RandomState(7)
    logits = rng.randn(6).astype(np.float32) * 1.5
    temp, tk, tp = 1.1, None, 0.95
    p = sampling.filtered_probs(logits, temp, tk, tp)
    d = int(np.argmax(logits))  # the greedy draft's proposal
    key = sampling.seq_key(1234, 0)
    N = 4000
    counts = np.zeros(len(p))
    for pos in range(N):
        if sampling.accept_draw(key, pos) < p[d]:
            counts[d] += 1
        else:
            counts[sampling.residual_sample(p, d, key, pos)] += 1
    expected = p * N
    live = expected > 0
    chi2 = float(((counts[live] - expected[live]) ** 2
                  / expected[live]).sum())
    # df <= 5; the 99.9th percentile of chi2(5) is 20.5 — a generous,
    # deterministic bound (fixed keys: this either always passes or
    # always fails).
    assert chi2 < 20.5, (chi2, counts, expected)
    assert counts[~live].sum() == 0  # nothing outside the support


# -- batched == single given the same key ------------------------------------

def test_batched_equals_single_given_same_key_at_block_boundaries():
    """Sampled requests at k*BT-1 / k*BT / k*BT+1 prompt lengths, mixed
    params, one greedy row riding along: the batched storm must emit
    bit-identical streams to each request run ALONE with the same seed
    (and the greedy row must match a greedy-only engine)."""
    rng = np.random.RandomState(1)
    rows = [
        (rng.randint(0, 61, size=(2 * BT - 1,)).tolist(),
         dict(temperature=0.8, seed=101)),
        (rng.randint(0, 61, size=(2 * BT,)).tolist(),
         dict(temperature=1.1, top_k=7, seed=102)),
        (rng.randint(0, 61, size=(2 * BT + 1,)).tolist(),
         dict(temperature=0.9, top_p=0.7, seed=103)),
        (rng.randint(0, 61, size=(2 * BT,)).tolist(),
         dict(temperature=0.0, seed=104)),          # greedy rides along
    ]
    new = 9  # crosses the next block boundary mid-decode
    batched_eng = _engine().start()
    reqs = [Request(p, max_new_tokens=new, **kw) for p, kw in rows]
    for r in reqs:
        batched_eng.batcher.submit(r)
    batched = [r.result(timeout=300) for r in reqs]
    batched_eng.stop()

    # A DIFFERENT engine (fresh pool, width-1 batches): cross-engine
    # replay exactness and batched==single in one storm.
    single_eng = _engine(replica_id="sampling-single").start()
    singles = [single_eng.generate(p, max_new_tokens=new, **kw)
               for p, kw in rows]
    assert batched == singles
    # Replay with the same seed reproduces; a different seed diverges.
    assert single_eng.generate(rows[0][0], max_new_tokens=new,
                               **rows[0][1]) == batched[0]
    other = single_eng.generate(rows[0][0], max_new_tokens=new,
                                temperature=0.8, seed=999)
    single_eng.stop()
    assert other != batched[0]


# -- n>1 CoW-forked n-best ---------------------------------------------------

def test_fork_shares_prompt_blocks_cow_counts_and_zero_leaks():
    n = 3
    prompt = list(np.random.RandomState(2).randint(
        0, 61, size=(2 * BT + 3,)))  # 2 full blocks + a partial
    eng = _engine(max_batch=8, num_blocks=32,
                  replica_id="fork-t").start()
    req = Request([int(t) for t in prompt], max_new_tokens=5,
                  temperature=0.9, n=n, seed=77)
    # Admission cost: the full prompt blocks are counted ONCE, each fork
    # privately owns only the partial tail + its decode region.
    base = eng._request_cost_blocks(Request([int(t) for t in prompt],
                                            max_new_tokens=5))
    cost = eng._request_cost_blocks(req)
    shared_full = len(prompt) // BT
    assert cost == base + (n - 1) * (base - shared_full)
    assert cost < n * base
    eng.batcher.submit(req)
    out = req.result(timeout=300)
    kv = eng.kv_stats()
    # CoW really engaged: n-1 forked sequences, each forking the shared
    # partial prompt block on its first divergent append.
    assert kv["seq_forks"] == n - 1
    assert kv["forked_requests"] == 1
    assert kv["cow"] >= n - 1
    # Peak pool footprint strictly below n independent sequences' cost.
    assert kv["used_peak"] <= cost < n * base
    # Zero leaked refs once the family retired (prefix-retained blocks
    # are refcount-0 by definition and excluded from `used`).
    assert kv["used"] == 0
    # All n completions present; sample 0 is the legacy surface; each
    # sample is bit-identical to a single run with the same (seed, i)
    # stream — sample 0 shares the request seed's stream exactly.
    assert len(req.samples) == n and all(s for s in req.samples)
    assert out == req.samples[0]
    single = eng.generate([int(t) for t in prompt], max_new_tokens=5,
                          temperature=0.9, seed=77)
    assert req.samples[0] == single
    eng.stop()


def test_fork_primary_finishing_first_never_aliases_blocks():
    """Review regression: the primary retiring on its FIRST token (n>1,
    max_new_tokens=1) must not free the shared prompt blocks before the
    other forks take their references — a ref on a free-listed block
    aliases it with the next allocation.  The BlockManager invariant
    free + retained + used == total (with used >= 0) detects the
    duplicate free-list entries deterministically."""
    eng = _engine(max_batch=8, num_blocks=32, prefix_cache=False,
                  replica_id="fork-first").start()
    prompt = [int(t) for t in
              np.random.RandomState(5).randint(0, 61, size=(BT + 3,))]
    req = Request(prompt, max_new_tokens=1, temperature=0.8, n=3, seed=11)
    eng.batcher.submit(req)
    req.result(timeout=300)
    assert all(len(s) == 1 for s in req.samples)
    kv = eng.kv_stats()
    assert kv["used"] == 0
    assert kv["free"] + kv["retained"] == kv["total"]
    # The pool still behaves after churn (no aliased allocations).
    out1 = eng.generate(prompt, max_new_tokens=4)
    out2 = eng.generate(prompt, max_new_tokens=4)
    assert out1 == out2
    kv = eng.kv_stats()
    assert kv["used"] == 0 and kv["free"] + kv["retained"] == kv["total"]
    eng.stop()


def test_retired_member_table_never_double_freed_on_group_preempt():
    """Review regression: a fork member that retires (EOS) leaves its
    FREED table cleared — a later pool-exhaustion preempt of a surviving
    member walks the whole family and must not free it again (a double
    free raises, or silently releases a reallocated block)."""
    from horovod_tpu.serve.engine import _ForkGroup, _Seq
    eng = _engine(max_batch=4, num_blocks=8, replica_id="retire-preempt")
    req = Request([1] * BT, max_new_tokens=4, n=2)
    group = _ForkGroup(req)
    members = []
    for i in range(2):
        m = _Seq(req, 0, eng.blocks.allocate(2), [], admit_seq=0)
        m.group = group
        m.sample_index = i
        m.generated = [7]
        m.length = BT
        m.prompt_pos = BT
        group.seqs.append(m)
        members.append(m)
    group.forked = True
    eng._slots[0], eng._slots[1] = members
    with eng._lock:
        eng._retire_seq(0, members[0])  # one fork hits EOS and retires
    assert members[0].table == []       # freed AND cleared
    eng._preempt(1, members[1])         # exhaustion later picks the family
    kv = eng.kv_stats()
    assert kv["used"] == 0
    assert kv["free"] + kv["retained"] == kv["total"]
    assert req.requeues == 1


def test_fork_tail_reservation_blocks_over_admission():
    """Review regression: the (n-1) fork tails admission COUNTS but does
    not allocate stay RESERVED across admission rounds — a later round
    must not hand those blocks to another request (which would turn
    pool-exhaustion preemption into a steady-state tax on every n>1
    request).  With the reservation, both requests complete with ZERO
    preemptions."""
    eng = _engine(max_batch=8, num_blocks=5, prefix_cache=False,
                  replica_id="reserve-t").start()
    prompt = [int(t) for t in
              np.random.RandomState(6).randint(0, 61, size=(12,))]
    # cost = base 3 (24 positions) + 1 tail * (3 - 1 shared full) = 5:
    # exactly the pool; the fork tail (2 blocks) is reserved, the
    # competitor (2 blocks) must WAIT for the family instead of
    # stealing the reservation.
    big = Request(prompt, max_new_tokens=12, temperature=0.7, n=2, seed=1)
    small = Request([1] * BT, max_new_tokens=8)
    eng.batcher.submit(big)
    eng.batcher.submit(small)
    assert len(big.result(timeout=300)) == 12
    assert len(small.result(timeout=300)) == 8
    snap = eng.metrics.snapshot()
    assert snap["requests"]["preempted"] == 0, snap["requests"]
    kv = eng.kv_stats()
    assert kv["used"] == 0
    assert kv["free"] + kv["retained"] == kv["total"]
    eng.stop()


def test_pool_exhaustion_preempts_whole_fork_group():
    """A fork family is preempted as ONE unit: every member's blocks
    freed, every member slot cleared, the request requeued once."""
    from horovod_tpu.serve.engine import _ForkGroup, _Seq
    eng = _engine(max_batch=4, num_blocks=3,
                  replica_id="exhaust-fork")
    old_req = Request([1] * BT, max_new_tokens=4)
    old_req.generated = [5]
    old = _Seq(old_req, 0, eng.blocks.allocate(2), [], admit_seq=0)
    old.length = BT
    old.prompt_pos = BT
    # The YOUNGEST sequences: a 2-way fork family holding one block.
    fork_req = Request([2] * BT, max_new_tokens=4, n=2)
    group = _ForkGroup(fork_req)
    members = []
    for i in range(2):
        m = _Seq(fork_req, 0, eng.blocks.allocate(1) if i == 0 else [],
                 [], admit_seq=1)
        m.group = group
        m.sample_index = i
        m.generated = [7]
        m.length = BT
        m.prompt_pos = BT
        group.seqs.append(m)
        members.append(m)
    eng._slots[0] = old
    eng._slots[1], eng._slots[2] = members
    group.forked = True
    fork_req.samples = [None, None]
    eng._decode_once()
    # The whole family lost its slots and its block; the request sits
    # requeued ONCE with progress reset; the old sequence decoded on.
    assert eng._slots[1] is None and eng._slots[2] is None
    assert fork_req.requeues == 1
    assert fork_req.samples == [None, None]
    assert all(m.table == [] for m in members)
    assert eng.batcher.depth() == 1
    assert eng.metrics.snapshot()["requests"]["preempted"] == 1
    assert eng.blocks.stats()["used"] == 2  # only the old seq's blocks
    assert len(old_req.generated) == 2


# -- speculative decoding ----------------------------------------------------

def test_spec_greedy_equals_greedy_across_bucket_boundaries():
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 61, size=(L,)).tolist()
               for L in (BT - 1, BT, BT + 1, 2 * BT)]
    new = 10  # crosses block boundaries mid-decode
    plain = _engine(replica_id="plain-g").start()
    base = [plain.generate(p, max_new_tokens=new) for p in prompts]
    plain.stop()
    spec = _engine(spec_k=4, replica_id="spec-g").start()
    reqs = [Request(p, max_new_tokens=new) for p in prompts]
    for r in reqs:
        spec.batcher.submit(r)
    outs = [r.result(timeout=300) for r in reqs]
    snap = spec.metrics.snapshot()
    spec.stop()
    assert outs == base  # bit-identical, batched spec vs single plain
    # The draft/verify machinery really ran and is observable.
    assert snap["spec"]["steps"] > 0
    assert snap["spec"]["drafted"] > 0
    assert snap["spec"]["drafted"] == (snap["spec"]["accepted"]
                                       + snap["spec"]["rejected"])
    assert snap["stage"]["spec"]["count"] >= len(prompts)
    assert snap["spec"]["acceptance_rate"] > 0


def test_spec_rejection_rollback_leaks_zero_refs():
    """Force draft/target divergence (amplified late-layer weights) so
    rejections actually fire, then pin: greedy spec still bit-equals
    greedy, and a rejected draft's extended block-table state rolls back
    with zero leaked refs (pool used == 0 after completion)."""
    _, params = _tiny()
    params = dict(params)
    params["block_1"] = jax.tree.map(lambda a: a * 6.0,
                                     params["block_1"])
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 61, size=(BT + 2,)).tolist()
               for _ in range(3)]
    new = 12
    amp_ad = TransformerAdapter(_TINY, params, block_tokens=BT,
                                draft_layers=1)
    plain = _engine(adapter=amp_ad, replica_id="plain-r").start()
    base = [plain.generate(p, max_new_tokens=new) for p in prompts]
    plain.stop()
    spec = _engine(adapter=amp_ad, spec_k=4,
                   replica_id="spec-r").start()
    outs = [spec.generate(p, max_new_tokens=new) for p in prompts]
    snap = spec.metrics.snapshot()
    kv = spec.kv_stats()
    spec.stop()
    assert outs == base
    assert snap["spec"]["rejected"] > 0, snap["spec"]  # divergence real
    assert kv["used"] == 0  # rejected-draft rollback left nothing behind


def test_spec_sampled_matches_nonspec_sampled_distribution():
    """Sampled speculation preserves the target process distribution:
    the empirical distribution of full sampled sequences under spec must
    match non-spec sampling (two-sample chi-square over a tiny vocab —
    the draws differ mechanically, the law must not).  Deterministic:
    fixed seed set."""
    ad = _mlp_adapter(vocab=7)
    seeds = list(range(5000, 5400))

    def storm(spec_k):
        from horovod_tpu.serve import DynamicBatcher
        eng = InferenceEngine(ad, max_batch=8,
                              batcher=DynamicBatcher(max_queue=1024),
                              metrics=ServeMetrics(), spec_k=spec_k,
                              replica_id=f"dist-{spec_k}").start()
        reqs = [Request([1, 2], max_new_tokens=2, temperature=1.2,
                        top_k=4, seed=s) for s in seeds]
        for r in reqs:
            eng.batcher.submit(r)
        outs = [tuple(r.result(timeout=300)) for r in reqs]
        eng.stop()
        return outs

    plain = storm(0)
    spec = storm(3)
    outcomes = sorted(set(plain) | set(spec))
    c1 = np.array([sum(o == x for o in plain) for x in outcomes], float)
    c2 = np.array([sum(o == x for o in spec) for x in outcomes], float)
    # Two-sample chi-square with pooled expectations.
    pooled = (c1 + c2) / 2
    live = pooled > 0
    chi2 = float((((c1 - pooled) ** 2 + (c2 - pooled) ** 2)
                  / pooled)[live].sum())
    df = int(live.sum()) - 1
    # 99.9th percentile of chi2(df) is under df + 4*sqrt(2*df) + 11 for
    # the df range here — a generous deterministic bound.
    assert chi2 < df + 4 * (2 * df) ** 0.5 + 11, (chi2, df, outcomes)


# -- HTTP surface ------------------------------------------------------------

def _serve_http():
    eng = InferenceEngine(_mlp_adapter(), max_batch=4,
                          metrics=ServeMetrics(), replica_id="replica-0")
    sched = ReplicaScheduler([Replica("replica-0", None, eng)],
                             metrics=eng.metrics).start()
    server = ServeServer(sched)
    port = server.start(port=0, host="127.0.0.1")
    return server, sched, port


def _post(port, payload, timeout=60):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def test_http_per_field_400s_seed_echo_and_fork_counters():
    server, sched, port = _serve_http()
    try:
        # Per-field strict validation → HTTP 400, each field alone.
        for bad in [{"temperature": -1}, {"temperature": "hot"},
                    {"top_k": 0}, {"top_k": 2.5}, {"top_p": 0},
                    {"top_p": 1.5}, {"n": 0}, {"n": "two"},
                    {"seed": "abc"}, {"seed": 1.5}, {"seed": True}]:
            payload = {"tokens": [1, 2, 3], "max_new_tokens": 3, **bad}
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(port, payload)
            assert e.value.code == 400, bad
        # The effective seed is echoed on EVERY response; replaying it
        # reproduces a sampled answer bit-for-bit (e2e exactness).
        out = _post(port, {"tokens": [1, 2, 3], "max_new_tokens": 6,
                           "temperature": 0.9})
        assert isinstance(out["seed"], int)
        replay = _post(port, {"tokens": [1, 2, 3], "max_new_tokens": 6,
                              "temperature": 0.9, "seed": out["seed"]})
        assert replay["tokens"] == out["tokens"]
        assert replay["seed"] == out["seed"]
        greedy = _post(port, {"tokens": [1, 2, 3], "max_new_tokens": 3})
        assert isinstance(greedy["seed"], int)  # greedy echoes too
        # n>1: all n completions in the response, sample 0 mirrored on
        # the legacy tokens field, and the fork counters visible on
        # /metrics + healthz from this first forked request.
        nbest = _post(port, {"tokens": [1, 2, 3], "max_new_tokens": 4,
                             "temperature": 1.0, "n": 3, "seed": 9})
        assert nbest["n"] == 3
        assert len(nbest["completions"]) == 3
        assert nbest["tokens"] == nbest["completions"][0]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert 'hvd_serve_cow_forks_total{replica="replica-0"} 2' in text
        assert ('hvd_serve_forked_requests_total{replica="replica-0"} 1'
                in text)
        assert "hvd_serve_spec_tokens_total" in text
        health = sched.healthz()
        kvb = health["replicas"][0]["kv_blocks"]
        assert kvb["seq_forks"] == 2
        assert kvb["forked_requests"] == 1
        assert kvb["spec_k"] == 0
        snap = sched.metrics.snapshot()
        assert snap["seq_forks"] == 2
    finally:
        server.stop()
        sched.stop()


def test_drain_resets_fork_family_once():
    """A drained n>1 request travels as ONE unit: returned once, with
    samples and generated progress cleared for clean resubmission."""
    eng = _engine(max_batch=8, num_blocks=32, replica_id="drain-f")
    from horovod_tpu.serve.engine import _ForkGroup, _Seq
    req = Request([1] * (BT + 2), max_new_tokens=4, temperature=0.5,
                  n=2, seed=3)
    group = _ForkGroup(req)
    for i in range(2):
        m = _Seq(req, 0, eng.blocks.allocate(1), [], admit_seq=i)
        m.group = group
        m.sample_index = i
        m.generated = [4 + i]
        group.seqs.append(m)
        eng._slots[i] = m
    group.forked = True
    req.samples = [[9], None]
    inflight = eng.drain()
    assert inflight == [req]  # once, not per member
    assert req.samples == [None, None]
    assert req.generated == [] and req.requeues == 1
    assert eng.blocks.stats()["used"] == 0
