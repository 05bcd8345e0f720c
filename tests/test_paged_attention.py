"""ISSUE 8: Pallas paged-attention decode kernel + quantized KV blocks.

Pins the tentpole's contracts layer by layer:

* kernel unit parity — ``paged_decode_attention`` /
  ``paged_prefill_attention`` vs the gather reference across every mask
  mode, block_tokens ∈ {8, 16}, pool geometries and table widths (the
  online softmax associates reductions blockwise, so parity is pinned at
  flash-kernel tolerance, and at exact token-stream level through the
  engine);
* the clip-mode hole hazard — ``jnp.take(..., mode="clip")`` clamps the
  hole sentinel onto the last REAL pool block, so correctness silently
  depends on the validity mask covering every clamped entry: a poisoned
  pool (garbage written into block NB-1) must leave outputs unchanged in
  BOTH impls, so a future mask regression fails loudly instead of
  corrupting decodes;
* engine parity — ``HVD_SERVE_ATTN_IMPL=kernel`` token streams equal the
  gather engine's bit-for-bit across block-boundary prompt lengths
  (k·BT, k·BT±1), jit-bucket transitions, chunked prefill, and the
  recovery paths (poisoned batch, pool-exhaustion preemption);
* quantized KV — int8 logit error within pinned cosine/abs tolerance vs
  bf16 storage, batched==single inside the int8 engine, prefix-cache
  hashing (token-content based) unaffected by storage dtype, and the
  bytes-per-block accounting the fixed-budget bench arm is built on;
* export surfaces — kv_bytes_per_token / attention-impl / kv-dtype
  gauges in the Prometheus exposition, replica ``to_dict``.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serve import (InferenceEngine, Request, ServeMetrics,
                               TransformerAdapter)
from horovod_tpu.serve import paged_attention as pa

BT = 8

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


def _engine(params, impl, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_chunk", 5)  # deliberately unaligned with BT
    ad = TransformerAdapter(_TINY, params, block_tokens=BT, attn_impl=impl,
                            kv_dtype=kw.pop("kv_dtype", None))
    return InferenceEngine(ad,
                           replica_id=f"pa-{impl}", **kw)


def _rand_pool(rng, NB, bt, H, Dh):
    return (jnp.asarray(rng.randn(NB, bt, H, Dh).astype(np.float32)),
            jnp.asarray(rng.randn(NB, bt, H, Dh).astype(np.float32)))


# -- kernel unit parity -------------------------------------------------------

@pytest.mark.parametrize("bt", [8, 16])
@pytest.mark.parametrize("geometry", [(6, 4), (9, 7), (3, 2)])
def test_decode_kernel_matches_gather_reference(bt, geometry):
    """Decode kernel vs the gather reference across pool sizes, table
    widths, and positions straddling block boundaries (k·BT, k·BT±1) —
    including hole-sentinel tables and an inactive (pos=0, all-hole)
    row, at flash-kernel tolerance."""
    NB, MB = geometry
    H, Dh = 2, 16
    rng = np.random.RandomState(NB * bt)
    kp, vp = _rand_pool(rng, NB, bt, H, Dh)
    B = 4
    q = jnp.asarray(rng.randn(B, H, Dh).astype(np.float32))
    tables = np.full((B, MB), NB, np.int32)
    perm = rng.permutation(NB)
    positions = []
    for b, pos in enumerate([bt - 1, bt, min(bt + 1, MB * bt - 1), 0]):
        nblk = pos // bt + 1
        tables[b, :min(nblk, NB)] = perm[:min(nblk, NB)]
        positions.append(pos)
    tables[3, :] = NB  # inactive row: all holes, pos 0
    positions = jnp.asarray(positions, jnp.int32)
    tables = jnp.asarray(tables)
    out = pa.paged_decode_attention(q, kp, vp, tables, positions)
    ref = pa.paged_attention_reference(q, kp, vp, tables, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("mask_mode",
                         [pa.MASK_NONE, pa.MASK_CAUSAL, pa.MASK_STRICT])
def test_prefill_kernel_matches_gather_reference_all_mask_modes(mask_mode):
    """Chunked-prefill kernel vs the gather reference under every mask
    mode of the shared machinery (the engine uses MASK_CAUSAL; STRICT
    and NONE stay available to ring-style consumers)."""
    NB, bt, MB, H, Dh, B, C = 6, 8, 4, 2, 16, 3, 5
    rng = np.random.RandomState(mask_mode)
    kp, vp = _rand_pool(rng, NB, bt, H, Dh)
    q = jnp.asarray(rng.randn(B, C, H, Dh).astype(np.float32))
    # Block NB-1 is deliberately referenced by NO table entry: every
    # read of it is a clamped hole, so the poisoned-pool invariance
    # check below can poison it without touching legitimate keys.
    tables = jnp.asarray(
        np.array([[0, 2, NB, NB], [1, 3, 4, NB], [2, NB, NB, NB]],
                 np.int32))
    starts = jnp.asarray(np.array([7, 15, 0], np.int32))
    out = pa.paged_prefill_attention(q, kp, vp, tables, starts,
                                     mask_mode=mask_mode)
    ref = pa.paged_attention_reference(q, kp, vp, tables, starts,
                                       mask_mode=mask_mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    if mask_mode == pa.MASK_STRICT:
        # Review finding: a row with EVERY key masked (row 2's first
        # query sits at absolute position 0 — strict mode attends
        # nothing) must contribute exactly 0 in BOTH impls, not a
        # weight-1 average of masked garbage (exp(NEG_INF - NEG_INF)
        # == 1 without the online-softmax floor).
        assert float(jnp.max(jnp.abs(out[2, 0]))) == 0.0
        assert float(jnp.max(jnp.abs(ref[2, 0]))) == 0.0
    # Review finding: hole sentinels are never real keys in ANY mask
    # mode — under MASK_NONE the positional mask doesn't cover them, so
    # both impls must mask holes by table entry: outputs are invariant
    # to the clamped block's contents.
    kp2 = kp.at[NB - 1].set(1e30)
    vp2 = vp.at[NB - 1].set(-1e30)
    out2 = pa.paged_prefill_attention(q, kp2, vp2, tables, starts,
                                      mask_mode=mask_mode)
    ref2 = pa.paged_attention_reference(q, kp2, vp2, tables, starts,
                                        mask_mode=mask_mode)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(ref2), np.asarray(ref))


def test_quantized_kernel_matches_quantized_gather_and_error_bound():
    """int8 (and fp8 where the build has it): the kernel's fused
    dequantization matches the dequantizing gather at kernel tolerance,
    and quantized attention stays within a pinned error of exact."""
    NB, bt, MB, H, Dh, B = 6, 8, 4, 4, 32, 3
    rng = np.random.RandomState(9)
    kp, vp = _rand_pool(rng, NB, bt, H, Dh)
    q = jnp.asarray(rng.randn(B, H, Dh).astype(np.float32))
    tables = jnp.asarray(
        np.array([[0, 2, 3, NB], [1, 4, NB, NB], [5, NB, NB, NB]],
                 np.int32))
    positions = jnp.asarray(np.array([25, 10, 7], np.int32))
    exact = pa.paged_attention_reference(q, kp, vp, tables, positions)
    for kvd in pa.KV_DTYPES:
        if kvd == "native":
            continue
        kq, ks = pa.quantize_kv(kp, kvd)
        vq, vs = pa.quantize_kv(vp, kvd)
        out = pa.paged_decode_attention(q, kq, vq, tables, positions,
                                        k_scale=ks, v_scale=vs)
        ref = pa.paged_attention_reference(q, kq, vq, tables, positions,
                                           k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5, err_msg=kvd)
        err = float(jnp.max(jnp.abs(out - exact)))
        assert err < 0.08, (kvd, err)  # ~1% of unit-variance outputs


def test_quantize_roundtrip_and_bytes_accounting():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 4, 16).astype(np.float32) * 3.0)
    q, s = pa.quantize_kv(x, "int8")
    assert q.dtype == jnp.int8 and s.shape == (5, 4)
    back = pa.dequantize_kv(q, s)
    # absmax/127 symmetric quantization: elementwise error <= scale/2
    # from rounding + up to 127 * 2^-11 * scale from the f16-stored
    # scale's own rounding (~0.56 total).
    assert float(jnp.max(jnp.abs(back - x)
                         / jnp.maximum(s.astype(jnp.float32)[..., None],
                                       1e-8))) <= 0.57
    # Zero rows survive (scale floors at eps instead of dividing by 0).
    qz, sz = pa.quantize_kv(jnp.zeros((2, 2, 8)), "int8")
    assert float(jnp.max(jnp.abs(pa.dequantize_kv(qz, sz)))) == 0.0
    # bytes-per-token: int8 payload + one f16 scale vs 2-byte bf16.
    assert pa.kv_bytes_per_token("int8", 64, jnp.bfloat16) == 64 + 2
    assert pa.kv_bytes_per_token("native", 64, jnp.bfloat16) == 128
    assert pa.kv_bytes_per_token("native", 64, jnp.float32) == 256


# -- the clip-mode hole hazard ------------------------------------------------

def _poison_last_block(eng):
    """Write extreme finite garbage into pool block NB-1 — the block the
    hole sentinel CLAMPS onto.  Finite (not NaN) on purpose: the
    contract is contribution-masking (clamped entries get softmax weight
    exactly 0), and 0 * NaN would poison even a correct mask — the
    regression must fail on mask regressions, not on IEEE NaN rules."""
    nb = eng.blocks.capacity
    garbage = 1e30
    cache = dict(eng._cache)
    for key in ("k", "v"):
        arr = cache[key]
        cache[key] = arr.at[:, nb - 1].set(
            jnp.full(arr.shape[1:][1:], garbage, arr.dtype))
    eng._cache = cache
    return nb


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_poisoned_pool_block_never_leaks_through_clip_mask(impl):
    """The poisoned-pool regression (ISSUE 8 satellite): garbage in the
    last REAL block — exactly where ``mode="clip"`` clamps every hole
    sentinel — must leave decode outputs unchanged in both impls.  The
    pool is sized so block NB-1 is never allocated (the free list hands
    out low ids first), so every read of it is a clamped hole read."""
    model, params = _tiny()
    prompt = np.random.RandomState(4).randint(0, 61, (2 * BT + 3,)).tolist()
    ref = _flax_greedy(model, params, prompt, 6)
    eng = _engine(params, impl, num_blocks=16).start()
    try:
        assert eng.generate(prompt, max_new_tokens=6) == ref
        nb = _poison_last_block(eng)
        # The poisoned block must still be unallocated (all reads of it
        # are clamped holes) — and stay so through the next request.
        assert eng.blocks.refcount(nb - 1) == 0
        assert eng.generate(prompt, max_new_tokens=6) == ref, \
            "clamped hole reads leaked into the output"
        assert eng.blocks.refcount(nb - 1) == 0
    finally:
        eng.stop()


# -- engine-level kernel-vs-gather parity -------------------------------------

@pytest.mark.slow  # ~30s sweep; batched-equals-single kernel parity stays
def test_kernel_engine_matches_gather_engine_at_block_boundaries():
    """Token-stream parity across prompt lengths straddling block and
    jit-bucket boundaries (k·BT, k·BT±1), chunk budget unaligned with
    BT — and both equal the flax recompute."""
    model, params = _tiny()
    g = _engine(params, "gather").start()
    k = _engine(params, "kernel").start()
    try:
        for plen in (BT - 1, BT, BT + 1, 2 * BT, 2 * BT + 1, 3):
            prompt = np.random.RandomState(plen).randint(
                0, 61, (plen,)).tolist()
            got_g = g.generate(prompt, max_new_tokens=5)
            got_k = k.generate(prompt, max_new_tokens=5)
            assert got_g == got_k, f"plen={plen}"
            assert got_k == _flax_greedy(model, params, prompt, 5), \
                f"plen={plen}"
    finally:
        g.stop()
        k.stop()


def test_kernel_engine_batched_equals_single():
    """The engine exactness contract holds under the kernel impl: a
    concurrent storm == the same prompts served alone, bit-for-bit."""
    _, params = _tiny()
    eng = _engine(params, "kernel", max_batch=8).start()
    try:
        prompts = [np.random.RandomState(i).randint(
            0, 61, (3 + (i * 5) % (2 * BT),)).tolist() for i in range(8)]
        singles = [eng.generate(p, max_new_tokens=5) for p in prompts]
        results = [None] * len(prompts)

        def run(i):
            results[i] = eng.generate(prompts[i], max_new_tokens=5)

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


def test_kernel_engine_poisoned_batch_recovery():
    """Poisoned-batch recovery under HVD_SERVE_ATTN_IMPL=kernel: the
    failed iteration's block refs are freed, the registry survives, and
    the replica keeps answering exactly."""
    model, params = _tiny()

    class _PoisonOnce:
        def __init__(self, inner):
            self._inner = inner
            self.armed = False
            for attr in ("vocab_size", "max_len", "block_tokens",
                         "kv_token_cost", "attn_impl", "kv_dtype"):
                setattr(self, attr, getattr(inner, attr))

        @property
        def max_blocks_per_seq(self):
            return self._inner.max_blocks_per_seq

        def paged_block_bytes(self):
            return self._inner.paged_block_bytes()

        def init_paged_cache(self, num_blocks, max_batch):
            return self._inner.init_paged_cache(num_blocks, max_batch)

        def prefill_chunk(self, cache, chunks, starts, tables):
            return self._inner.prefill_chunk(cache, chunks, starts, tables)

        def decode_paged(self, cache, tokens, positions, tables):
            if self.armed:
                self.armed = False
                raise RuntimeError("simulated device fault")
            return self._inner.decode_paged(cache, tokens, positions,
                                            tables)

        def copy_block(self, cache, src, dst):
            return self._inner.copy_block(cache, src, dst)

    ad = _PoisonOnce(TransformerAdapter(_TINY, params, block_tokens=BT,
                                        attn_impl="kernel"))
    eng = InferenceEngine(ad, max_batch=4,
                          prefill_chunk=64, replica_id="k-poison").start()
    try:
        shared = list(range(2 * BT))
        warm = eng.generate(shared + [3], max_new_tokens=4)
        assert warm == _flax_greedy(model, params, shared + [3], 4)
        ad.armed = True
        doomed = Request(shared + [9], max_new_tokens=8)
        eng.batcher.submit(doomed)
        with pytest.raises(RuntimeError, match="simulated device fault"):
            doomed.result(timeout=30)
        stats = eng.kv_stats()
        assert stats["used"] == 0
        assert stats["retained"] > 0  # registry survived
        assert eng.generate(shared + [3], max_new_tokens=4) == warm
    finally:
        eng.stop()


def test_kernel_engine_pool_exhaustion_preempts_youngest():
    """The defensive preemption path under the kernel impl (hand-built
    over-committed pool, same shape as the gather-path pin)."""
    _, params = _tiny()
    ad = TransformerAdapter(_TINY, params, block_tokens=BT,
                            attn_impl="kernel")
    eng = InferenceEngine(ad, max_batch=4, num_blocks=2,
                          prefill_chunk=64, replica_id="k-exhaust")
    from horovod_tpu.serve.engine import _Seq
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
    assert eng._slots[1] is None
    assert young_req.generated == [] and young_req.requeues == 1
    assert eng.metrics.snapshot()["requests"]["preempted"] == 1
    assert len(old_req.generated) == 2


# -- quantized KV through the engine ------------------------------------------

@pytest.mark.slow  # ~18s
def test_int8_engine_error_bounds_and_batched_equals_single():
    """int8 KV blocks: batched==single inside the int8 engine (the
    exactness contract at any storage dtype), and final logits within
    pinned cosine/abs tolerance of bf16 storage."""
    _, params = _tiny()
    ad8 = TransformerAdapter(_TINY, params, block_tokens=BT,
                             kv_dtype="int8")
    ad16 = TransformerAdapter(_TINY, params, block_tokens=BT,
                              kv_dtype="bf16")
    prompts = [np.random.RandomState(i).randint(
        0, 61, (5 + 3 * i,)).tolist() for i in range(4)]
    for p in prompts:
        l8 = ad8.prompt_logits(p)
        l16 = ad16.prompt_logits(p)
        cos = float(np.dot(l8, l16)
                    / (np.linalg.norm(l8) * np.linalg.norm(l16)))
        assert cos > 0.999, cos
        assert float(np.max(np.abs(l8 - l16))) < 0.05
    eng = InferenceEngine(ad8, max_batch=4,
                          prefill_chunk=5, replica_id="int8").start()
    try:
        singles = [eng.generate(p, max_new_tokens=5) for p in prompts]
        results = [None] * len(prompts)

        def run(i):
            results[i] = eng.generate(prompts[i], max_new_tokens=5)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == singles
    finally:
        eng.stop()


@pytest.mark.slow  # ~10s dtype sweep
def test_prefix_cache_hashing_unaffected_by_storage_dtype():
    """Prefix hashes are token-content based, so int8 storage reuses
    cached blocks exactly like bf16 — same hit tokens, identical output
    (a cached quantized block holds the same ints a re-prefill would
    write)."""
    _, params = _tiny()
    shared = np.random.RandomState(7).randint(0, 61, (2 * BT,)).tolist()
    hits = {}
    outs = {}
    for kvd in ("native", "int8"):
        eng = _engine(params, "gather", kv_dtype=kvd,
                      prefill_chunk=64).start()
        try:
            a = eng.generate(shared + [5], max_new_tokens=4)
            b = eng.generate(shared + [5], max_new_tokens=4)
            assert a == b  # cached-prefix decode == cold decode
            hits[kvd] = eng.kv_stats()["prefix_hit_tokens"]
            outs[kvd] = a
        finally:
            eng.stop()
    assert hits["native"] == hits["int8"] > 0
    # int8's token stream may differ from native's (logits shifted), but
    # on this prompt the argmax margin dominates the quantization noise:
    assert outs["native"] == outs["int8"]


def test_paged_block_bytes_matches_pool_and_manager():
    _, params = _tiny()
    # _TINY head_dim = 16: f32 native 64 B, bf16 32 B, int8 16+2 B per
    # (token, head) of K or V.
    for kvd, per_tok_head in (("native", 16 * 4), ("bf16", 16 * 2),
                              ("int8", 16 + 2)):
        ad = TransformerAdapter(_TINY, params, block_tokens=BT,
                                kv_dtype=kvd)
        expect = _TINY.num_layers * 2 * BT * _TINY.num_heads * per_tok_head
        assert ad.paged_block_bytes() == expect, kvd
        eng = InferenceEngine(ad, max_batch=2,
                              num_blocks=4, replica_id=f"bytes-{kvd}")
        stats = eng.kv_stats()
        assert stats["bytes_per_block"] == expect
        assert stats["kv_bytes_per_token"] == expect / BT
        assert stats["bytes_total"] == 4 * expect
        assert stats["kv_dtype"] == kvd
        # The device pool really is smaller under int8: sum of leaf
        # bytes tracks the accounting (scale rows included).
        pool = ad.init_paged_cache(4, 2)
        nbytes = sum(a.size * a.dtype.itemsize for a in pool.values())
        assert nbytes == 4 * expect, kvd


def test_fp8_engine_generates_when_supported():
    if "fp8" not in pa.KV_DTYPES:
        pytest.skip("no float8_e4m3fn in this jax build")
    model, params = _tiny()
    prompt = [3, 17, 42, 9, 11]
    eng = _engine(params, "gather", kv_dtype="fp8").start()
    try:
        out = eng.generate(prompt, max_new_tokens=4)
        assert out == _flax_greedy(model, params, prompt, 4)
    finally:
        eng.stop()


def test_knob_validation_errors():
    _, params = _tiny()
    with pytest.raises(ValueError, match="attn_impl"):
        TransformerAdapter(_TINY, params, attn_impl="fused")
    with pytest.raises(ValueError, match="kv_dtype"):
        TransformerAdapter(_TINY, params, kv_dtype="int4")
    with pytest.raises(ValueError, match="outside"):
        TransformerAdapter(_TINY, params).prompt_logits([])


def test_env_knob_resolution(monkeypatch):
    _, params = _tiny()
    monkeypatch.setenv("HVD_SERVE_ATTN_IMPL", "kernel")
    monkeypatch.setenv("HVD_SERVE_KV_DTYPE", "int8")
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    assert ad.attn_impl == "kernel" and ad.kv_dtype == "int8"
    monkeypatch.setenv("HVD_SERVE_ATTN_IMPL", "auto")
    ad = TransformerAdapter(_TINY, params, block_tokens=BT)
    # auto = kernel on TPU, gather elsewhere (this suite runs on CPU).
    assert ad.attn_impl == "gather"


# -- export surfaces ----------------------------------------------------------

def test_metrics_expose_kv_bytes_impl_and_dtype_gauges():
    _, params = _tiny()
    eng = _engine(params, "kernel", kv_dtype="int8").start()
    eng.metrics.register_kv_stats("pa-kernel", eng.kv_stats)
    try:
        eng.generate([1, 2, 3], max_new_tokens=3)
        snap = eng.metrics.snapshot()
        s = snap["kv_blocks"]["pa-kernel"]
        assert s["attn_impl"] == "kernel"
        assert s["kv_dtype"] == "int8"
        assert s["kv_bytes_per_token"] > 0
        text = eng.metrics.render()
        assert 'hvd_serve_kv_bytes_per_token{replica="pa-kernel"}' in text
        assert ('hvd_serve_attention_impl{replica="pa-kernel",'
                'impl="kernel"} 1') in text
        assert ('hvd_serve_kv_dtype{replica="pa-kernel",'
                'dtype="int8"} 1') in text
    finally:
        eng.stop()


def test_replica_to_dict_carries_impl_and_dtype():
    from horovod_tpu.serve import Replica
    _, params = _tiny()
    eng = _engine(params, "kernel", kv_dtype="int8")
    d = Replica("r0", None, eng).to_dict()
    assert d["attn_impl"] == "kernel"
    assert d["kv_dtype"] == "int8"
    assert d["kv_blocks"]["bytes_per_block"] == \
        eng.adapter.paged_block_bytes()
