#!/usr/bin/env python
"""Synthetic ResNet-50 training benchmark — the reference's headline harness.

Mirrors examples/pytorch/pytorch_synthetic_benchmark.py /
examples/tensorflow2/tensorflow2_synthetic_benchmark.py:25-80: ResNet-50,
synthetic ImageNet-shaped data, full training steps (forward + backward +
DistributedOptimizer update), reports images/sec.  Batch 128/chip: the v5e
plateaus there (measured sweep 32->1665, 64->1711, 128->1949 img/s); the
reference harness's bs-32-per-GPU convention was sized for 16 GB Pascals.

Baseline: the reference's published absolute number is 1656.82 images/sec on
16 Pascal GPUs (docs/benchmarks.rst:40-42) → 103.55 images/sec/GPU;
``vs_baseline`` is images/sec-per-chip against that.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models import create_resnet50  # noqa: E402

BATCH_PER_CHIP = 128
WARMUP = 5
ITERS = 30
BASELINE_IMG_S_PER_DEV = 1656.82 / 16  # docs/benchmarks.rst:40-42
# Defaults of the knobs the arms read, in one place.
KNOB_DEFAULTS = {
    "BENCH_BERT_BATCH": "32",
    "BENCH_BERT_ATTN": "auto",
    "BENCH_BERT_MLMPOS": "20",
    "BENCH_GPT2_BATCH": "8",
    "BENCH_SERVE_REQUESTS": "64",
    "BENCH_SERVE_NEWTOKENS": "32",
    "BENCH_SERVE_REPLICAS": "2",
    "HVD_SERVE_BLOCK_TOKENS": "16",
    "HVD_SERVE_PREFILL_CHUNK": "64",
    "HVD_SERVE_PREFIX_CACHE": "1",
    "HVD_SERVE_DRAFT_LAYERS": "0",
    "BENCH_SERVE_SPEC_K": "4",
    "BENCH_SERVE_SAMPLE_TEMP": "0.8",
    "BENCH_SERVE_SLO_MS": "15000",
    "HVD_FAULTLINE_SEED": "0",
    "BENCH_SERVE_STREAM_SESSIONS": "6",
    "BENCH_SERVE_STREAM_TEMP": "0.8",
}


def _emit(record):
    """Print the one-JSON-line contract, tagged with the device the
    numbers were taken on: a figure from a CPU run is a count or a
    correctness check, never a device metric."""
    devices = jax.devices()
    print(json.dumps(dict(record, platform=devices[0].platform,
                          device_kind=devices[0].device_kind,
                          device_count=len(devices))), flush=True)


def bench_gpt2():
    """BENCH_MODEL=gpt2-medium (BASELINE config 4: GPT-2 medium with
    Adasum): samples/sec over the same one-JSON-line contract.
    scan_layers cuts the 24-layer compile ~12x, and per-slice Adasum
    keeps the reference's per-layer coefficient granularity through the
    stacked layout (examples/gpt2_adasum.py)."""
    import contextlib
    from examples.gpt2_adasum import main as gpt2_main
    model = os.environ.get("BENCH_MODEL", "gpt2-medium")
    size = model.split("-", 1)[1] if "-" in model else "medium"
    bs = os.environ.get("BENCH_GPT2_BATCH",
                        KNOB_DEFAULTS["BENCH_GPT2_BATCH"])
    argv = ["--size", size, "--steps", "10", "--batch-per-slot", bs,
            "--seq-len", "128"]
    with contextlib.redirect_stdout(sys.stderr):  # keep stdout = 1 JSON line
        losses, samples_s = gpt2_main(argv)
    _emit({
        "metric": f"gpt2_{size}_adasum_samples_per_sec",
        "value": round(samples_s, 2),
        "unit": "samples/sec",
        "vs_baseline": round(samples_s / hvd.num_slots(), 3),
        "config": f"bs{bs}/slot seq128 adasum(per-layer) remat scan-layers",
    })


def bench_bert():
    """BENCH_MODEL=bert-large: BERT-large MLM samples/sec (BASELINE config 3).
    Keeps the same one-JSON-line contract; the reference publishes no BERT
    number, so vs_baseline reports per-chip samples/sec directly."""
    import contextlib
    from examples.bert_pretraining import main as bert_main
    bs = os.environ.get("BENCH_BERT_BATCH",
                        KNOB_DEFAULTS["BENCH_BERT_BATCH"])
    attn = os.environ.get("BENCH_BERT_ATTN",
                          KNOB_DEFAULTS["BENCH_BERT_ATTN"])
    mlm_pos = os.environ.get("BENCH_BERT_MLMPOS",
                             KNOB_DEFAULTS["BENCH_BERT_MLMPOS"])
    argv = ["--size", "large", "--steps", "10", "--batch-per-slot", bs,
            "--seq-len", "128", "--attention", attn,
            "--mlm-positions", mlm_pos]
    with contextlib.redirect_stdout(sys.stderr):  # keep stdout = 1 JSON line
        losses, samples_s = bert_main(argv)
    _emit({
        "metric": "bert_large_mlm_samples_per_sec",
        "value": round(samples_s, 2),
        "unit": "samples/sec",
        "vs_baseline": round(samples_s / hvd.num_slots(), 3),
        # Not comparable across configs: round-1/2 records used bs 8 with
        # remat on and the full-sequence LM head; this records the actual
        # measurement setup.
        "config": f"bs{bs}/slot seq128 accum2 no-remat attn-{attn} "
                  f"mlmpos{mlm_pos}",
    })


def bench_ring():
    """BENCH_MODEL=ring: sequence-parallel ring-attention microbench.

    Times full fwd+bwd ring_attention steps on the hvd mesh across the
    schedule/layout matrix — contiguous-causal serial (the legacy
    compute-then-rotate order), contiguous-causal overlapped (double-
    buffered ppermute + true skip of above-diagonal hops), striped-causal
    overlapped, and non-causal overlapped — and reports the overlapped
    causal path, with serial/overlap as ``vs_baseline`` (>= 1.0 means the
    overlapped+skip schedule is no slower, the ISSUE 1 acceptance bar).
    Also times a single K/V rotation and a single hop-sized attention fold
    in isolation, attributing step time to transfer vs kernel; with
    HOROVOD_TIMELINE set the traced RING_HOP schedule lands in the trace."""
    from jax.sharding import PartitionSpec as P2
    from horovod_tpu.parallel import ring as ring_mod

    n = hvd.num_slots()
    mesh = hvd.mesh()
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    B, s_local, H, D = (1, 16, 2, 16) if smoke else (1, 128, 4, 64)
    warm, iters = (1, 2) if smoke else (3, 10)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, s_local * n, H, D).astype(np.float32) * 0.3)

    tl = None
    if os.environ.get("HOROVOD_TIMELINE"):
        from horovod_tpu import core as _core
        # hvd.init() already opened the HOROVOD_TIMELINE writer (rank 0);
        # reuse it — a second Timeline on the same path would interleave
        # two JSON streams.  stop_timeline() below flushes and closes.
        tl = _core._state.timeline
        if tl is not None:
            ring_mod.set_ring_timeline(tl, "ring_microbench")

    def sp_step(schedule, causal, striped):
        def f(qq, kk, vv):
            def loss(qq):
                return jnp.mean(ring_mod.ring_attention(
                    qq, kk, vv, axis_name="hvd", causal=causal,
                    striped=striped, schedule=schedule) ** 2)
            return jax.grad(loss)(qq)
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P2(None, "hvd"),) * 3,
            out_specs=P2(None, "hvd")))

    def timeit(step, *args):
        out = None
        for _ in range(warm):
            out = step(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    times = {name: round(timeit(sp_step(*cfg), q, q, q), 3)
             for name, cfg in (
                 ("contiguous_causal_serial", ("serial", True, False)),
                 ("contiguous_causal_overlap", ("overlap", True, False)),
                 ("striped_causal_overlap", ("overlap", True, True)),
                 ("full_overlap", ("overlap", False, False)))}

    # Kernel-vs-transfer attribution: one K/V rotation and one hop-sized
    # local attention fold, timed in isolation.
    perm = [(i, (i - 1) % n) for i in range(n)]
    transfer = jax.jit(jax.shard_map(
        lambda kk, vv: (jax.lax.ppermute(kk, "hvd", perm),
                        jax.lax.ppermute(vv, "hvd", perm)),
        mesh=mesh, in_specs=(P2(None, "hvd"),) * 2,
        out_specs=(P2(None, "hvd"),) * 2))
    kernel = jax.jit(jax.shard_map(
        lambda qq, kk, vv: ring_mod.ring_attention_reference(qq, kk, vv),
        mesh=mesh, in_specs=(P2(None, "hvd"),) * 3,
        out_specs=P2(None, "hvd")))
    t_transfer = round(timeit(transfer, q, q), 4)
    t_kernel = round(timeit(kernel, q, q, q), 4)

    if tl is not None:
        ring_mod.set_ring_timeline(None)
        hvd.stop_timeline()

    serial = times["contiguous_causal_serial"]
    overlap = times["contiguous_causal_overlap"]
    _emit({
        "metric": "ring_sp_causal_ms_per_step",
        "value": overlap,
        "unit": "ms/step",
        "vs_baseline": round(serial / max(overlap, 1e-9), 3),
        "config": f"n={n} B{B} Slocal{s_local} H{H} D{D} f32 fwd+bwd "
                  f"overlap+skip vs serial" + (" SMOKE" if smoke else ""),
        "variants": times,
        "per_hop": {"transfer_ms": t_transfer, "kernel_ms": t_kernel},
    })


def bench_serve():
    """BENCH_MODEL=serve: continuous-batching serving microbench
    (horovod_tpu/serve, docs/serving.md).

    Main storm: the replica scheduler over process sets under concurrent
    generation load through the real batcher/engine path (HTTP is
    exercised by tests/test_serve_e2e.py; the bench measures the decode
    plane) — aggregate tokens/sec, TTFT / per-output-token latency split,
    achieved batch occupancy.

    The arms, each with the identical prompts run on both engine configs
    so exactness is checked in-band:

    * ``paged``   — a mixed-length storm at a FIXED cache-memory budget
      (4 × max_len token positions): concurrent sequences admitted +
      tokens/s, batched against each prompt alone;
    * ``chunked`` — decode token_step p99 while max_len prompts prefill,
      chunked (``HVD_SERVE_PREFILL_CHUNK``) vs unchunked;
    * ``prefix``  — shared-prefix storm: prefix-cache hit rate and block
      allocations saved;
    * ``kernel``  — gather vs the Pallas paged-attention kernel at an
      identical config (ISSUE 8): in-band token-stream exactness, decode
      token_step p50/p99 and tokens/s for both impls.  Off-TPU the
      kernel runs under the Pallas interpreter (``interpret`` recorded
      in-band), which checks exactness and times nothing of the chip;
    * ``kv_dtype`` — bf16 vs int8 block storage at a FIXED HBM budget in
      BYTES (bytes-per-block accounting from the BlockManager):
      admit_ratio of concurrent sequences, max final-logit error vs the
      bf16 engine, and batched==single exactness WITHIN the int8 engine
      (quantization changes logits, so the int8 engine's own
      single-request run is its reference);
    * ``trace``    — request-tracing overhead (ISSUE 9): the identical
      storm with the hvdtrace tracer absent (sample=0, the zero-
      overhead contract — acceptance: ≤2% tokens/s regression, tracked
      against the record's main trajectory) vs installed at sample=1
      with shard files written, with in-band exactness;
    * ``spec``     — speculative decoding (ISSUE 11): the identical
      greedy storm non-spec vs spec (truncated-stack draft,
      ``BENCH_SERVE_SPEC_K``): in-band bit-exactness plus the
      amortization statistic target-model decode invocations per
      emitted token (acceptance: ≤ 0.67 at k=4);
    * ``sampling`` — seeded sampling (ISSUE 11): the identical sampled
      storm (fixed per-request seeds) run twice must produce identical
      outputs, and an n=4 CoW-forked n-best request's peak pool bytes
      must sit strictly below 4x the n=1 footprint (prompt blocks
      shared through the BlockManager's copy-on-write tables);
    * ``stream``   — token streaming (ISSUE 19): the same prompts
      buffered then streamed over SSE — streamed-concat == buffered is
      hard, client-perceived TTFT p50/p99 vs the buffered wait,
      inter-token p99, a mid-stream hangup must free every KV block,
      and grammar-constrained sampled completions must be 100%
      schema-valid."""
    import threading
    from horovod_tpu.models.transformer import (Transformer,
                                                TransformerConfig)
    from horovod_tpu.serve import (InferenceEngine, Request, ServeMetrics,
                                   TransformerAdapter, build_replicas)

    smoke = os.environ.get("BENCH_SMOKE") == "1"
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                    KNOB_DEFAULTS["BENCH_SERVE_REQUESTS"]))
    new_tokens = int(os.environ.get("BENCH_SERVE_NEWTOKENS",
                                    KNOB_DEFAULTS["BENCH_SERVE_NEWTOKENS"]))
    replicas = int(os.environ.get("BENCH_SERVE_REPLICAS",
                                  KNOB_DEFAULTS["BENCH_SERVE_REPLICAS"]))
    block_tokens = int(os.environ.get(
        "HVD_SERVE_BLOCK_TOKENS", KNOB_DEFAULTS["HVD_SERVE_BLOCK_TOKENS"]))
    chunk = int(os.environ.get(
        "HVD_SERVE_PREFILL_CHUNK",
        KNOB_DEFAULTS["HVD_SERVE_PREFILL_CHUNK"]))
    budget_rows = 4  # arm 1's pool: this many full-length sequences
    prefix_on = os.environ.get(
        "HVD_SERVE_PREFIX_CACHE",
        KNOB_DEFAULTS["HVD_SERVE_PREFIX_CACHE"]) not in ("0", "false")
    if smoke:
        n_requests, new_tokens = min(n_requests, 16), min(new_tokens, 8)
        budget_rows, chunk = 2, min(chunk, 8)
    cfg = TransformerConfig(
        vocab_size=256, causal=True, dtype=jnp.float32, scan_layers=False,
        **({"num_layers": 2, "num_heads": 2, "d_model": 64, "d_ff": 128,
            "max_len": 64} if smoke else
           {"num_layers": 4, "num_heads": 4, "d_model": 256, "d_ff": 1024,
            "max_len": 256}))
    model = Transformer(cfg)
    rng = np.random.RandomState(0)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [rng.randint(0, 256, size=(int(rng.randint(4, 24)),)).tolist()
               for _ in range(n_requests)]
    # One adapter per replica, SHARED across the warm and measured
    # schedulers: their prefill/decode compile caches live on the adapter,
    # so running the identical storm once first compiles every (count,
    # prompt-length) bucket the workload can hit — a single warm request
    # would leave most buckets to compile inside the timed window.
    adapters = [TransformerAdapter(cfg, params, block_tokens=block_tokens)
                for _ in range(replicas)]

    def run_storm(sched):
        requests = [Request(p, max_new_tokens=new_tokens) for p in prompts]
        for r in requests:
            sched.submit(r)
        return [r.result(timeout=600) for r in requests]

    it = iter(adapters)
    warm_sched = build_replicas(lambda: next(it), num_replicas=replicas,
                                metrics=ServeMetrics())
    warm_sched.start()
    run_storm(warm_sched)
    warm_sched.stop()

    metrics = ServeMetrics()
    from horovod_tpu import core as _core
    if _core._state.timeline is not None:
        metrics.set_timeline(_core._state.timeline)
    it = iter(adapters)
    sched = build_replicas(lambda: next(it), num_replicas=replicas,
                           metrics=metrics)
    sched.start()
    metrics.started_at = time.monotonic()
    t0 = time.perf_counter()
    outs = run_storm(sched)
    dt = time.perf_counter() - t0
    sched.stop()
    total_tokens = sum(len(o) for o in outs)
    snap = metrics.snapshot()

    def engine_storm(engine, storm_prompts, toks):
        reqs = [Request(p, max_new_tokens=toks) for p in storm_prompts]
        for r in reqs:
            engine.batcher.submit(r)
        return [r.result(timeout=600) for r in reqs]

    def timed_storm(make_engine, storm_prompts, toks):
        """Warm run (compiles every bucket on the shared adapter), then
        the measured run on a fresh engine; returns (outs, dt, snapshot,
        kv stats)."""
        warm = make_engine().start()
        engine_storm(warm, storm_prompts, toks)
        warm.stop()
        eng = make_engine().start()
        eng.metrics.started_at = time.monotonic()
        t0 = time.perf_counter()
        outs = engine_storm(eng, storm_prompts, toks)
        dt = time.perf_counter() - t0
        stats = eng.kv_stats()
        eng.stop()
        return outs, dt, eng.metrics.snapshot(), stats

    # -- arm 1: a mixed-length storm at a FIXED cache-memory budget -----------
    # Budget = budget_rows × max_len token positions, shared as blocks:
    # the mixed-(short-)length storm packs many more concurrent sequences
    # into it than budget_rows full-length reservations would.
    budget_tokens = budget_rows * cfg.max_len
    mixed_prompts = [rng.randint(0, 256, size=(
        int(rng.randint(4, max(6, cfg.max_len // 4))),)).tolist()
        for _ in range(n_requests)]
    paged_adapter = TransformerAdapter(cfg, params,
                                       block_tokens=block_tokens)

    def paged_engine():
        # 4x the budget's rows: enough for the block-bound concurrency
        # the mixed storm reaches.
        return InferenceEngine(paged_adapter,
                               max_batch=min(budget_rows * 4, 64),
                               num_blocks=budget_tokens // block_tokens,
                               prefill_chunk=chunk, prefix_cache=prefix_on,
                               metrics=ServeMetrics(),
                               replica_id="bench-paged")

    paged_outs, paged_dt, paged_snap, _ = timed_storm(
        paged_engine, mixed_prompts, new_tokens)
    single = paged_engine().start()
    single_outs = [single.generate(p, max_new_tokens=new_tokens)
                   for p in mixed_prompts]
    single.stop()
    arm_paged = {
        "budget_tokens": budget_tokens,
        "admitted_concurrent": paged_snap["occupancy"]["max"],
        "tokens_per_sec": round(
            sum(len(o) for o in paged_outs) / paged_dt, 2),
        "outputs_match": paged_outs == single_outs,
    }

    # -- arm 2: chunked vs unchunked under a long-prompt storm ----------------
    # Long prompts are injected SEQUENTIALLY against a steady decode
    # background: each unchunked whole-prompt prefill lands in one
    # inter-decode gap, and repeated injections keep those gaps above the
    # p99 sample threshold.
    # Enough long injections that their inter-decode gaps clear the p99
    # sample threshold, few enough that the background decoders outlive
    # the whole storm.
    n_long = 2 if smoke else 10
    bg_tokens = 40 if smoke else 96
    bg_prompts = [rng.randint(0, 256, size=(4,)).tolist()
                  for _ in range(max(2, budget_rows))]
    long_len = cfg.max_len - 12
    long_prompts = [rng.randint(0, 256, size=(long_len,)).tolist()
                    for _ in range(n_long)]
    chunk_adapter = TransformerAdapter(cfg, params,
                                       block_tokens=block_tokens)
    interf_blocks = (len(bg_prompts) + n_long + 2) * \
        chunk_adapter.max_blocks_per_seq

    def interference(prefill_chunk):
        def storm():
            eng = InferenceEngine(chunk_adapter, max_batch=8,
                                  num_blocks=interf_blocks,
                                  prefill_chunk=prefill_chunk,
                                  prefix_cache=False,
                                  metrics=ServeMetrics(),
                                  replica_id="bench-interf").start()
            bg = [Request(p, max_new_tokens=bg_tokens) for p in bg_prompts]
            for r in bg:
                eng.batcher.submit(r)
            # Let the background decoders reach steady state, then land
            # the long prompts one after another mid-flight.
            deadline = time.monotonic() + 60
            while eng.metrics.snapshot()["decode_steps"] < 3 \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            outs = []
            for p in long_prompts:
                r = Request(p, max_new_tokens=4)
                eng.batcher.submit(r)
                outs.append(r.result(timeout=600))
            outs.extend(r.result(timeout=600) for r in bg)
            p99 = eng.metrics.snapshot()["token_step"]["p99_ms"]
            eng.stop()
            return p99, outs
        storm()  # warm: compile this config's chunk buckets
        return storm()

    chunked_p99, chunked_outs = interference(chunk)
    unchunked_p99, unchunked_outs = interference(0)
    arm_chunked = {
        "prefill_chunk": chunk,
        "long_prompt_len": long_len,
        "token_step_p99_ms": chunked_p99,
        "unchunked_token_step_p99_ms": unchunked_p99,
        "p99_ratio": round(unchunked_p99 / max(chunked_p99, 1e-9), 3),
        "outputs_match": chunked_outs == unchunked_outs,
    }

    # -- arm 3: prefix reuse --------------------------------------------------
    shared = rng.randint(0, 256,
                         size=(cfg.max_len // 2,)).tolist()
    prefix_prompts = [shared + rng.randint(0, 256, size=(3,)).tolist()
                      for _ in range(max(4, budget_rows * 2))]
    prefix_adapter = TransformerAdapter(cfg, params,
                                        block_tokens=block_tokens)

    def prefix_storm():
        # Leader first: its completed prompt blocks populate the prefix
        # cache, then the rest of the storm maps them (a fully-concurrent
        # first wave would look up before anything registered).
        eng = InferenceEngine(prefix_adapter, max_batch=8,
                              num_blocks=interf_blocks,
                              prefill_chunk=chunk, prefix_cache=True,
                              metrics=ServeMetrics(),
                              replica_id="bench-prefix").start()
        engine_storm(eng, prefix_prompts[:1], 4)
        engine_storm(eng, prefix_prompts[1:], 4)
        stats = eng.kv_stats()
        eng.stop()
        return stats

    prefix_storm()  # warm the (count, chunk) compile buckets
    prefix_kv = prefix_storm()
    arm_prefix = {
        "enabled": prefix_on,
        "hit_rate": round(prefix_kv["prefix_hit_rate"], 4),
        "hit_tokens": prefix_kv["prefix_hit_tokens"],
        "cow_copies": prefix_kv["cow"],
        "evictions": prefix_kv["evictions"],
    }

    # -- arm 3b: gather vs Pallas paged-attention kernel ----------------------
    # Identical engine config either side; only HVD_SERVE_ATTN_IMPL
    # differs.  Short max_len keeps the interpreter-unrolled grid small
    # enough that the full hermetic bench stays runnable on CPU; on TPU
    # the same arm compiles the real Mosaic kernel.
    kernel_interpret = jax.default_backend() != "tpu"
    kernel_len = min(cfg.max_len, 64)
    kernel_prompts = [p[:kernel_len // 2] for p in
                      mixed_prompts[:8 if smoke else 16]]
    kernel_tokens = min(new_tokens, 8)

    def impl_arm(impl):
        ad = TransformerAdapter(cfg, params, max_len=kernel_len,
                                block_tokens=block_tokens, attn_impl=impl)
        outs, dt, snap, _ = timed_storm(
            lambda: InferenceEngine(ad, max_batch=4,
                                    prefill_chunk=chunk,
                                    prefix_cache=False,
                                    metrics=ServeMetrics(),
                                    replica_id=f"bench-{impl}"),
            kernel_prompts, kernel_tokens)
        return outs, dt, snap

    gather_outs, gather_dt, gather_snap = impl_arm("gather")
    kernel_outs, kernel_dt, kernel_snap = impl_arm("kernel")
    arm_kernel = {
        "interpret": kernel_interpret,
        "outputs_match": kernel_outs == gather_outs,
        "gather_tokens_per_sec": round(
            sum(len(o) for o in gather_outs) / gather_dt, 2),
        "tokens_per_sec": round(
            sum(len(o) for o in kernel_outs) / kernel_dt, 2),
        "gather_token_step_p50_ms": gather_snap["token_step"]["p50_ms"],
        "gather_token_step_p99_ms": gather_snap["token_step"]["p99_ms"],
        "token_step_p50_ms": kernel_snap["token_step"]["p50_ms"],
        "token_step_p99_ms": kernel_snap["token_step"]["p99_ms"],
        "speedup": round((sum(len(o) for o in kernel_outs) / kernel_dt)
                         / max(sum(len(o) for o in gather_outs)
                               / gather_dt, 1e-9), 3),
    }

    # -- arm 3c: bf16 vs int8 KV blocks at a FIXED HBM budget (bytes) ---------
    # The bf16 pool spends the byte budget on bytes_per_block(bf16)
    # blocks; int8 blocks cost ~half (payload + f16 scale rows), so the
    # same bytes hold ~2x the blocks.  The storm uses UNIFORM-cost
    # prompts (fixed length, so every sequence reserves the same block
    # count) and a pool sized to 8 concurrent bf16 sequences — making
    # the byte budget, not slot count or request mix, the binding
    # constraint the admit_ratio reads.  Exactness: int8 shifts logits,
    # so the int8 engine is pinned against ITS OWN single-request run
    # (batched == single is the engine contract at any storage dtype).
    # Enough requests to saturate the BIGGER (int8) pool's concurrency,
    # else the request count caps both arms and the ratio reads 1.0.
    kv_prompt_len = max(block_tokens - kernel_tokens - 2, 2)
    kv_arm_prompts = [rng.randint(0, 256, size=(kv_prompt_len,)).tolist()
                      for _ in range(20)]

    def dtype_arm(ad, nblocks, prompts_, singles=False):
        # Unchunked prefill: every admitted sequence enters decode in the
        # SAME iteration, so occupancy reads the pool's true concurrency
        # bound instead of the chunk budget's staggered ramp-in.
        mk = lambda rid: InferenceEngine(  # noqa: E731
            ad, max_batch=64, num_blocks=nblocks,
            prefill_chunk=0, prefix_cache=False,
            metrics=ServeMetrics(), replica_id=rid)
        outs, dt, snap, kv = timed_storm(
            lambda: mk(f"bench-kv-{ad.kv_dtype}"), prompts_,
            kernel_tokens)
        sgl = None
        if singles:
            eng = mk(f"bench-kv-{ad.kv_dtype}-single").start()
            sgl = [eng.generate(p, max_new_tokens=kernel_tokens)
                   for p in prompts_]
            eng.stop()
        return outs, dt, snap, kv, sgl

    ad16, ad8 = (TransformerAdapter(cfg, params, max_len=kernel_len,
                                    block_tokens=block_tokens,
                                    kv_dtype=kvd)
                 for kvd in ("bf16", "int8"))
    bf16_bpb = ad16.paged_block_bytes()
    int8_bpb = ad8.paged_block_bytes()
    seq_cost = -(-(kv_prompt_len + kernel_tokens) // block_tokens)
    bf16_blocks = 8 * seq_cost
    budget_bytes = bf16_blocks * bf16_bpb
    int8_blocks = budget_bytes // int8_bpb
    outs16, dt16, snap16, _, _ = dtype_arm(
        ad16, bf16_blocks, kv_arm_prompts)
    outs8, dt8, snap8, kv8, int8_singles = dtype_arm(
        ad8, int8_blocks, kv_arm_prompts, singles=True)
    max_logit_err = max(
        float(np.max(np.abs(ad8.prompt_logits(p)
                            - ad16.prompt_logits(p))))
        for p in kv_arm_prompts[:4])
    arm_kv_dtype = {
        "budget_bytes": int(budget_bytes),
        "bytes_per_block_bf16": int(bf16_bpb),
        "bytes_per_block_int8": int(int8_bpb),
        "bf16_blocks": int(bf16_blocks),
        "int8_blocks": int(int8_blocks),
        "kv_bytes_per_token_int8": kv8.get("kv_bytes_per_token"),
        "bf16_admitted_concurrent": snap16["occupancy"]["max"],
        "admitted_concurrent": snap8["occupancy"]["max"],
        "admit_ratio": round(snap8["occupancy"]["max"]
                             / max(snap16["occupancy"]["max"], 1), 3),
        "bf16_tokens_per_sec": round(
            sum(len(o) for o in outs16) / dt16, 2),
        "tokens_per_sec": round(sum(len(o) for o in outs8) / dt8, 2),
        "max_logit_err": round(max_logit_err, 6),
        "outputs_match": outs8 == int8_singles,
    }

    # -- arm 4: faults — recovery time + goodput under a seeded plan ----------
    # The robustness trajectory (ISSUE 6): the identical storm runs under
    # a seeded FaultPlan (faultline) — a poisoned engine step on
    # replica-0 plus a rank kill + recovery (mark_dead → mark_alive, the
    # scale-up path) on the last replica — and the record captures what
    # the throughput arms cannot: how fast the fleet is BACK ("replica
    # re-admitted and answering") and how much accepted work survived
    # first-try ("goodput_ratio"; failed requests are retried client-side
    # and still checked for correctness, so faults cost latency, never
    # wrong answers).
    from horovod_tpu import faultline as _fl
    fault_seed = int(os.environ.get(
        "HVD_FAULTLINE_SEED", KNOB_DEFAULTS["HVD_FAULTLINE_SEED"]))
    it = iter(adapters)
    fault_metrics = ServeMetrics()
    fsched = build_replicas(lambda: next(it), num_replicas=replicas,
                            metrics=fault_metrics)
    fsched.start()
    victim = fsched.replicas[-1]
    plan = _fl.install(_fl.FaultPlan([
        _fl.FaultSpec("slow-decode", target="replica-0", param=0.002),
        _fl.FaultSpec("poison-step", target="replica-0"),
    ], seed=fault_seed))
    recovery_box = {}

    def kill_and_recover():
        deadline = time.monotonic() + 120
        while victim.engine.load() == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        fsched.mark_dead(victim.replica_id, reason="bench fault arm")
        t_kill = time.perf_counter()
        fsched.mark_alive(victim.replica_id, reason="bench rank recovery")
        while fsched.healthz()["status"] != "ok" \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        # Recovered means ANSWERING, not just listed: a probe submitted
        # straight to the revived replica's queue must complete.
        probe = Request(prompts[0], max_new_tokens=2)
        victim.engine.batcher.submit(probe)
        probe.result(timeout=600)
        recovery_box["recovery_s"] = time.perf_counter() - t_kill

    killer = threading.Thread(target=kill_and_recover, daemon=True)
    killer.start()
    first_try_fail = 0
    fault_outs = []
    fault_requests = [Request(p, max_new_tokens=new_tokens)
                      for p in prompts]
    for r in fault_requests:
        fsched.submit(r)
    for i, r in enumerate(fault_requests):
        try:
            fault_outs.append(r.result(timeout=600))
        except Exception:
            # Client-side retry: a poisoned step fails its batch with the
            # real error (engine contract); the caller retries, as a real
            # front-end would.  Counted against goodput.
            first_try_fail += 1
            retry = Request(prompts[i], max_new_tokens=new_tokens)
            fsched.submit(retry)
            fault_outs.append(retry.result(timeout=600))
    killer.join(timeout=600)
    _fl.uninstall()
    fsched.stop()
    fault_snap = fault_metrics.snapshot()
    arm_faults = {
        "seed": fault_seed,
        "fired": plan.firing_sequence(),
        "recovery_s": round(recovery_box.get("recovery_s", -1.0), 4),
        "goodput_ratio": round(
            (len(prompts) - first_try_fail) / max(len(prompts), 1), 4),
        "requeued": fault_snap["requests"].get("requeued", 0),
        "errors": fault_snap["requests"].get("error", 0),
        "replica_events": fault_snap["replica_events"],
        "outputs_match": fault_outs == outs,
    }

    # -- arm 5: trace-sampling overhead (ISSUE 9) -----------------------------
    # Identical storm with the tracer ABSENT (sample=0 — the zero-
    # overhead contract's fast path: every instrumented site is one
    # module-attribute/None read, so this number tracks the record's
    # main tokens/s trajectory; acceptance is ≤2% regression there) vs
    # INSTALLED at sample=1.0 with shard files on disk (every request
    # spanned end-to-end: queue-wait/prefill/decode/flow per token).
    # The sampled number prices full tracing, not the production
    # configuration — production samples a few percent.
    import shutil
    import tempfile
    from horovod_tpu.obs import tracing as _tr
    tr_prompts = mixed_prompts[:8 if smoke else 16]
    tr_tokens = min(new_tokens, 8)
    tr_adapter = TransformerAdapter(cfg, params,
                                    block_tokens=block_tokens)

    def trace_storm():
        tsched = build_replicas(lambda: tr_adapter, num_replicas=1,
                                metrics=ServeMetrics())
        tsched.start()
        reqs = [Request(p, max_new_tokens=tr_tokens) for p in tr_prompts]
        t0 = time.perf_counter()
        for r in reqs:
            tsched.submit(r)
        outs_ = [r.result(timeout=600) for r in reqs]
        dt_ = time.perf_counter() - t0
        tsched.stop()
        return outs_, dt_

    trace_storm()  # warm this config's compile buckets
    off_outs, off_dt = trace_storm()
    trace_dir = tempfile.mkdtemp(prefix="hvdtrace-bench-")
    tracer = _tr.install(_tr.Tracer(sample=1.0, shard_dir=trace_dir))
    on_outs, on_dt = trace_storm()
    spans = tracer.spans_emitted
    # Count shards only AFTER uninstall(): shard files are created
    # lazily by the tracer's writer thread, which uninstall joins.
    _tr.uninstall()
    shard_count = len([f for f in os.listdir(trace_dir)
                       if f.startswith("trace-")])
    shutil.rmtree(trace_dir, ignore_errors=True)
    off_tps = sum(len(o) for o in off_outs) / off_dt
    on_tps = sum(len(o) for o in on_outs) / on_dt
    arm_trace = {
        "sample0_tokens_per_sec": round(off_tps, 2),
        "sample1_tokens_per_sec": round(on_tps, 2),
        "sampled_throughput_ratio": round(on_tps / max(off_tps, 1e-9), 4),
        "outputs_match": on_outs == off_outs,
        "spans": int(spans),
        "shards": shard_count,
    }

    # -- arm 6: speculative decoding (ISSUE 11) -------------------------------
    # The identical greedy storm, non-speculative vs speculative with a
    # truncated-stack draft (HVD_SERVE_DRAFT_LAYERS, arm default 1) at
    # BENCH_SERVE_SPEC_K.  Greedy speculation is bit-identical to plain
    # greedy by construction (the engine accepts while draft == target
    # argmax and emits the target's token at the first mismatch), so
    # outputs_match is checked in-band; the amortization statistic is
    # target-model decode invocations per emitted decode token — per
    # sequence, one verify step emits accepted+1 tokens, so
    # calls/token = (emitted - accepted) / emitted (1.0 without spec,
    # 1/(k+1) at full acceptance).  Acceptance bar: <= 0.67 (>= 1.5x).
    spec_k = int(os.environ.get("BENCH_SERVE_SPEC_K",
                                KNOB_DEFAULTS["BENCH_SERVE_SPEC_K"]))
    draft_layers = max(int(os.environ.get(
        "HVD_SERVE_DRAFT_LAYERS",
        KNOB_DEFAULTS["HVD_SERVE_DRAFT_LAYERS"])), 1)
    spec_adapter = TransformerAdapter(cfg, params, max_len=kernel_len,
                                      block_tokens=block_tokens,
                                      draft_layers=draft_layers)

    def spec_storm(sk):
        mk = lambda: InferenceEngine(  # noqa: E731
            spec_adapter, max_batch=4,
            prefill_chunk=chunk, prefix_cache=False,
            metrics=ServeMetrics(), replica_id=f"bench-spec{sk}",
            spec_k=sk)
        if not smoke:
            # Warm pass compiles this config's buckets outside the timed
            # window; the smoke run (exactness/contract only — the
            # compile caches live on the shared adapter anyway) skips it.
            warm = mk().start()
            engine_storm(warm, kernel_prompts, kernel_tokens)
            warm.stop()
        eng = mk().start()
        eng.metrics.started_at = time.monotonic()
        t0_ = time.perf_counter()
        outs_ = engine_storm(eng, kernel_prompts, kernel_tokens)
        dt_ = time.perf_counter() - t0_
        snap_ = eng.metrics.snapshot()
        eng.stop()
        return outs_, dt_, snap_

    spec_base_outs, spec_base_dt, _ = spec_storm(0)
    spec_outs, spec_dt, spec_snap = spec_storm(spec_k)
    spec_emitted = sum(len(o) for o in spec_outs) - len(kernel_prompts)
    spec_accepted = spec_snap["spec"]["accepted"]
    arm_spec = {
        "spec_k": spec_k,
        "draft_layers": draft_layers,
        "outputs_match": spec_outs == spec_base_outs,
        "acceptance_rate": spec_snap["spec"]["acceptance_rate"],
        "drafted": spec_snap["spec"]["drafted"],
        "accepted": spec_accepted,
        "rejected": spec_snap["spec"]["rejected"],
        "spec_steps": spec_snap["spec"]["steps"],
        "target_calls_per_token": round(
            (spec_emitted - spec_accepted) / max(spec_emitted, 1), 4),
        "baseline_tokens_per_sec": round(
            sum(len(o) for o in spec_base_outs) / spec_base_dt, 2),
        "tokens_per_sec": round(
            sum(len(o) for o in spec_outs) / spec_dt, 2),
        "speedup": round(
            (sum(len(o) for o in spec_outs) / spec_dt)
            / max(sum(len(o) for o in spec_base_outs)
                  / spec_base_dt, 1e-9), 3),
    }

    # -- arm 7: seeded sampling + CoW-forked n-best (ISSUE 11) ----------------
    # Determinism: the identical sampled storm (per-request fixed seeds,
    # temperature/top_k from the knobs) on two fresh engines must produce
    # identical outputs — the batched==single-given-the-same-key contract
    # at storm concurrency.  n-best: one n=4 request against one n=1
    # request at the same prompt length on fresh pools; the fork family
    # shares the full prompt blocks, so its peak pool footprint must sit
    # STRICTLY below 4x the single sequence's (the CoW acceptance bar).
    sample_temp = float(os.environ.get(
        "BENCH_SERVE_SAMPLE_TEMP",
        KNOB_DEFAULTS["BENCH_SERVE_SAMPLE_TEMP"]))
    sample_seeds = [9000 + i for i in range(len(kernel_prompts))]

    def sampled_storm():
        eng = InferenceEngine(spec_adapter, max_batch=4,
                              prefill_chunk=chunk, prefix_cache=False,
                              metrics=ServeMetrics(),
                              replica_id="bench-sampled").start()
        reqs = [Request(p, max_new_tokens=kernel_tokens,
                        temperature=sample_temp, top_k=64, seed=s)
                for p, s in zip(kernel_prompts, sample_seeds)]
        t0_ = time.perf_counter()
        for r in reqs:
            eng.batcher.submit(r)
        outs_ = [r.result(timeout=600) for r in reqs]
        dt_ = time.perf_counter() - t0_
        eng.stop()
        return outs_, dt_

    if not smoke:
        sampled_storm()  # warm the sampled decode/logit-prefill buckets
    sam1_outs, sam1_dt = sampled_storm()
    sam2_outs, _ = sampled_storm()

    nbest_prompt = rng.randint(0, 256,
                               size=(3 * block_tokens + 5,)).tolist()

    def nbest_run(n):
        eng = InferenceEngine(spec_adapter, max_batch=8,
                              prefill_chunk=chunk, prefix_cache=False,
                              metrics=ServeMetrics(),
                              replica_id=f"bench-nbest{n}").start()
        req = Request(nbest_prompt, max_new_tokens=kernel_tokens,
                      temperature=sample_temp, top_k=64, n=n, seed=1234)
        eng.batcher.submit(req)
        req.result(timeout=600)
        kv_ = eng.kv_stats()
        eng.stop()
        return req, kv_

    _, kv_n1 = nbest_run(1)
    nbest_req, kv_n4 = nbest_run(4)
    bpb = kv_n1.get("bytes_per_block", 1)
    arm_sampling = {
        "temperature": sample_temp,
        "top_k": 64,
        "deterministic": sam1_outs == sam2_outs,
        "tokens_per_sec": round(
            sum(len(o) for o in sam1_outs) / sam1_dt, 2),
        "nbest_n": 4,
        "cow_forks": kv_n4["seq_forks"],
        "forked_requests": kv_n4["forked_requests"],
        "cow_copies": kv_n4["cow"],
        "n1_peak_pool_bytes": int(kv_n1["used_peak"] * bpb),
        "n4_peak_pool_bytes": int(kv_n4["used_peak"] * bpb),
        "pool_share_ratio": round(
            kv_n4["used_peak"] / max(4 * kv_n1["used_peak"], 1), 4),
        "completions_distinct": len({tuple(s)
                                     for s in nbest_req.samples}) > 1,
    }

    # -- arm 8: autoscale — hvdctl under a seeded diurnal sweep (ISSUE 13) ----
    # The identical greedy prompts ride a ``faultline.diurnal_load``
    # low -> peak -> low shape against a fleet that starts at ONE
    # healthy replica (the rest are dead spares), with the controller's
    # poll loop driven between ticks.  The record captures the control
    # plane's own acceptance numbers: did the latency-tier p99 hold the
    # SLO across the sweep (slo_held), how long the brownout ladder was
    # engaged (brownout_seconds), and the scale_up/scale_down/brownout
    # event tallies — plus in-band exactness (brownout_max_new is held
    # >= the storm's max_new_tokens, so degradation never truncates).
    from horovod_tpu.serve import ControllerConfig, FleetController
    from horovod_tpu.serve import QueueFullError as _QFull
    slo_ms = float(os.environ.get("BENCH_SERVE_SLO_MS",
                                  KNOB_DEFAULTS["BENCH_SERVE_SLO_MS"]))
    it = iter(adapters)
    ctl_metrics = ServeMetrics()
    # max_batch=2 keeps peak ticks from vanishing straight into one
    # replica's active set — queue depth must be VISIBLE for the
    # controller's pressure signal to mean anything at smoke shapes.
    csched = build_replicas(lambda: next(it), num_replicas=replicas,
                            max_batch=2, metrics=ctl_metrics)
    csched.start()
    for r in csched.replicas[1:]:
        csched.mark_dead(r.replica_id, reason="bench autoscale arm: spare")
    ctl = FleetController(csched, config=ControllerConfig(
        poll_s=0.05, min_replicas=1, max_replicas=replicas,
        queue_high=2.0, queue_low=1.0, up_polls=2, down_polls=2,
        up_cooldown_s=0.0, down_cooldown_s=0.0,
        brownout_polls=1, brownout_clear_polls=2,
        brownout_max_new=max(new_tokens, 1)).validate(),
        metrics=ctl_metrics)
    shape = _fl.diurnal_load(8, peak=max(len(prompts) // 2, 4), base=1,
                             seed=fault_seed)
    max_brownout = 0
    shed_throughput = 0
    ctl_outs = []
    cursor = 0
    tick = 0
    while cursor < len(prompts):
        n_tick = max(shape[tick % len(shape)], 1)
        chunk_prompts = prompts[cursor:cursor + n_tick]
        cursor += len(chunk_prompts)
        tick += 1
        reqs = [Request(p, max_new_tokens=new_tokens)
                for p in chunk_prompts]
        for r in reqs:
            csched.submit(r)
        # Best-effort filler riding the same tick: at peak the ladder
        # sheds exactly this tier — that IS the measurement.
        try:
            csched.submit(Request(prompts[0][:4] or [1], max_new_tokens=2,
                                  qos="throughput"))
        except _QFull:
            shed_throughput += 1
        # Drive the control plane WHILE the tick drains (not just at the
        # edges) — sustained queue pressure across consecutive polls is
        # what arms scale-up and the brownout ladder.
        while not all(r.done for r in reqs):
            ctl.poll()
            max_brownout = max(max_brownout,
                               ctl.stats()["brownout_level"])
            time.sleep(0.02)
        ctl.poll()
        max_brownout = max(max_brownout, ctl.stats()["brownout_level"])
        ctl_outs.extend(r.result(timeout=600) for r in reqs)
    # Recede: idle polls walk the ladder off and shrink the fleet.
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        s = ctl.stats()
        if s["brownout_level"] == 0 and \
                s["scale_events"]["scale_down"] >= 1:
            break
        ctl.poll()
        time.sleep(0.02)
    ctl.stop()
    csched.stop()
    ctl_snap = ctl_metrics.snapshot()
    ctl_stats = ctl.stats()
    lat_p99 = ctl_snap["request_latency"]["latency"]["p99_ms"]
    arm_autoscale = {
        "slo_ms": slo_ms,
        "latency_p99_ms": lat_p99,
        "slo_held": lat_p99 <= slo_ms,
        "scale_events": ctl_stats["scale_events"],
        "brownout_seconds": ctl_stats["brownout_seconds"],
        "max_brownout_level": max_brownout,
        "shed_throughput": shed_throughput,
        "diurnal_shape": shape,
        "outputs_match": ctl_outs == outs,
    }

    # -- arm 9: multitenant — hvdtenant platform (ISSUE 15) -------------------
    # Two model variants resident on a small fleet, three tenants at
    # weights 3:2:1 driving a saturating storm (max_batch=2 keeps a
    # visible backlog, so WDRR admission IS the goodput dial), with a
    # live roll of the second variant mid-storm.  Recorded acceptance
    # numbers: per-tenant fair-share ratio (observed early-goodput share
    # / weight share), swap_zero_failures (every storm request
    # succeeded across the roll), post-roll bit-exactness vs the new
    # weights served cold, and the revived-replica cold-start
    # (warmup ms + first-request latency vs the storm's steady p50).
    from horovod_tpu.models import create_mlp
    from horovod_tpu.serve import (DynamicBatcher, MLPAdapter,
                                   ModelRegistry, Replica, ReplicaScheduler,
                                   TenantConfig)
    mt_vocab = 61

    def _mt_adapter(seed):
        mlp_mod = create_mlp(features=(32, mt_vocab))
        p = mlp_mod.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, mt_vocab)))["params"]
        return MLPAdapter(mlp_mod, p, vocab_size=mt_vocab, max_len=64)

    mt_weights = {"gold": 3.0, "silver": 2.0, "bronze": 1.0}
    mt_cfg_t = TenantConfig(weights=mt_weights, quantum=8)
    mt_metrics = ServeMetrics()
    n_mt = 2 if smoke else 4
    per_tenant = 6 if smoke else 12
    mt_tokens = max(min(new_tokens, 8), 2)
    mt_replicas = []
    for i in range(n_mt):
        eng = InferenceEngine(
            _mt_adapter(3), batcher=DynamicBatcher(tenants=mt_cfg_t),
            metrics=mt_metrics, max_batch=2,
            replica_id=f"mt-{i}", warmup=True)
        mt_replicas.append(Replica(f"mt-{i}", None, eng))
    mt_sched = ReplicaScheduler(mt_replicas, metrics=mt_metrics)
    registry = ModelRegistry(mt_sched, metrics=mt_metrics)
    registry.adopt("default")
    registry.register("tuned", adapter=_mt_adapter(7))
    mt_sched.start()
    mt_prompt = [1, 2, 3, 4, 5, 6]

    def mt_storm(with_models):
        """One interleaved-arrival storm; returns (requests, stamps,
        failures).  Completion stamps come from a poll loop (Request
        carries no finish time) — 1 ms granularity is far below a
        decode pass here, so completion ORDER is preserved."""
        reqs = []
        for j in range(per_tenant):
            for tenant in mt_weights:  # interleaved, no head start
                mdl = "tuned" if with_models and j % 3 == 2 else None
                reqs.append(Request(list(mt_prompt),
                                    max_new_tokens=mt_tokens,
                                    tenant=tenant, model=mdl))
        for r in reqs:
            mt_sched.submit(r)
        return reqs

    def mt_collect(reqs):
        stamp = {}
        deadline = time.monotonic() + 600
        while len(stamp) < len(reqs) and time.monotonic() < deadline:
            now = time.perf_counter()
            for i, r in enumerate(reqs):
                if i not in stamp and r.done:
                    stamp[i] = now
            if len(stamp) < len(reqs):
                time.sleep(0.001)
        done, failures = [], 0
        for i, r in enumerate(reqs):
            try:
                out = r.result(timeout=60)
                done.append((stamp.get(i, time.perf_counter()), r.tenant,
                             len(out)))
            except Exception:
                failures += 1
        return done, failures

    # Fairness storm (no roll churn: a mid-storm roll requeues orphans
    # into the requeued-first priority class, which would scramble the
    # very ordering under measurement).
    mt_t0 = time.perf_counter()
    fair_reqs = mt_storm(with_models=False)
    mt_done, fair_failures = mt_collect(fair_reqs)
    # Early-goodput share: tokens per tenant over the first HALF of
    # completions — under saturation the DRR quantum ratio, not arrival
    # order, decides who lands there.
    mt_done.sort(key=lambda x: x[0])
    half = mt_done[:max(len(mt_done) // 2, 1)]
    share = {t: 0 for t in mt_weights}
    for _, tenant, toks in half:
        share[tenant] += toks
    total_share = max(sum(share.values()), 1)
    wsum = sum(mt_weights.values())
    fair_ratio = {
        t: round((share[t] / total_share) / (mt_weights[t] / wsum), 3)
        for t in mt_weights}
    # Swap storm: live roll mid-storm — replica-by-replica
    # drain -> swap -> revive while requests (both variants) drain;
    # orphaned work requeues onto holders of the same variant, so zero
    # requests may fail.
    swap_reqs = mt_storm(with_models=True)
    registry.roll("tuned", adapter=_mt_adapter(11))
    _, mt_failures = mt_collect(swap_reqs)
    mt_failures += fair_failures
    # Post-roll exactness: the rolled variant served by the fleet must
    # equal the new weights served COLD by a fresh engine.
    post = Request(list(mt_prompt), max_new_tokens=mt_tokens,
                   model="tuned")
    mt_sched.submit(post)
    post_out = post.result(timeout=600)
    cold_eng = InferenceEngine(_mt_adapter(11),
                               batcher=DynamicBatcher(),
                               metrics=ServeMetrics(), max_batch=2,
                               replica_id="mt-cold").start()
    cold_req = Request(list(mt_prompt), max_new_tokens=mt_tokens)
    cold_eng.batcher.submit(cold_req)
    cold_out = cold_req.result(timeout=600)
    cold_eng.stop()
    # Cold-start: revive a replica (the controller-grown path) — warmup
    # re-runs at start() and the first request onto the warm replica is
    # compared against the storm's steady per-request latency.
    steady = sorted(t - mt_t0 for t, _, _ in mt_done)
    steady_p50_s = steady[len(steady) // 2] if steady else 0.0
    mt_sched.mark_dead("mt-0", reason="bench cold-start probe")
    mt_sched.mark_alive("mt-0", reason="bench cold-start probe")
    cold_ms = mt_replicas[0].engine.last_warmup_ms
    probe = Request(list(mt_prompt), max_new_tokens=mt_tokens)
    p_t0 = time.perf_counter()
    mt_sched.submit(probe)
    probe.result(timeout=600)
    first_request_ms = (time.perf_counter() - p_t0) * 1e3
    mt_sched.stop()
    mt_snap = mt_metrics.snapshot()
    arm_multitenant = {
        "replicas": n_mt,
        "tenants": {t: w for t, w in mt_weights.items()},
        "fair_share_ratio": fair_ratio,
        "swap_zero_failures": mt_failures == 0,
        "swap_progress": mt_snap["swap"],
        "post_roll_exact": post_out == cold_out,
        "cold_start_ms": round(cold_ms, 3),
        "warmup_runs": mt_replicas[0].engine.warmup_runs,
        "first_request_ms": round(first_request_ms, 3),
        "tenant_requests": {t: mt_snap["tenants"].get(t, {}).get(
            "requests", {}) for t in mt_weights},
    }

    # -- arm 10: hvdtier tiered KV hierarchy (ISSUE 16) -----------------------
    # Offload sub-arm: a FIXED device pool sized for ~4 concurrent
    # untiered lifetimes, stormed with 10 long-decode requests.  The
    # untiered engine caps in-flight at what the pool admits; the tiered
    # engine oversubscribes, swapping cold sequences host-ward instead
    # of preempting — acceptance: admit_ratio >= 2 at the same pool
    # bytes, zero preemptions, outputs bit-identical.
    from horovod_tpu.runner.http_server import (KVStoreClient,
                                                KVStoreServer)
    from horovod_tpu.serve import TierClient, TierConfig

    tier_tokens = 24 if smoke else min(new_tokens * 2, cfg.max_len - 16)
    tier_plen = 8
    tier_cost = (tier_plen + tier_tokens + block_tokens - 1) \
        // block_tokens
    tier_pool = 4 * tier_cost
    n_tier = 10
    tier_prompts = [rng.randint(0, 256, size=(tier_plen,)).tolist()
                    for _ in range(n_tier)]
    tier_adapter = TransformerAdapter(cfg, params,
                                      block_tokens=block_tokens)

    def untiered_engine():
        return InferenceEngine(tier_adapter, max_batch=12,
                               num_blocks=tier_pool,
                               prefill_chunk=chunk,
                               metrics=ServeMetrics(),
                               replica_id="bench-untier")

    unt_outs, _unt_dt, unt_snap, _ = timed_storm(
        untiered_engine, tier_prompts, tier_tokens)

    def tiered_engine():
        return InferenceEngine(tier_adapter, max_batch=12,
                               num_blocks=tier_pool,
                               prefill_chunk=chunk,
                               tiering=TierConfig(oversub=4.0, quantum=2),
                               metrics=ServeMetrics(),
                               replica_id="bench-tiered")

    tier_outs, _tier_dt, tier_snap, tier_kv = timed_storm(
        tiered_engine, tier_prompts, tier_tokens)
    tier_peak = tier_kv["tier"]["inflight_peak"]
    unt_peak = unt_snap["occupancy"]["max"]

    # Migration sub-arm: replica A's leader storm publishes the shared
    # prefix chain into an in-process KV block directory; replica B
    # (cold local cache) serves the follower storm by MIGRATING those
    # blocks over the transport instead of re-prefilling — acceptance:
    # B's prefix hit tokens (all migration-derived) at least match the
    # single-replica prefix arm's, outputs == a never-tiered engine.
    tier_srv = KVStoreServer()
    tier_port = tier_srv.start(0)

    def fleet_engine(rid):
        client = TierClient(KVStoreClient("127.0.0.1", tier_port),
                            replica_id=rid)
        return InferenceEngine(prefix_adapter, max_batch=8,
                               num_blocks=interf_blocks,
                               prefill_chunk=chunk, prefix_cache=True,
                               tiering=TierConfig(quantum=2),
                               tier_client=client,
                               metrics=ServeMetrics(), replica_id=rid)

    mig_prompts = prefix_prompts + \
        [shared + rng.randint(0, 256, size=(3,)).tolist()
         for _ in range(2)]
    eng_a = fleet_engine("tier-a").start()
    engine_storm(eng_a, mig_prompts[:1], 4)  # leader publishes
    shared_blocks = (len(shared) - 1) // block_tokens
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and \
            eng_a.kv_stats()["tier"]["published"] < shared_blocks:
        time.sleep(0.02)
    eng_b = fleet_engine("tier-b").start()
    # First follower migrates the chain; the rest hit it locally —
    # every B-side prefix hit exists only because of the migration.
    mig_first = engine_storm(eng_b, mig_prompts[:1], 4)
    mig_rest = engine_storm(eng_b, mig_prompts[1:], 4)
    mig_kv = eng_b.kv_stats()
    mig_stall = eng_b.metrics.snapshot()["tier"]["fault_stall"]
    eng_a.stop()
    eng_b.stop()
    tier_srv.stop()
    ref_eng = InferenceEngine(prefix_adapter, max_batch=8,
                              num_blocks=interf_blocks,
                              prefill_chunk=chunk, prefix_cache=True,
                              metrics=ServeMetrics(),
                              replica_id="bench-mig-ref").start()
    mig_ref = engine_storm(ref_eng, mig_prompts, 4)
    ref_eng.stop()
    arm_tiered = {
        "pool_blocks": tier_pool,
        "admitted_concurrent": tier_peak,
        "untiered_admitted_concurrent": unt_peak,
        "admit_ratio": round(tier_peak / max(unt_peak, 1), 3),
        "outputs_match": tier_outs == unt_outs,
        "preempted": tier_snap["requests"]["preempted"],
        "untiered_preempted": unt_snap["requests"]["preempted"],
        "swapped_out_seqs": tier_kv["tier"]["swapped_out_seqs"],
        "spill_bytes": tier_kv["tier"]["spill_bytes"],
        "tier_fault_stall_p50_ms": mig_stall["p50_ms"],
        "tier_fault_stall_p99_ms": mig_stall["p99_ms"],
        "tier_faults": mig_kv["tier"]["faults"],
        "migrated_tokens": mig_kv["tier"]["migrated_tokens"],
        "migrated_hit_tokens": mig_kv["prefix_hit_tokens"],
        "migration_failures": mig_kv["tier"]["migration_failures"],
        "migration_outputs_match": mig_first + mig_rest == mig_ref,
    }

    # -- arm 11: hvdroute front door (ISSUE 18) -------------------------------
    # Two single-replica serve endpoints behind the prefix-affinity
    # router, repeat sessions driven through the real HTTP tier:
    # affinity_hit_rate (did repeats land where their blocks live),
    # zero_lost (every request answered, bit-identical to a single
    # engine serving the same prompts), and the hedging sub-arm — a
    # seeded slow-route fault train stalls one endpoint's forwards and
    # the hedged pass must beat the unhedged pass's p99.
    import http.client
    from horovod_tpu.faultline import runtime as _flt
    from horovod_tpu.faultline.plan import parse_plan
    from horovod_tpu.serve import (Router, RouterConfig, RouterServer,
                                   ServeServer)

    route_backends = []
    route_endpoints = []
    for i in range(2):
        bsched = build_replicas(
            lambda: prefix_adapter, num_replicas=1,
            metrics=ServeMetrics(),
            num_blocks=interf_blocks, prefill_chunk=chunk,
            prefix_cache=True)
        bsrv = ServeServer(bsched)
        bport = bsrv.start(port=0, host="127.0.0.1")
        route_backends.append(bsrv)
        route_endpoints.append(f"127.0.0.1:{bport}")
    router = Router(route_endpoints, config=RouterConfig())
    rsrv = RouterServer(router)
    rport = rsrv.start(port=0, host="127.0.0.1")

    def route_post(payload):
        conn = http.client.HTTPConnection("127.0.0.1", rport, timeout=120)
        try:
            conn.request("POST", "/generate",
                         json.dumps(payload).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    route_sessions = 4 if smoke else 6
    route_reps = 3
    route_toks = 4
    route_prompts = [[(17 * s + j) % 256 for j in range(12)]
                     for s in range(route_sessions)]
    route_lost = 0
    route_outs = {}
    for rep in range(route_reps):
        for i, p in enumerate(route_prompts):
            st, rbody = route_post({"tokens": p,
                                    "max_new_tokens": route_toks})
            if st != 200:
                route_lost += 1
            else:
                route_outs.setdefault(i, set()).add(tuple(rbody["tokens"]))
    route_ref_eng = InferenceEngine(prefix_adapter, max_batch=8,
                                    num_blocks=interf_blocks,
                                    prefill_chunk=chunk, prefix_cache=True,
                                    metrics=ServeMetrics(),
                                    replica_id="bench-route-ref").start()
    route_ref = engine_storm(route_ref_eng, route_prompts, route_toks)
    route_ref_eng.stop()
    route_zero_lost = route_lost == 0 and all(
        route_outs.get(i) == {tuple(route_ref[i])}
        for i in range(route_sessions))
    rsnap = router.metrics.snapshot()

    # Hedging sub-arm: prompts whose affinity target is endpoint 0, a
    # persistent slow-route stall on that endpoint, unhedged vs hedged.
    hedge_prompts = []
    s = 0
    while len(hedge_prompts) < 4 and s < 4096:
        p = [(31 * s + j) % 256 for j in range(12)]
        if router._ring.lookup(router.affinity_key(p))[0] == \
                route_endpoints[0]:
            hedge_prompts.append(p)
        s += 1
    stall_s = 0.15 if smoke else 0.3
    hedge_lat = {}
    hsnaps = {}
    for mode, hedge_ms in (("unhedged", 0.0), ("hedged", 30.0)):
        hrouter = Router(route_endpoints,
                         config=RouterConfig(hedge_s=hedge_ms / 1e3))
        _flt.install(parse_plan(
            f"slow-route:{route_endpoints[0]}@0*100000~{stall_s}"
            f"/router.forward", seed=0))
        lats = []
        try:
            for p in hedge_prompts:
                t1 = time.perf_counter()
                hrouter.handle(
                    json.dumps({"tokens": p,
                                "max_new_tokens": route_toks}).encode(),
                    {}, None)
                lats.append((time.perf_counter() - t1) * 1e3)
        finally:
            _flt.uninstall()
        hedge_lat[mode] = sorted(lats)[-1]  # p99 ~= max at this n
        hsnaps[mode] = hrouter.metrics.snapshot()
    rsrv.stop()
    for bsrv in route_backends:
        bsrv.stop()
    arm_router = {
        "endpoints": len(route_endpoints),
        "requests": route_sessions * route_reps,
        "zero_lost": route_zero_lost,
        "affinity_hit_rate": rsnap["affinity"]["hit_rate"],
        "retries": rsnap["retries"],
        "ejections": rsnap["ejections"],
        "hedges": hsnaps["hedged"]["hedges"],
        "hedges_won": hsnaps["hedged"]["hedges_won"],
        "unhedged_p99_ms": round(hedge_lat["unhedged"], 3),
        "hedged_p99_ms": round(hedge_lat["hedged"], 3),
        "hedge_win": hedge_lat["hedged"] <= hedge_lat["unhedged"],
    }

    # -- arm 12: hvdstream token streaming (ISSUE 19) -------------------------
    # One serve endpoint driven through the real HTTP tier, the same
    # prompts buffered then streamed: streamed-concat == buffered is
    # HARD (bit-exactness through the SSE path), client-perceived TTFT
    # (first token event vs the buffered full-response wait — the whole
    # point of streaming), inter-token p99, a mid-stream client
    # disconnect must free every KV block, and the structured sub-arm
    # must emit 100% schema-valid completions at temperature > 0.
    stream_sessions = int(os.environ.get(
        "BENCH_SERVE_STREAM_SESSIONS",
        KNOB_DEFAULTS["BENCH_SERVE_STREAM_SESSIONS"]))
    stream_temp = float(os.environ.get(
        "BENCH_SERVE_STREAM_TEMP",
        KNOB_DEFAULTS["BENCH_SERVE_STREAM_TEMP"]))
    if smoke:
        stream_sessions = min(stream_sessions, 3)
    stream_toks = min(new_tokens, 16)
    stream_sched = build_replicas(
        lambda: prefix_adapter, num_replicas=1, metrics=ServeMetrics(),
        num_blocks=interf_blocks, prefill_chunk=chunk,
        prefix_cache=True)
    stream_srv = ServeServer(stream_sched)
    stream_port = stream_srv.start(port=0, host="127.0.0.1")
    stream_prompts = [[(13 * s + j) % 256 for j in range(10)]
                      for s in range(stream_sessions)]

    def buffered_post(payload):
        conn = http.client.HTTPConnection("127.0.0.1", stream_port,
                                          timeout=120)
        try:
            conn.request("POST", "/generate", json.dumps(payload).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    def stream_post(payload, hangup_after=None):
        """POST with ``stream: true``; returns (events, first-token
        latency ms, inter-token gaps ms).  ``hangup_after=n`` closes
        the socket after the nth token event (the client-gone arm)."""
        from horovod_tpu.serve.streaming import parse_sse
        conn = http.client.HTTPConnection("127.0.0.1", stream_port,
                                          timeout=120)
        t1 = time.perf_counter()
        conn.request("POST", "/generate",
                     json.dumps(dict(payload, stream=True)).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raw = resp.read()
            conn.close()
            return [("error", json.loads(raw))], None, []
        buf = b""
        seen = 0
        ttft_ms = None
        gaps = []
        last_t = None
        try:
            while True:
                data = resp.read1(8192)
                if not data:
                    break
                buf += data
                n_tok = sum(1 for e in parse_sse(buf)
                            if e[0] == "token")
                if n_tok > seen:
                    now_t = time.perf_counter()
                    if ttft_ms is None:
                        ttft_ms = (now_t - t1) * 1e3
                    if last_t is not None:
                        gaps.append((now_t - last_t) * 1e3)
                    last_t = now_t
                    seen = n_tok
                    if hangup_after is not None and seen >= hangup_after:
                        return parse_sse(buf), ttft_ms, gaps
        finally:
            conn.close()
        return parse_sse(buf), ttft_ms, gaps

    buffered_lat = []
    buffered_toks = []
    for p in stream_prompts:
        t1 = time.perf_counter()
        st, rbody = buffered_post({"tokens": p,
                                   "max_new_tokens": stream_toks})
        buffered_lat.append((time.perf_counter() - t1) * 1e3)
        buffered_toks.append(rbody["tokens"] if st == 200 else None)
    stream_match = True
    stream_ttft = []
    stream_gaps = []
    for i, p in enumerate(stream_prompts):
        events, ttft_ms, gaps = stream_post(
            {"tokens": p, "max_new_tokens": stream_toks})
        toks = [t for e in events if e[0] == "token"
                for t in e[1]["tokens"]]
        if toks != buffered_toks[i]:
            stream_match = False
        stream_ttft.append(ttft_ms)
        stream_gaps.extend(gaps)

    def _pctl(xs, q):
        if not xs:
            return None
        xs = sorted(xs)
        return round(xs[min(int(q * len(xs)), len(xs) - 1)], 3)

    # Client-gone sub-arm: hang up mid-stream, the engine must reap the
    # sequence and hand back every block.
    stream_post({"tokens": stream_prompts[0],
                 "max_new_tokens": max(stream_toks, 8)}, hangup_after=1)
    stream_eng = stream_sched.replicas[0].engine
    gone_deadline = time.monotonic() + 30
    kv_used = -1
    while time.monotonic() < gone_deadline:
        kv_used = stream_eng.kv_stats()["used"]
        if kv_used == 0:
            break
        time.sleep(0.02)
    gone_count = stream_eng.metrics.snapshot()["requests"].get(
        "client_gone", 0)

    # Structured sub-arm: sampled (temperature > 0) generation under a
    # JSON-Schema grammar — every completion must parse AND validate.
    stream_schema = {"type": "object",
                     "properties": {"ok": {"type": "boolean"}},
                     "required": ["ok"]}
    schema_valid = 0
    schema_total = stream_sessions
    for i, p in enumerate(stream_prompts):
        st, rbody = buffered_post(
            {"tokens": p, "max_new_tokens": 24, "schema": stream_schema,
             "eos_id": 0, "temperature": stream_temp, "seed": 1000 + i})
        if st != 200:
            continue
        toks = rbody["tokens"]
        if toks and toks[-1] == 0:
            toks = toks[:-1]
        try:
            doc = json.loads(bytes(toks).decode())
        except (ValueError, UnicodeDecodeError):
            continue
        if isinstance(doc, dict) and isinstance(doc.get("ok"), bool) \
                and set(doc) <= {"ok"}:
            schema_valid += 1
    stream_srv.stop()
    arm_stream = {
        "sessions": stream_sessions,
        "new_tokens": stream_toks,
        "outputs_match": stream_match,
        "buffered_p50_ms": _pctl(buffered_lat, 0.5),
        "buffered_p99_ms": _pctl(buffered_lat, 0.99),
        "ttft_p50_ms": _pctl(stream_ttft, 0.5),
        "ttft_p99_ms": _pctl(stream_ttft, 0.99),
        "intertoken_p99_ms": _pctl(stream_gaps, 0.99),
        "ttft_win": (_pctl(stream_ttft, 0.5) or 1e9)
        < (_pctl(buffered_lat, 0.5) or 0),
        "client_gone_kv_used": kv_used,
        "client_gone_counted": gone_count,
        "schema_valid": schema_valid,
        "schema_total": schema_total,
        "schema_valid_rate": round(schema_valid / max(schema_total, 1),
                                   3),
    }

    _emit({
        "metric": "serve_tokens_per_sec",
        "value": round(total_tokens / dt, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(total_tokens / dt / hvd.num_slots(), 3),
        "config": f"{replicas} replica(s) x batch "
                  f"{os.environ.get('HVD_SERVE_MAX_BATCH', '8')}, "
                  f"{n_requests} reqs x {new_tokens} tokens, "
                  f"L{cfg.num_layers} d{cfg.d_model} greedy f32 "
                  f"bt{block_tokens} chunk{chunk}"
                  + (" SMOKE" if smoke else ""),
        "attn_impl": sched.replicas[0].engine.attn_impl,
        "kv_dtype": sched.replicas[0].engine.kv_dtype,
        "block_tokens": block_tokens,
        "prefill_chunk": chunk,
        "prefix_cache": prefix_on,
        "ttft_p50_ms": snap["ttft"]["p50_ms"],
        "ttft_p99_ms": snap["ttft"]["p99_ms"],
        "token_step_p50_ms": snap["token_step"]["p50_ms"],
        "token_step_p99_ms": snap["token_step"]["p99_ms"],
        "occupancy_mean": snap["occupancy"]["mean"],
        "occupancy_max": snap["occupancy"]["max"],
        "requests": snap["requests"],
        "token_split": snap["token_split"],
        "paged": arm_paged,
        "chunked": arm_chunked,
        "prefix": arm_prefix,
        "kernel": arm_kernel,
        "kv_dtype_arm": arm_kv_dtype,
        "faults": arm_faults,
        "trace": arm_trace,
        "spec": arm_spec,
        "sampling": arm_sampling,
        "autoscale": arm_autoscale,
        "multitenant": arm_multitenant,
        "tiered": arm_tiered,
        "router": arm_router,
        "stream": arm_stream,
    })


def main():
    if os.environ.get("BENCH_MODEL", "").startswith("bert"):
        hvd.init()
        bench_bert()
        return
    if os.environ.get("BENCH_MODEL", "").startswith("gpt2"):
        hvd.init()
        bench_gpt2()
        return
    if os.environ.get("BENCH_MODEL", "") == "ring":
        hvd.init()
        bench_ring()
        return
    if os.environ.get("BENCH_MODEL", "") == "serve":
        hvd.init()
        bench_serve()
        return
    hvd.init()
    nslots = hvd.num_slots()
    fast_stem = os.environ.get("BENCH_FAST_STEM", "1") == "1"
    # BENCH_SMOKE=1: tiny shapes/iters so the whole path runs on the CPU
    # in tests; such a record says platform "cpu" and is no measurement.
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    bpc, warmup, iters, hw, ncls = \
        (4, 1, 2, 64, 10) if smoke else \
        (BATCH_PER_CHIP, WARMUP, ITERS, 224, 1000)
    model = create_resnet50(num_classes=ncls, dtype=jnp.bfloat16,
                            sync_bn=True, fast_stem=fast_stem)
    rng = jax.random.PRNGKey(0)
    batch = bpc * nslots

    images = jnp.asarray(
        np.random.RandomState(0).rand(batch, hw, hw, 3).astype(np.float32))
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, ncls, size=(batch,)))

    # init outside shard_map: train=False avoids unbound-axis sync-BN stats
    variables = model.init(rng, images[:2], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    opt_state = opt.init(params)

    def local_step(params, batch_stats, opt_state, xb, yb):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats}, xb, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        loss = hvd.allreduce(loss, op=hvd.Average)  # metric averaging
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    step = hvd.parallel.shard_step(
        local_step,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P()),
        donate_argnums=(0, 1, 2))

    # Warmup (includes compile).  Sync via host transfer: the steps form a
    # dependency chain through params, so fetching the last loss forces every
    # step to have executed.
    for _ in range(warmup):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    float(loss)

    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    float(loss)
    dt = time.perf_counter() - t0
    if profile_dir:
        jax.profiler.stop_trace()

    img_s = batch * iters / dt
    per_dev = img_s / nslots
    record = {
        "metric": "resnet50_synthetic_images_per_sec",
        "value": round(img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(per_dev / BASELINE_IMG_S_PER_DEV, 3),
        "config": f"bs{bpc}/chip bf16 sync-bn "
                  f"{'s2d-stem' if fast_stem else 'naive-stem'}"
                  + (" SMOKE" if smoke else ""),
    }
    # HVD_ANALYZE=1: the shard_step hook checked the step program on first
    # compile (analysis/hook.py); surface its per-step collective census
    # (count + payload bytes per primitive) in the bench record so a perf
    # number always names the collectives that produced it.  Reports only
    # exist when the hook ran, so no separate env gate is needed.
    from horovod_tpu import core as _core
    reports = _core.analysis_reports()
    if reports:
        record["collective_census"] = reports[-1].census
        record["analysis_findings"] = len(reports[-1].findings)
        # hvdmem rode along on the same trace: the step program's peak
        # live footprint + per-primitive allocation breakdown, so a perf
        # number also names the memory it ran in (analysis/memplan.py).
        mem = getattr(reports[-1], "memory", None)
        if mem:
            record["memory_census"] = mem
        # hvdshard rode the same trace: per-step communication plan —
        # wire bytes per collective with the ICI/DCN fabric split and
        # any resharding the compiler would insert (analysis/shardplan.py)
        # — so a perf number also names the bytes it moved.
        comm = getattr(reports[-1], "comm", None)
        if comm:
            record["comm_census"] = comm
    _emit(record)


if __name__ == "__main__":
    main()
