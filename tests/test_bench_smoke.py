"""bench.py on the CPU at smoke sizes: the record's keys, its counts and
its in-band exactness checks.

A CPU run yields counts and correctness, never a time: these tests assert
that keys are present, that outputs match their references and that
counted quantities hold, and compare no wall-clock figure with another.
Every record names the device it was taken on.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")

# An ambient shell's bench/serve knobs would change what the arms run.
_KNOB_PREFIXES = ("BENCH_", "HVD_SERVE_", "HVD_ROUTE_", "HVD_FAULTLINE_",
                  "HVD_TRACE_", "HVD_KV_RETRY_", "HVD_MEM_", "HVD_COMM_",
                  "HVD_ANALYZE", "HVD_SANITIZE", "HVD_RACE_RAISE",
                  "HVD_TIMELINE_QUEUE_CAP")


def _bench_env(**overrides):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(_KNOB_PREFIXES)}
    env.update(JAX_PLATFORMS="cpu", BENCH_SMOKE="1", **overrides)
    return env


def _last_record(stdout):
    records = [json.loads(l) for l in stdout.splitlines()
               if l.strip().startswith("{")]
    assert records, f"no JSON line on stdout: {stdout!r}"
    return records[-1]


def _assert_names_cpu(record):
    assert record["platform"] == "cpu"
    assert record["device_kind"]
    assert record["device_count"] >= 1


def test_serve_bench_smoke_emits_throughput_and_latency():
    """ISSUE 4 satellite + ISSUE 5 satellite: BENCH_MODEL=serve runs the
    continuous-batching serving microbench (bench.bench_serve)
    end-to-end on CPU under BENCH_SMOKE shapes and the emitted record
    carries the throughput AND latency keys the serving story is judged
    on — tokens/sec, the TTFT / per-output-token split, achieved batch
    occupancy — plus the ISSUE 5 paged/chunked/prefix arm records with
    their config keys and in-band exactness checks."""
    r = subprocess.run([sys.executable, _BENCH],
                       env=_bench_env(BENCH_MODEL="serve"),
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    last = _last_record(r.stdout)
    _assert_names_cpu(last)
    assert last["metric"] == "serve_tokens_per_sec"
    assert last["unit"] == "tokens/sec"
    assert last["value"] > 0
    for key in ("ttft_p50_ms", "ttft_p99_ms", "token_step_p50_ms",
                "token_step_p99_ms", "occupancy_mean",
                "occupancy_max"):
        assert key in last, f"{key} missing from serve record: {last}"
    # Continuous batching demonstrably engaged even in the smoke run.
    assert last["occupancy_max"] > 1
    assert last["requests"]["ok"] >= 16
    # ISSUE 5: the paged-cache config keys and the three arms.
    assert last["block_tokens"] == 16
    assert last["prefill_chunk"] > 0
    assert last["prefix_cache"] is True
    paged = last["paged"]
    for key in ("budget_tokens", "admitted_concurrent", "tokens_per_sec"):
        assert key in paged, f"paged.{key} missing: {paged}"
    assert paged["outputs_match"] is True  # batched == single
    chunked = last["chunked"]
    for key in ("prefill_chunk", "token_step_p99_ms",
                "unchunked_token_step_p99_ms"):
        assert key in chunked, f"chunked.{key} missing: {chunked}"
    assert chunked["outputs_match"] is True
    prefix = last["prefix"]
    for key in ("enabled", "hit_rate", "hit_tokens", "cow_copies"):
        assert key in prefix, f"prefix.{key} missing: {prefix}"
    assert prefix["hit_rate"] > 0  # shared-prefix storm really hit
    # ISSUE 8: attention impl + KV storage dtype are visible in the
    # record, and the two new arms carry their keys with in-band
    # exactness.  The kernel arm runs under the Pallas interpreter
    # on CPU (recorded), so the hermetic bench keeps tracking the
    # kernel's trend while on-chip capture is unavailable.
    assert last["attn_impl"] in ("gather", "kernel")
    assert last["kv_dtype"] == "native"
    kernel = last["kernel"]
    for key in ("interpret", "outputs_match", "tokens_per_sec",
                "gather_tokens_per_sec", "token_step_p50_ms",
                "token_step_p99_ms", "gather_token_step_p50_ms",
                "gather_token_step_p99_ms"):
        assert key in kernel, f"kernel.{key} missing: {kernel}"
    assert kernel["outputs_match"] is True  # kernel == gather, exact
    assert kernel["interpret"] is True      # CPU-hermetic run
    kvarm = last["kv_dtype_arm"]
    for key in ("budget_bytes", "bytes_per_block_bf16",
                "bytes_per_block_int8", "admit_ratio",
                "max_logit_err", "outputs_match"):
        assert key in kvarm, f"kv_dtype_arm.{key} missing: {kvarm}"
    # The fixed-HBM-budget acceptance bar: int8 blocks admit >= 1.8x
    # the concurrent sequences bf16 blocks do, exactness (batched ==
    # single within the int8 engine) intact, logit error bounded.
    assert kvarm["admit_ratio"] >= 1.8
    assert kvarm["outputs_match"] is True
    assert 0 <= kvarm["max_logit_err"] < 0.5
    # ISSUE 6: the fault arm — the bench trajectory records
    # robustness (recovery time + goodput under a seeded plan), not
    # just throughput.
    faults = last["faults"]
    for key in ("seed", "fired", "recovery_s", "goodput_ratio",
                "requeued", "replica_events"):
        assert key in faults, f"faults.{key} missing: {faults}"
    assert faults["recovery_s"] >= 0   # kill→re-admit→answering
    assert 0 < faults["goodput_ratio"] <= 1
    assert faults["fired"], "the seeded plan never fired"
    assert faults["replica_events"]["mark_alive"] >= 1  # scale-up
    assert faults["outputs_match"] is True  # faults never corrupt
    # ISSUE 9: the trace arm records the sampling-overhead contract
    # in-band — tokens/s with the tracer absent (sample=0, the
    # zero-overhead fast path) vs installed at sample=1 with shard
    # files written, exactness intact either way.
    trace = last["trace"]
    for key in ("sample0_tokens_per_sec", "sample1_tokens_per_sec",
                "sampled_throughput_ratio", "outputs_match",
                "spans", "shards"):
        assert key in trace, f"trace.{key} missing: {trace}"
    assert trace["sample0_tokens_per_sec"] > 0
    assert trace["sample1_tokens_per_sec"] > 0
    assert trace["outputs_match"] is True  # tracing never corrupts
    assert trace["spans"] > 0 and trace["shards"] >= 1
    # ISSUE 11: the spec arm — greedy speculation is bit-exact and
    # amortizes the target model (acceptance bar: <= 0.67 target
    # decode invocations per emitted token at k=4, i.e. >= 1.5x).
    spec = last["spec"]
    for key in ("spec_k", "draft_layers", "outputs_match",
                "acceptance_rate", "drafted", "accepted",
                "target_calls_per_token", "tokens_per_sec",
                "baseline_tokens_per_sec"):
        assert key in spec, f"spec.{key} missing: {spec}"
    assert spec["spec_k"] == 4
    assert spec["outputs_match"] is True  # spec-greedy ≡ greedy
    assert spec["drafted"] > 0
    assert spec["target_calls_per_token"] <= 0.67
    # ISSUE 11: the sampling arm — seeded storm determinism and the
    # CoW n-best footprint (n=4 peak pool strictly < 4x the n=1
    # footprint: prompt blocks shared through CoW tables).
    sam = last["sampling"]
    for key in ("temperature", "deterministic", "cow_forks",
                "forked_requests", "n1_peak_pool_bytes",
                "n4_peak_pool_bytes", "pool_share_ratio"):
        assert key in sam, f"sampling.{key} missing: {sam}"
    assert sam["deterministic"] is True  # same seeds → same outputs
    assert sam["cow_forks"] == 3 and sam["forked_requests"] == 1
    assert sam["pool_share_ratio"] < 1.0
    assert sam["n4_peak_pool_bytes"] < 4 * sam["n1_peak_pool_bytes"]
    # ISSUE 13: the autoscale arm — a seeded diurnal sweep under the
    # fleet controller scales up and back down, holds the latency
    # SLO, and browning out never changes latency-tier outputs.
    auto = last["autoscale"]
    for key in ("slo_ms", "slo_held", "latency_p99_ms",
                "scale_events", "brownout_seconds",
                "max_brownout_level", "shed_throughput",
                "outputs_match"):
        assert key in auto, f"autoscale.{key} missing: {auto}"
    assert auto["outputs_match"] is True  # brownout ≠ wrong tokens
    assert auto["scale_events"]["scale_up"] >= 1
    assert auto["scale_events"]["scale_down"] >= 1
    assert auto["brownout_seconds"] >= 0.0
    # ISSUE 15: the multitenant arm — two variants on a shared
    # fleet under weighted fair scheduling, a mid-traffic rolling
    # hot-swap with zero failed requests and post-roll exactness,
    # and the warmed cold-start probe.  fair_share_ratio values are
    # recorded for the trend (tiny smoke storms are too short to
    # gate on); the exactness/zero-failure booleans are hard.
    mt = last["multitenant"]
    for key in ("replicas", "tenants", "fair_share_ratio",
                "swap_zero_failures", "swap_progress",
                "post_roll_exact", "cold_start_ms", "warmup_runs",
                "first_request_ms", "tenant_requests"):
        assert key in mt, f"multitenant.{key} missing: {mt}"
    assert mt["swap_zero_failures"] is True
    assert mt["post_roll_exact"] is True
    assert set(mt["fair_share_ratio"]) == {"gold", "silver",
                                           "bronze"}
    prog = mt["swap_progress"]["tuned"]
    assert prog["done"] == prog["total"] >= 1
    assert mt["cold_start_ms"] > 0     # revived replica re-warmed
    assert mt["warmup_runs"] >= 2      # start + the revival re-run
    assert mt["first_request_ms"] > 0
    for t in ("gold", "silver", "bronze"):
        assert mt["tenant_requests"][t]["ok"] >= 1
    # ISSUE 16: the tiered arm — a fixed HBM budget stormed with
    # long-decode requests keeps >= 2x the untiered concurrency by
    # swapping host-ward instead of preempting (zero preemptions,
    # bit-identical outputs), and the migration storm serves a cold
    # replica's shared prefix from a peer's published blocks at
    # least as well as the single-replica prefix arm did locally.
    tiered = last["tiered"]
    for key in ("pool_blocks", "admitted_concurrent",
                "untiered_admitted_concurrent", "admit_ratio",
                "outputs_match", "preempted", "swapped_out_seqs",
                "tier_fault_stall_p50_ms", "tier_fault_stall_p99_ms",
                "migrated_tokens", "migrated_hit_tokens",
                "migration_failures", "migration_outputs_match"):
        assert key in tiered, f"tiered.{key} missing: {tiered}"
    assert tiered["admit_ratio"] >= 2.0
    assert tiered["outputs_match"] is True
    assert tiered["preempted"] == 0
    assert tiered["swapped_out_seqs"] >= 1
    assert tiered["migration_outputs_match"] is True
    assert tiered["migration_failures"] == 0
    assert tiered["migrated_tokens"] > 0
    assert tiered["migrated_hit_tokens"] >= last["prefix"]["hit_tokens"]
    # ISSUE 18: the router arm — the hvdroute front door in front of
    # a 2-endpoint fleet keeps the zero-lost contract (every routed
    # response bit-identical to the single-engine reference), keeps
    # prefix affinity, and the hedged sub-arm's tail beats the
    # seeded slow-route train it raced.
    route = last["router"]
    for key in ("endpoints", "requests", "zero_lost",
                "affinity_hit_rate", "retries", "ejections",
                "hedges", "hedges_won", "unhedged_p99_ms",
                "hedged_p99_ms", "hedge_win"):
        assert key in route, f"router.{key} missing: {route}"
    assert route["zero_lost"] is True  # routed ≡ reference, exact
    assert route["endpoints"] >= 2
    assert route["requests"] >= 8
    assert 0 <= route["affinity_hit_rate"] <= 1
    assert route["hedges"] >= 1        # the hedge arm really raced
    # ISSUE 19: the stream arm — SSE streaming of the same prompts
    # is bit-exact vs buffered, the client-perceived first token
    # beats the buffered full-response wait, a mid-stream hangup
    # frees every KV block, and grammar-constrained sampled
    # completions are 100% schema-valid.
    stream = last["stream"]
    for key in ("sessions", "outputs_match", "buffered_p50_ms",
                "ttft_p50_ms", "ttft_p99_ms", "intertoken_p99_ms",
                "ttft_win", "client_gone_kv_used",
                "client_gone_counted", "schema_valid",
                "schema_total", "schema_valid_rate"):
        assert key in stream, f"stream.{key} missing: {stream}"
    assert stream["outputs_match"] is True  # streamed ≡ buffered
    assert stream["client_gone_kv_used"] == 0  # hangup freed blocks
    assert stream["client_gone_counted"] >= 1
    assert stream["schema_valid_rate"] == 1.0


@pytest.mark.slow  # ~67s: a real ResNet train at smoke shapes
def test_resnet_bench_smoke_record_carries_census():
    """The default (ResNet-50) arm end-to-end on CPU at BENCH_SMOKE
    shapes with HVD_ANALYZE=1: one record, tagged with its device, that
    carries the step program's collective, memory and comm census."""
    r = subprocess.run([sys.executable, _BENCH],
                       env=_bench_env(HVD_ANALYZE="1"),
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-1500:]
    last = _last_record(r.stdout)
    _assert_names_cpu(last)
    assert last["metric"] == "resnet50_synthetic_images_per_sec"
    assert "SMOKE" in last["config"]
    # HVD_ANALYZE=1 rode along: the shard_step hook checked the step
    # program on first compile and bench surfaced its collective
    # census (count + payload bytes per primitive) in the record.
    census = last["collective_census"]
    assert census["psum"]["count"] >= 1
    assert census["psum"]["bytes"] > 0
    assert last["analysis_findings"] == 0
    # ... and the hvdmem liveness walk rode the same trace: the
    # step's peak live footprint + allocation breakdown land under
    # memory_census (analysis/memplan.py).
    mem = last["memory_census"]
    assert mem["peak_live_bytes"] > 0
    assert mem["input_bytes"] > 0
    assert mem["by_primitive"]
    # ... and the hvdshard sharding walk (analysis/shardplan.py)
    # rode the same trace too: wire bytes per collective + per mesh
    # axis land under comm_census.
    comm = last["comm_census"]
    assert comm["by_primitive"]["psum"]["wire_bytes"] > 0
    assert comm["total_wire_bytes"] > 0
    assert comm["axes_declared"]
