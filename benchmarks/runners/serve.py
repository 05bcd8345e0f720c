"""What a window is for the serving engine.

This process holds the chip: ``hvd.init()``, the seeded weights, one
replica built by ``build_replicas(..., warmup=True)`` and ``ServeServer``
on a local port, exactly the objects ``hvdserve`` stands up, because only
the process that holds the chip can trace it.  The load comes from a child
that never imports JAX (``harness/loadgen.py``): open loop, each request
sent when it is due.  Requests due in the ramp before the window bring the
engine to its steady occupancy and are not counted; requests due in the
window are followed to their end after it (a bounded drain).  Set-up ends,
and the window starts, at the instant the first counted request is due.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

from harness import manifest as mf
from harness import loadgen, result, stats, trace as tracing


def post(port: int, path: str, payload: dict, timeout: float = 600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def check_correct(run, job, params, port: int) -> bool:
    """Prefill through ``/score`` and decode through ``/generate`` with
    ``logprobs``, each log-probability beside the plain reference's for the
    same token given the same prefix."""
    import random
    config, spec = run.config, run.config["correct"]
    rng = random.Random(run.seed * 1000003 + 11)
    vocab = config["vocab_size"]
    worst = 0.0
    for length in spec["score_lengths"]:
        tokens = [rng.randrange(vocab) for _ in range(length)]
        got = [e["logprob"] for e in post(
            port, "/score", {"tokens": tokens})["logprobs"][1:]]
        want = job.reference_token_logprobs(config, params, tokens)
        err = max(abs(g - w) for g, w in zip(got, want))
        result.log(f"correct: /score {length} tokens: max |logprob error| "
                   f"{err:.3e} over {len(got)} positions (reference mean "
                   f"{sum(want) / len(want):.3f})")
        worst = max(worst, err)
    for length in spec["generate_prompt_lengths"]:
        prompt = [rng.randrange(vocab) for _ in range(length)]
        body = post(port, "/generate", {
            "tokens": prompt, "max_new_tokens": spec["generate_tokens"],
            "logprobs": 1})
        got = [e["logprob"] for e in body["logprobs"]]
        sequence = prompt + body["tokens"]
        want = job.reference_token_logprobs(
            config, params, sequence)[length - 1:]
        ok = (len(body["tokens"]) == spec["generate_tokens"]
              and [e["token"] for e in body["logprobs"]] == body["tokens"])
        err = max(abs(g - w) for g, w in zip(got, want)) if ok \
            else float("inf")
        result.log(f"correct: /generate {length}-token prompt, "
                   f"{len(body['tokens'])} greedy tokens: max |logprob "
                   f"error| {err:.3e}")
        worst = max(worst, err)
    result.log(f"correct: worst error {worst:.3e}, tolerance "
               f"{spec['logprob_tolerance']}")
    return worst <= spec["logprob_tolerance"]


def counters(metrics) -> dict:
    """The program's own counters and sums (``serve/metrics.py``)."""
    with metrics._lock:
        queue = metrics.stage_ms.get("queue")
        return {
            "queue_sum_ms": queue.sum if queue else 0.0,
            "queue_count": queue.count if queue else 0,
            "step_sum_ms": metrics.token_step_ms.sum,
            "step_count": metrics.token_step_ms.count,
            "occupancy_sum": metrics.occupancy_sum,
            "occupancy_samples": metrics.occupancy_samples,
            "tokens_total": metrics.tokens_total,
            "prefill_tokens": metrics.prefill_tokens_total,
            "requests": dict(metrics.requests),
        }


def measure(run, port: int, metrics, traffic: dict, seconds: float,
            trace_dir=None) -> dict:
    """One ramp, window and drain at ``traffic``; returns the client's
    records reduced to metrics, and the counters' change over the window."""
    requests = loadgen.schedule(traffic, run.seed, seconds)
    lead = 1.5  # for the child to start and read its plan
    t0 = time.monotonic() + lead + traffic["ramp_seconds"]
    plan = os.path.join(run.out_dir, "plan.json")
    out = os.path.join(run.out_dir, "client.json")
    if os.path.exists(out):
        os.remove(out)
    with open(plan, "w") as f:
        json.dump({"t0_monotonic": t0, "port": port, "seconds": seconds,
                   "drain_seconds": traffic["drain_seconds"],
                   "request_timeout_s": traffic["drain_seconds"] + seconds,
                   "requests": requests}, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(mf.BENCH_DIR, "harness", "loadgen.py"),
         plan, out])
    try:
        time.sleep(max(0.0, t0 - time.monotonic()))
        window_start = time.monotonic()
        before = counters(metrics)
        compiled = run.compiles.snapshot()["programs"]
        if trace_dir is not None:
            time.sleep(traffic["trace_after_seconds"])
            tracing.start(trace_dir)
            time.sleep(traffic["trace_seconds"])
            tracing.stop()
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        after = counters(metrics)
        compiled = run.compiles.snapshot()["programs"] - compiled
        child.wait(timeout=traffic["drain_seconds"] + 30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if not os.path.exists(out):
        raise SystemExit("the load generator ended without its records")
    with open(out) as f:
        client = json.load(f)

    records = client["records"]
    counted = [r for r in records if r["counted"]]
    good = [r for r in counted if r.get("status") == 200 and r["finished"]
            and "error" not in r and r["events"]]
    ttft = [(r["events"][0][0] - r["due"]) * 1e3 for r in good]
    tpot = []
    for r in good:
        n = sum(k for _, k in r["events"])
        if n > 1:
            tpot.append((r["events"][-1][0] - r["events"][0][0])
                        / (n - 1) * 1e3)
    late = [(r["sent"] - r["due"]) * 1e3 for r in counted if "sent" in r]
    received = sum(k for r in records for t, k in r["events"]
                   if 0.0 <= t < seconds)
    offered = sum(r["max_new_tokens"] for r in counted)
    delta = {k: after[k] - before[k] for k in before if k != "requests"}
    outcomes = {k: after["requests"].get(k, 0) - before["requests"].get(k, 0)
                for k in after["requests"]}
    result.log(f"window: {len(counted)} requests due in {seconds} s at "
               f"{traffic['rate_rps']} requests/s, {len(good)} finished, "
               f"{offered} tokens offered, {received} received in the "
               f"window; outcomes {outcomes}; started "
               f"{(window_start - t0) * 1e3:.2f} ms after it was due")
    result.log(f"window: ttft ms {stats.summary(ttft)}")
    result.log(f"window: tpot ms {stats.summary(tpot)}")
    result.log(f"window: generator lateness ms {stats.summary(late)}")
    result.log(f"window: counters {delta}")
    return {
        "attempted": len(counted), "failed": len(counted) - len(good),
        "ttft_p95_ms": stats.percentile(ttft, 95)[0],
        "tpot_p95_ms": stats.percentile(tpot, 95)[0],
        "serve_tokens_per_s": received / seconds,
        "offered_tokens_per_s": offered / seconds,
        "loadgen_late_p95_ms": stats.percentile(late, 95)[0],
        "delta": delta, "outcomes": outcomes,
        "compiles_in_window": compiled, "t0": t0,
    }


def start_server(run):
    """``(server, port, metrics, job, params)``: the replica world of
    ``hvdserve`` in this process, warmed up."""
    import horovod_tpu as hvd
    from horovod_tpu.serve.replica import build_replicas
    from horovod_tpu.serve.server import ServeServer

    config = run.config
    result.mark(run, "imports done, hvd.init")
    hvd.init()
    job = mf.load_module("jobs", config["job"])
    params = job.seeded_params(config, run.seed)
    serve = config["serve"]
    result.mark(run, "weights made, engine and its warm-up")
    scheduler = build_replicas(
        job.adapter_factory(config, params), num_replicas=1,
        max_batch=serve["max_batch"], num_blocks=serve["num_blocks"],
        warmup=True)
    server = ServeServer(scheduler)
    port = server.start(port=0, host="127.0.0.1")
    engine = scheduler.replicas[0].engine
    result.log(f"server: port {port}, attention {engine.attn_impl}, "
               f"{engine.blocks.capacity} blocks, warm-up "
               f"{engine.last_warmup_ms / 1e3:.1f} s")
    return server, port, scheduler.metrics, job, params


def run(run) -> dict:
    import jax
    server, port, metrics, job, params = start_server(run)
    try:
        result.mark(run, "server up, correct")
        correct = check_correct(run, job, params, port)
        result.mark(run, "correct done, ramp")
        setup = run.compiles.snapshot()
        result.log(f"set-up: {setup['programs']} programs requested, "
                   f"{setup['cache_hits']} found in the compile cache, "
                   f"{setup['seconds']:.1f} s in the backend")
        trace_dir = (os.path.join(run.out_dir, "trace") if run.trace
                     else None)
        measured = measure(run, port, metrics, run.cell["traffic"],
                           run.seconds, trace_dir)
    finally:
        server.stop()
    result.log(f"memory: {jax.devices()[0].memory_stats()}")
    measured.update(
        correct=correct and measured["failed"] == 0,
        end_to_end={k: measured[k] for k in (
            "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s")},
        trace_dir=trace_dir,
        memory_peak_bytes=result.memory_peak_bytes(jax.devices()[:1]))
    measured["end_to_end"]["setup_s"] = measured["t0"] - run.process_start
    return measured
