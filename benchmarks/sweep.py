#!/usr/bin/env python3
"""Find a serving cell's knee: one server, the cell's mix at several rates.

    python3 benchmarks/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 8,10,12.5

The benchmark itself never searches for a rate: a cell's ``rate_rps`` is a
number in its file.  This tool is how that number was found, once, on the
chip (PERF.md holds the table): the knee is the highest rate at which the
tokens received in the window are at least 97 % of the tokens offered and
nothing is shed, and the cell runs at four fifths of it.  Prints one JSON
line per rate.
"""

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)

from harness import manifest as mf  # noqa: E402
from harness.context import open_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)

    _, run = open_run(args.workload, args.seed, args.seconds, False,
                      PROCESS_START)
    cell, device = run.cell, run.device
    runner = mf.load_module("runners", cell["runner"])
    server, port, metrics, _, _ = runner.start_server(run)
    engine = server.scheduler.replicas[0].engine
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(cell["traffic"], rate_rps=rate)
            m = runner.measure(run, port, metrics, traffic, args.seconds)
            while engine.active_count or engine.batcher.depth():
                time.sleep(0.2)
            d = m["delta"]
            print("sweep: " + json.dumps({
                "rate_rps": rate, "attempted": m["attempted"],
                "failed": m["failed"],
                "offered_tokens_per_s": m["offered_tokens_per_s"],
                "serve_tokens_per_s": m["serve_tokens_per_s"],
                "received_share": (m["serve_tokens_per_s"]
                                   / m["offered_tokens_per_s"]),
                "shed": m["outcomes"].get("shed", 0)
                + m["outcomes"].get("expired", 0),
                "ttft_p95_ms": m["ttft_p95_ms"],
                "tpot_p95_ms": m["tpot_p95_ms"],
                "occupancy": d["occupancy_sum"]
                / max(d["occupancy_samples"], 1),
                "decode_step_ms": d["step_sum_ms"] / max(d["step_count"], 1),
                "queue_ms": d["queue_sum_ms"] / max(d["queue_count"], 1),
                "device": device}), flush=True)
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
