#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` and the files it names (``harness/manifest.py``),
hands the cell to its runner, and prints one JSON object as the last line
of standard output: the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics (and a breakdown of the device trace) with ``--trace 1``.
Exits non-zero with no result line where the platform or the number of
chips is not what the cell names, or the device is not in the peaks table.
"""

import time

PROCESS_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))  # the program's package
sys.path.insert(0, BENCH_DIR)                   # harness, found by name

from harness import manifest as mf  # noqa: E402
from harness import result, trace as tracing  # noqa: E402
from harness.context import open_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest, run = open_run(args.workload, args.seed, args.seconds,
                             bool(args.trace), PROCESS_START)
    run.results = mf.load_module("runners", run.cell["runner"]).run(run)
    res = run.results

    metrics = {}
    if args.trace:
        if res.get("trace_dir"):
            run.traced = tracing.reduce(tracing.load(res["trace_dir"]))
        for m in manifest.metrics("per_layer", args.workload):
            value = mf.load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest.metrics("end_to_end", args.workload):
            metrics[m["name"]] = {"value": res["end_to_end"][m["name"]],
                                  "unit": m["unit"]}

    device = dict(run.device, count=run.chips,
                  memory_peak_bytes=res["memory_peak_bytes"])
    breakdown = None
    if run.traced:
        device.update(busy_s=run.traced["busy_s"],
                      window_s=run.traced["window_s"])
        breakdown = tracing.breakdown(run.traced)
    for name, m in sorted(metrics.items()):
        result.log(f"metric {name} = {m['value']} {m['unit']}")
    result.emit(res["correct"], res["attempted"], res["failed"], metrics,
                device, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
