"""The control of a cell's ``correct``: the same cell with the plain
reference, computed in a lower precision, in the program's place.

    python3 benchmarks/tests/control.py --workload <cell> --seed <n>

makes a copy of the benchmark in a temporary directory, adds to it, as
new files and manifest entries, a configuration that is the cell's own
but for its job (``<job>_control``) and a cell over it (one warm-up step,
no traced steps to speak of), runs that cell through ``benchmarks/run.py``
there and exits 0 only if its last line says ``correct`` false: the
limits of ``correct`` told the stated precision from the one below.  On
the chip for the real cell, on the CPU for the rehearsal cell (the tests
call ``add_control``).
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
SUFFIX = "-control"


def add_control(copy: str, cell_name: str) -> str:
    """Add the control of ``cell_name`` to the benchmark in ``copy``;
    returns the control cell's name."""
    bench = os.path.join(copy, "benchmarks")
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(w for w in manifest["workloads"] if w["name"] == cell_name)
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    with open(os.path.join(copy, config_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench, "workloads", cell_name + ".json")) as f:
        cell = json.load(f)
    config.update(name=config["name"] + SUFFIX,
                  job=config["job"] + "_control")
    cell.update(name=cell_name + SUFFIX, config=config["name"],
                traffic=dict(cell["traffic"], warmup_steps=1,
                             name=cell["traffic"]["name"] + SUFFIX))
    config_file = f"benchmarks/configs/{config['name']}.json"
    for path, content in ((os.path.join(copy, config_file), config),
                          (os.path.join(bench, "workloads",
                                        cell["name"] + ".json"), cell)):
        with open(path, "x") as f:
            json.dump(content, f, indent=1)
    manifest["configs"].append(dict(config_entry, name=config["name"],
                                    file=config_file))
    manifest["workloads"].append(dict(
        entry, name=cell["name"], config=config["name"],
        traffic=cell["traffic"]["name"]))
    for metric in manifest["end_to_end"]:
        if cell_name in metric.get("workloads", ()):
            metric["workloads"].append(cell["name"])
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return cell["name"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    copy = tempfile.mkdtemp(prefix="bench_control_")
    try:
        shutil.copytree(BENCH_DIR, os.path.join(copy, "benchmarks"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
        control = add_control(copy, args.workload)
        proc = subprocess.run(
            [sys.executable, os.path.join(copy, "benchmarks", "run.py"),
             "--workload", control, "--seed", str(args.seed), "--seconds",
             "1", "--trace", "0"],
            cwd=copy, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, PYTHONPATH=os.pathsep.join(filter(
                None, [ROOT, os.environ.get("PYTHONPATH")]))))
    finally:
        shutil.rmtree(copy, ignore_errors=True)
    print(proc.stdout, end="", flush=True)
    if proc.returncode:
        print(f"control: run.py exited {proc.returncode}", flush=True)
        return 1
    correct = json.loads(proc.stdout.strip().rsplit("\n", 1)[-1])["correct"]
    print(f"control: {control} came out correct={correct}; "
          + ("the limits tell the precisions apart" if not correct else
             "the limits CANNOT tell the precisions apart"), flush=True)
    return 1 if correct else 0


if __name__ == "__main__":
    sys.exit(main())
