"""A copy of the benchmark with the rehearsal cells of ``cells/`` added.

The copy holds ``BENCHMARK.json`` and ``benchmarks/`` as committed; the
cells, the configuration and the per-layer metric of ``cells/`` go in as
**new files** and new manifest entries, and no file that was there is
edited except the manifest, which gains entries.  That is how a later PR
adds a cell, and the tests run the rehearsal cells through it.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


def make_copy(dest: str) -> str:
    """Copy the benchmark to ``dest`` and add the rehearsal cells; returns
    ``dest``.  The program's package is not copied: run with ``PYTHONPATH``
    naming the checkout that holds ``horovod_tpu``."""
    shutil.copytree(BENCH_DIR, os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cells = os.path.join(HERE, "cells")
    for kind in ("configs", "workloads", "layer_metrics"):
        for name in os.listdir(os.path.join(cells, kind)):
            target = os.path.join(dest, "benchmarks", kind, name)
            if os.path.exists(target):
                raise FileExistsError(f"{target}: a rehearsal file may "
                                      f"not replace a file that is there")
            shutil.copy(os.path.join(cells, kind, name), target)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(cells, "manifest_entries.json")) as f:
        added = json.load(f)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        manifest[group].extend(added[group])
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = (metric["workloads"]
                                   + added["extend"].get(metric["name"], []))
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return dest


if __name__ == "__main__":
    print(make_copy(sys.argv[1]))
