"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found here **by name**:

    configs/<config>.json       the sizes as they are run
    workloads/<cell>.json       configuration, chips, runner, platform, traffic
    jobs/<job>.py               builds a configuration's program and reference
    flops/<config>.py           operations of one sample, from shapes
    layer_metrics/<metric>.py   one reader: ``read(run) -> value or None``
    runners/<runner>.py         what a window is for one kind of program

A later PR adds files and manifest entries and edits none.
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)  # the checkout: BENCHMARK.json lives here


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py``; names may hold ``.`` and ``-``."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind}/{name}.py in the benchmark")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}".replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.data = json.load(f)

    def entry(self, group: str, name: str) -> dict:
        for e in self.data[group]:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {name!r} under {group!r}")

    def cell(self, name: str):
        """``(entry, cell file, configuration file)`` of the cell ``name``."""
        entry = self.entry("workloads", name)
        with open(os.path.join(ROOT, self.entry(
                "configs", entry["config"])["file"])) as f:
            return entry, load_json("workloads", name + ".json"), json.load(f)

    def metrics(self, group: str, cell: str):
        """The metrics of ``end_to_end`` or ``per_layer`` that ``cell``
        reports: those with no ``workloads`` key, or one that lists it."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]
