"""``flash_grid_steps_per_tile.train``: the reader on the cell's own
configuration, against a program that exports the count and against one
that does not (the parent of the PR that added it)."""

import json
import os
import types

from conftest import ROOT

from harness import manifest as mf

NAME = "flash_grid_steps_per_tile.train"
CELL = "sdar30b-train-blockdiff-4k"


def read():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        run = types.SimpleNamespace(config=json.load(f))
    return mf.load_module("layer_metrics", NAME).read(run)


def test_no_step_computes_nothing_at_the_cells_sizes():
    from horovod_tpu.parallel import flash
    assert read() == 1.0
    # What the ratio is made of: 80 tiles a query head in each kernel.
    assert flash.grid_steps(flash.block_diffusion_mask(4, 4096), 8192, 512,
                            512, 32, 4) == (3 * 32 * 80, 3 * 32 * 80)


def test_absent_where_the_program_exports_no_count(monkeypatch):
    from horovod_tpu.parallel import flash
    monkeypatch.delattr(flash, "grid_steps")
    assert read() is None


def test_manifest_lists_the_reader_for_the_block_diffusion_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_samples_per_s", "workloads": [CELL]}
