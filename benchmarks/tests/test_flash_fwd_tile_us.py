"""``flash_fwd_tile_us.train``: the reader on a hand-built trace of the
cell's step (kernel time over the tiles the forward kernel computed),
against a step that runs no such kernel, and its entry in the manifest."""

import json
import os
import types

import pytest

from conftest import ROOT

from harness import manifest as mf
from harness import scope_times

NAME = "flash_fwd_tile_us.train"
CELL = "sdar30b-train-blockdiff-4k"
STEP = "jit(local_step)/shard_map/decoder/hvd::bd_attention/"
NAMES = {"custom-call.7": STEP + "hvd_flash_fwd/pallas_call",
         "custom-call.9": STEP + "transpose(jvp(hvd_flash_bwd_dq))/pallas_call",
         "fusion.3": STEP + "mul"}
CODES = {"custom-call.7": "custom-call", "custom-call.9": "custom-call",
         "fusion.3": "fusion"}


def read(ops):
    """The reader's value for one device's operations ``(instruction,
    nanoseconds)`` in two executions of the step, under the cell's own
    configuration and traffic."""
    event = "%{0} = f32[8]{{0}} {1}(%x)".format
    devices = {"/device:TPU:0": {
        "ops": [(event(inst, CODES[inst]), 100 * n, ns)
                for n, (inst, ns) in enumerate(ops)],
        "modules": [("jit_local_step(5)", 0, 1000),
                    ("jit_local_step(5)", 1000, 1000)]}}
    table = scope_times.reduce(devices, NAMES, CODES, scope_times.KERNELS)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "workloads",
                           CELL + ".json")) as f:
        cell = json.load(f)
    run = types.SimpleNamespace(scopes={"scope_times": table}, config=config,
                                cell=cell)
    return mf.load_module("layer_metrics", NAME).read(run)


def test_kernel_time_over_the_tiles_the_forward_kernel_computed():
    # Two steps x 4 sequences x 5 layers x 32 heads x the 80 tiles the mask
    # keeps; the backward kernel's and the fusion's time are not counted.
    tiles = 2 * 4 * 5 * 32 * 80
    got = read([("custom-call.7", 150_000), ("custom-call.9", 70_000),
                ("fusion.3", 9_000), ("custom-call.7", 106_000)])
    assert got == pytest.approx(256_000 / 1e3 / tiles)
    # The ledger's PR 29 line: 1.0305 s in 8 steps is 2.52 us a tile.
    assert 1.0305e6 / (8 * 4 * 5 * 32 * 80) == pytest.approx(2.516, abs=1e-3)


def test_absent_where_the_step_runs_no_forward_kernel():
    assert read([("custom-call.9", 70_000), ("fusion.3", 9_000)]) is None


def test_absent_without_a_device_trace():
    run = types.SimpleNamespace(scopes={"scope_times": None})
    assert mf.load_module("layer_metrics", NAME).read(run) is None


def test_absent_where_the_program_exports_no_count(monkeypatch):
    from horovod_tpu.parallel import flash
    monkeypatch.delattr(flash, "grid_steps")
    assert read([("custom-call.7", 150_000)]) is None


def test_manifest_lists_the_reader_for_the_block_diffusion_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_samples_per_s", "workloads": [CELL]}
