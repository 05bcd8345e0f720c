"""The AFMoE next-token job through ``runners/train.py`` on the CPU: the
rehearsal cell ``trinity-tiny-train-cpu1`` enters a copy of the benchmark as
new files and manifest entries (``cells/manifest_entries_trinity.json`` on
top of ``overlay.py``'s), as the real cell entered the benchmark."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

from conftest import HERE, run_cell

CELL = "trinity-tiny-train-cpu1"
NEW_READERS = {"window_attention_share.train", "full_attention_share.train",
               "shared_expert_share.train", "mixed_attention_roofline.train",
               "window_tiles_kept_share.train",
               "mixed_flash_fwd_tile_us.train",
               "mixed_flash_grid_steps_per_tile.train",
               "dense_mlp_share.train"}
COUNTS = {"window_tiles_kept_share.train",
          "mixed_flash_grid_steps_per_tile.train"}


@pytest.fixture(scope="module")
def trinity_copy(tmp_path_factory):
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_trinity")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    with open(os.path.join(HERE, "cells",
                           "manifest_entries_trinity.json")) as f:
        added = json.load(f)
    manifest["configs"] += added["configs"]
    manifest["workloads"] += added["workloads"]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if metric["name"] in added["extend"]:
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)
    return copy


def run_named(copy, cell, trace):
    rc, lines, err = run_cell(copy, cell, trace, seconds=1)
    assert rc == 0, err[-3000:]
    return json.loads(lines[-1]), lines


def test_cell_and_its_reference(trinity_copy):
    last, lines = run_named(trinity_copy, CELL, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
    routing = [line for line in lines if "routing:" in line]
    assert len(routing) == 2 and "0.000 % of its choices" in routing[1]
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and gradients[0].endswith("all inside")


def test_control_in_a_lower_precision_comes_out_not_correct(trinity_copy):
    """The cell's own files with the reference, its matrices rounded to
    ``correct.control_dtype``, in the program's place (``control.py``,
    ``jobs/afmoe_control.py``): the loss stays inside its limit, the
    gradients do not, and the runner's comparison says so."""
    import control
    last, lines = run_named(
        trinity_copy, control.add_control(trinity_copy, CELL), 0)
    assert last["correct"] is False and last["failed"] == 0
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and "OUTSIDE: " in gradients[0]
    compared = next(line for line in lines if "bench: correct:" in line)
    assert "reference [inf, " in compared


def test_cell_traced_reports_counts_but_no_device_metric(trinity_copy):
    """Of the eight new readers the two counts read a value on the CPU; the
    six that read a device trace are absent, never zero."""
    last, _ = run_named(trinity_copy, CELL, 1)
    assert last["correct"] is True
    assert last["metrics"]["compiles_in_window.train"]["value"] == 0
    assert last["metrics"]["steps_in_window.train"]["value"] == \
        last["attempted"]
    # 64 positions in tiles of 16 under a window of 16: a row holds the
    # diagonal tile and the one the window's far side cuts, 7 of 10 tiles.
    assert last["metrics"]["window_tiles_kept_share.train"] == {
        "value": 0.7, "unit": "ratio"}
    assert last["metrics"]["mixed_flash_grid_steps_per_tile.train"] == {
        "value": 1.0, "unit": "ratio"}
    assert NEW_READERS & set(last["metrics"]) == COUNTS
    assert not {"moe_share.train", "expert_matmul_roofline.train",
                "mfu.train"} & set(last["metrics"])


def test_every_new_reader_has_its_file_and_its_entry():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert os.path.exists(os.path.join(
            root, "benchmarks", "layer_metrics", name + ".py"))
        assert entries[name]["workloads"] == ["trinity-mini-train-8k"]
        assert entries[name]["moves"] == "train_samples_per_s"
    joined = [m["name"] for m in manifest["per_layer"]
              if "trinity-mini-train-8k" in m["workloads"]]
    assert len(joined) == 14 and len(set(joined) - NEW_READERS) == 6


def test_forward_kernel_time_over_the_tile_runs_of_a_mixed_stack():
    """``mixed_flash_fwd_tile_us.train`` on a hand-built trace under the
    cell's own files: the window layers' forward kernel runs twice (their
    type is not kept across the recomputation), the full layer's once."""
    from harness import manifest as mf
    from harness import scope_times
    from horovod_tpu.models import afmoe
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "configs", "trinity-mini-ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "workloads",
                           "trinity-mini-train-8k.json")) as f:
        cell = json.load(f)
    step = "jit(local_step)/shard_map/decoder/hvd::window_attention/"
    names = {"custom-call.7": step + "hvd_flash_fwd/pallas_call",
             "custom-call.9": step + "hvd_flash_bwd_dq/pallas_call"}
    codes = dict.fromkeys(names, "custom-call")
    event = "%{0} = f32[8]{{0}} custom-call(%x)".format
    devices = {"/device:TPU:0": {
        "ops": [(event("custom-call.7"), 0, 150_000),
                (event("custom-call.9"), 100, 70_000),
                (event("custom-call.7"), 200, 90_000)],
        "modules": [("jit_local_step(5)", 0, 1000)] * 2}}
    table = scope_times.reduce(devices, names, codes, scope_times.KERNELS)
    read = mf.load_module("layer_metrics",
                          "mixed_flash_fwd_tile_us.train").read
    run = types.SimpleNamespace(scopes={"scope_times": table},
                                config=config, cell=cell)
    assert afmoe.KEPT_ATTENTION == (afmoe.FULL,)
    # 2 steps x 4 sequences x 32 heads x (4 window layers x 70 tiles twice
    # + the full layer's 136 once): the 89,088 tile runs a step of PERF.md.
    assert 4 * 32 * (4 * 70 * 2 + 136) == 89_088
    assert read(run) == pytest.approx(240_000 / 1e3 / (2 * 89_088))
    run.scopes = {"scope_times": None}
    assert read(run) is None


def flops_module():
    spec = importlib.util.spec_from_file_location(
        "flops_afmoe", os.path.join(os.path.dirname(HERE), "flops",
                                    "afmoe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("length,window", [(64, 16), (48, 48), (40, 100),
                                           (33, 1), (128, 32)])
def test_operations_count_the_pairs_the_masks_keep(length, window):
    """``flops/afmoe.py: attended_pairs`` against a brute-force count of
    the dense mask."""
    ahead = np.arange(length)[:, None] - np.arange(length)[None, :]
    assert flops_module().attended_pairs(length, window) == \
        int(((ahead >= 0) & (ahead < window)).sum())


def test_operations_of_the_cell_by_part():
    """The issue's count at the published widths: 18.14 TFLOP a sequence."""
    flops = flops_module()
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "trinity-mini-ep8.json")) as f:
        config = json.load(f)
    assert flops.attended_pairs(8192, 2048) == 14_681_088
    assert flops.attended_pairs(8192, 8192) == 33_558_528
    parts = flops.train_flops_by_part(config)
    assert {k: round(v / 1e12, 2) for k, v in parts.items()} == dict(
        projections=6.70, attention=4.54, head=2.52, dense_mlp=1.86,
        shared=1.24, experts=1.24, router=0.05)
    assert parts["attention"] == (4 * 14_681_088 + 33_558_528) * 32 \
        * 4 * 128 * 3
    assert flops.train_flops_per_sample(config) == pytest.approx(18.14e12,
                                                                 rel=1e-3)
