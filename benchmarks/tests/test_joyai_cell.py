"""The JoyAI-LLM-Flash job through ``runners/train.py`` on the CPU: the
rehearsal cell ``joyai-tiny-train-cpu1`` enters a copy of the benchmark as
new files and manifest entries (``cells/manifest_entries_joyai.json`` on top
of ``overlay.py``'s), as the real cell entered the benchmark."""

import importlib.util
import json
import os
import types

import pytest

from conftest import HERE, run_cell

CELL = "joyai-tiny-train-cpu1"
REAL = "joyai-flash-train-mtp-8k"
NEW_READERS = {"mla_attention_share.train", "mla_expand_share.train",
               "mtp_share.train", "mla_attention_roofline.train",
               "mla_flash_fwd_tile_us.train",
               "mla_flash_grid_steps_per_tile.train"}
COUNTS = {"mla_flash_grid_steps_per_tile.train"}
APPENDED = {"mfu.train", "device_idle_share.train",
            "compiles_in_window.train", "host_dispatch_ms.train",
            "moe_share.train", "expert_matmul_roofline.train"}


@pytest.fixture(scope="module")
def joyai_copy(tmp_path_factory):
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_joyai")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    with open(os.path.join(HERE, "cells",
                           "manifest_entries_joyai.json")) as f:
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


def test_cell_and_its_reference(joyai_copy):
    last, lines = run_named(joyai_copy, CELL, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
    routing = [line for line in lines if "routing:" in line]
    assert len(routing) == 2 and "0.000 % of its choices" in routing[1]
    assert "the MTP block last" in routing[0]
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and gradients[0].endswith("all inside")
    assert "mtp.w_kvb" in gradients[0] and "w_eh" in gradients[0]
    # Both losses apart, by the program and by the reference.
    apart = [line for line in lines if "bench: losses:" in line]
    assert len(apart) == 2 and all(
        "L_main" in line and "L_mtp" in line for line in apart)


def test_control_in_a_lower_precision_comes_out_not_correct(joyai_copy):
    """The cell's own files with the reference, its matrices rounded to
    ``correct.control_dtype``, in the program's place (``control.py``,
    ``jobs/joyai_flash_control.py``): the loss stays inside its limit, the
    gradients do not, and the runner's comparison says so."""
    import control
    last, lines = run_named(
        joyai_copy, control.add_control(joyai_copy, CELL), 0)
    assert last["correct"] is False and last["failed"] == 0
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and "OUTSIDE: " in gradients[0]
    compared = next(line for line in lines if "bench: correct:" in line)
    assert "reference [inf, " in compared


def test_cell_traced_reports_counts_but_no_device_metric(joyai_copy):
    """Of the six new readers the count reads a value on the CPU; the five
    that read a device trace are absent, never zero."""
    last, _ = run_named(joyai_copy, CELL, 1)
    assert last["correct"] is True
    assert last["metrics"]["compiles_in_window.train"]["value"] == 0
    assert last["metrics"]["steps_in_window.train"]["value"] == \
        last["attempted"]
    assert last["metrics"]["mla_flash_grid_steps_per_tile.train"] == {
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
        assert REAL in entries[name]["workloads"]
        assert entries[name]["moves"] == "train_samples_per_s"
    joined = {m["name"] for m in manifest["per_layer"]
              if REAL in m["workloads"]}
    assert joined >= NEW_READERS | APPENDED
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL)
    assert cell["chips"] == 1 and cell["config"] == "joyai-llm-flash-ep16"


def load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}".replace(".", "_"),
        os.path.join(os.path.dirname(HERE), kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_files():
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "configs",
                           "joyai-llm-flash-ep16.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "workloads", REAL + ".json")) as f:
        return config, json.load(f)


def test_forward_kernel_time_over_the_tile_runs_of_the_cell():
    """``mla_flash_fwd_tile_us.train`` on a hand-built trace under the
    cell's own files: six layers of latent attention (the MTP module's
    block among them), 136 tiles a head, the forward kernel once more in
    the backward pass where the program does not keep its output."""
    from harness import scope_times
    from horovod_tpu.models import joyai_flash
    config, cell = cell_files()
    step = "jit(local_step)/shard_map/decoder/hvd::mla_attention/"
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
    read = load("layer_metrics", "mla_flash_fwd_tile_us.train").read
    run = types.SimpleNamespace(scopes={"scope_times": table},
                                config=config, cell=cell)
    runs = 2 - joyai_flash.KEEP_ATTENTION
    # 2 steps x 4 sequences x 32 heads x 6 layers x 136 tiles, each run.
    assert read(run) == pytest.approx(
        240_000 / 1e3 / (2 * 4 * 32 * 6 * 136 * runs))
    counts = load("layer_metrics", "mla_flash_grid_steps_per_tile.train")
    assert counts.tiles_a_head(run) == (3 * 32 * 136, 3 * 32 * 136)
    run.scopes = {"scope_times": None}
    assert read(run) is None


def test_operations_of_the_cell_by_part():
    """The issue's count at the published widths: 27.84 TFLOP a sequence,
    latent attention 72 % of it."""
    flops = load("flops", "joyai_flash")
    config, _ = cell_files()
    assert flops.attended_pairs(8192) == 33_558_528
    parts = flops.train_flops_by_part(config)
    assert {k: round(v / 1e12, 2) for k, v in parts.items()} == dict(
        projections=7.77, attention=12.37, dense_mlp=2.16, shared=1.16,
        router=0.13, experts=0.58, mtp_projection=0.41, head=3.25)
    assert parts["attention"] == 6 * 33_558_528 * 32 * (192 + 128) * 6
    assert parts["experts"] == 5 * (8192 * 8 // 16) * 3 * 2048 * 768 * 6
    total = flops.train_flops_per_sample(config)
    assert total == pytest.approx(27.84e12, rel=1e-3)
    assert (parts["attention"] + parts["projections"]) / total == \
        pytest.approx(0.72, abs=0.005)
