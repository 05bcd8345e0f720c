"""The Kimi-Linear job through ``runners/train.py`` on the CPU: the
rehearsal cell ``kimi-tiny-train-cpu1`` enters a copy of the benchmark as
new files and manifest entries (``cells/manifest_entries_kimi.json`` on top
of ``overlay.py``'s), as the real cell entered the benchmark."""

import importlib.util
import json
import os
import types

import pytest

from conftest import HERE, run_cell

CELL = "kimi-tiny-train-cpu1"
REAL = "kimi-linear-train-8k"
NEW_READERS = {"kda_attention_share.train", "kda_scan_share.train",
               "kda_scan_roofline.train", "kda_chunk_us.train",
               "kda_grid_steps_per_chunk.train"}
COUNTS = {"kda_grid_steps_per_chunk.train"}
APPENDED = {"mfu.train", "device_idle_share.train",
            "compiles_in_window.train", "host_dispatch_ms.train",
            "moe_share.train", "expert_matmul_roofline.train",
            "mla_attention_share.train", "mla_expand_share.train",
            "mla_attention_roofline.train",
            "mla_flash_grid_steps_per_tile.train",
            "unattributed_share.train", "copy_wait_share.train",
            "embed_share.train", "layer_loop_share.train",
            "lm_head_loss_share.train"}
#: Readers that would read this cell wrong as they stand, or that tests hold
#: to other cells (ISSUE 37, section 9).
NOT_JOINED = {"mla_flash_fwd_tile_us.train", "qk_rope_share.train",
              "shared_expert_share.train", "dense_mlp_share.train",
              "mtp_share.train"}


@pytest.fixture(scope="module")
def kimi_copy(tmp_path_factory):
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_kimi")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    with open(os.path.join(HERE, "cells",
                           "manifest_entries_kimi.json")) as f:
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


def test_cell_and_its_reference(kimi_copy):
    last, lines = run_named(kimi_copy, CELL, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
    routing = [line for line in lines if "routing:" in line]
    assert len(routing) == 2 and "0.000 % of its choices" in routing[1]
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and gradients[0].endswith("all inside")
    for leaf in ("A_log", "dt_bias", "conv_k", "w_fb", "w_gb", "o_norm",
                 "mla_wq", "w_kvb"):
        assert f" {leaf} " in gradients[0]


def test_control_in_a_lower_precision_comes_out_not_correct(kimi_copy):
    """The cell's own files with the reference, its matrices rounded to
    ``correct.control_dtype``, in the program's place (``control.py``,
    ``jobs/kimi_linear_control.py``): the gradients leave their limits and
    the runner's comparison says so."""
    import control
    last, lines = run_named(
        kimi_copy, control.add_control(kimi_copy, CELL), 0)
    assert last["correct"] is False and last["failed"] == 0
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and "OUTSIDE: " in gradients[0]
    compared = next(line for line in lines if "bench: correct:" in line)
    assert "reference [inf, " in compared


def test_cell_traced_reports_counts_but_no_device_metric(kimi_copy):
    """Of the five new readers the count reads a value on the CPU; the four
    that read a device trace are absent, never zero."""
    last, _ = run_named(kimi_copy, CELL, 1)
    assert last["correct"] is True
    assert last["metrics"]["compiles_in_window.train"]["value"] == 0
    assert last["metrics"]["steps_in_window.train"]["value"] == \
        last["attempted"]
    # 128 positions in chunks of 32, one block: a step a head, 4 chunks.
    assert last["metrics"]["kda_grid_steps_per_chunk.train"] == {
        "value": 0.25, "unit": "ratio"}
    assert last["metrics"]["mla_flash_grid_steps_per_tile.train"] == {
        "value": 1.0, "unit": "ratio"}
    assert NEW_READERS & set(last["metrics"]) == COUNTS
    assert not {"moe_share.train", "expert_matmul_roofline.train",
                "mfu.train", "mla_attention_share.train"} \
        & set(last["metrics"])


def test_every_new_reader_has_its_file_and_its_entry():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert os.path.exists(os.path.join(
            root, "benchmarks", "layer_metrics", name + ".py"))
        assert entries[name]["workloads"] == [REAL]
        assert entries[name]["moves"] == "train_samples_per_s"
    joined = {m["name"] for m in manifest["per_layer"]
              if REAL in m["workloads"]}
    assert joined == NEW_READERS | APPENDED
    assert not joined & NOT_JOINED
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL)
    assert cell["chips"] == 1 and cell["traffic"] == "steps-causal-8k"
    assert cell["config"] == "kimi-linear-48b-a3b-ep32"
    assert manifest["workloads"][-1] == cell
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "linear_attn_config"]
    for kind, name in (("jobs", "kimi_linear"),
                       ("jobs", "kimi_linear_control"),
                       ("flops", "kimi_linear")):
        assert os.path.exists(os.path.join(root, "benchmarks", kind,
                                           name + ".py"))


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
                           "kimi-linear-48b-a3b-ep32.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "workloads", REAL + ".json")) as f:
        return config, json.load(f)


def test_the_scans_time_over_its_roofline_and_over_its_chunks():
    """The three readers of ``hvd::kda_attention::scan`` on a hand-built
    trace under the cell's own files: the span holds the kernels' custom
    calls forward and backward, the bound is the HBM's, and a chunk's time
    is the span over 4 layers x 32 heads x 128 chunks a sequence."""
    from harness import manifest as mf
    from harness import peaks, scope_times
    config, cell = cell_files()
    scan = ("jit(local_step)/shard_map/decoder/hvd::kda_attention/"
            "hvd::kda_attention::scan/")
    names = {"custom-call.7": scan + "hvd_kda_fwd/pallas_call",
             "custom-call.9": scan + "hvd_kda_bwd/pallas_call",
             "fusion.3": "jit(local_step)/shard_map/decoder/"
                         "hvd::kda_attention/hvd::kda_attention::gates/mul"}
    codes = {"custom-call.7": "custom-call", "custom-call.9": "custom-call",
             "fusion.3": "fusion"}
    event = "%{0} = f32[8]{{0}} custom-call(%x)".format
    devices = {"/device:TPU:0": {
        "ops": [(event("custom-call.7"), 0, 400_000_000),
                (event("custom-call.9"), 100, 300_000_000),
                (event("fusion.3"), 200, 300_000_000)],
        "modules": [("jit_local_step(5)", 0, 1000)] * 2}}
    table = scope_times.reduce(devices, names, codes, scope_times.KERNELS)
    flops = load("flops", "kimi_linear")
    run = types.SimpleNamespace(
        scopes={"scope_times": table}, config=config, cell=cell, flops=flops,
        peaks=mf.load_json("harness", "peaks.json")["devices"]["TPU v5 lite"])
    assert load("layer_metrics", "kda_scan_share.train").read(run) == \
        pytest.approx(70.0)
    assert load("layer_metrics", "kda_attention_share.train").read(run) == \
        pytest.approx(100.0)
    # 0.7 s under the span over 2 steps x 4 sequences; the least time a
    # sequence is its 4.84 GB at 819 GB/s (its 0.31 TFLOP would be 1.6 ms).
    memory = flops.kda_scan_bytes(config) / 819e9
    assert memory == pytest.approx(5.907e-3, rel=1e-3)
    assert memory > flops.train_flops_by_part(config)["kda_recurrence"] \
        / 197e12 == pytest.approx(1.570e-3, rel=1e-3)
    assert load("layer_metrics", "kda_scan_roofline.train").read(run) == \
        pytest.approx(100 * memory * 8 / 0.7)
    assert load("layer_metrics", "kda_chunk_us.train").read(run) == \
        pytest.approx(0.7e6 / (2 * 4 * 4 * 32 * 128))
    counts = load("layer_metrics", "kda_grid_steps_per_chunk.train")
    assert counts.counted(run) == (32 * 16, 32 * 128)
    assert counts.read(run) == 0.125
    run.scopes = {"scope_times": None}
    for name in ("kda_scan_share.train", "kda_scan_roofline.train",
                 "kda_chunk_us.train", "kda_attention_share.train"):
        assert load("layer_metrics", name).read(run) is None


def test_operations_of_the_cell_by_part():
    """The issue's count at the published widths: 18.9 TFLOP a sequence,
    Kimi Delta Attention 43 % of it."""
    flops = load("flops", "kimi_linear")
    config, _ = cell_files()
    assert flops.attended_pairs(8192) == 33_558_528
    parts = flops.train_flops_by_part(config)
    assert {k: round(v / 1e12, 2) for k, v in parts.items()} == dict(
        kda_projections=7.76, kda_recurrence=0.31, projections=1.43,
        attention=2.06, dense_mlp=3.13, shared=1.39, router=0.12,
        experts=0.35, head=2.32)
    assert parts["kda_recurrence"] == 4 * 8192 * 32 * 3 * 128 * 128 * 6
    assert parts["attention"] == 33_558_528 * 32 * (192 + 128) * 6
    assert parts["experts"] == 4 * (8192 * 8 // 32) * 3 * 2304 * 1024 * 6
    total = flops.train_flops_per_sample(config)
    assert total == pytest.approx(18.87e12, rel=1e-3)
    assert (parts["kda_projections"] + parts["kda_recurrence"]) / total == \
        pytest.approx(0.43, abs=0.005)
    assert flops.kda_scan_bytes(config) == \
        4 * 3 * 8192 * 32 * (4 * 128 * 2 + 2 + 128 * 4)
