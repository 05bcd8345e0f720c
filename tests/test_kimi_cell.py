"""The rehearsal cell of ``benchmarks/tests/test_kimi_cell.py`` (the
Kimi-Linear job through ``runners/train.py`` on the CPU, its control, its
readers, its flops file and its manifest entries) as counted cases of this
suite."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@pytest.mark.parametrize("test", [
    "test_cell_and_its_reference",
    "test_cell_traced_reports_counts_but_no_device_metric",
    "test_control_in_a_lower_precision_comes_out_not_correct",
    "test_the_scans_time_over_its_roofline_and_over_its_chunks",
    "test_operations_of_the_cell_by_part"])
def test_rehearsal_cell_through_the_train_runner(test):
    """``benchmarks/tests/test_kimi_cell.py`` (the rehearsal cell of
    ``benchmarks/tests/cells/`` through ``runners/train.py``, in a child
    process) as counted cases of this suite."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "HVD_TPU_EMULATE_RANKS")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", "-p",
         "no:cacheprovider", "-p", "no:xdist",
         f"benchmarks/tests/test_kimi_cell.py::{test}"],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]


def test_every_reader_of_the_cell_has_its_file_and_its_entry():
    """What ``benchmarks/tests/test_kimi_cell.py::
    test_every_new_reader_has_its_file_and_its_entry`` holds, without its
    pin on the set of metrics that list the cell (PR 37's twenty; PR 38
    appended ``kda_surround_share.train`` for the cell, as ISSUE 38 asked,
    and no file under ``benchmarks/`` may be edited for it): PR 37's five
    readers are there and are the cell's own, the fifteen older metrics and
    the new one list the cell, the five that would read it wrong do not,
    every metric that lists the cell has its reader, and the cell, its
    configuration and its job's files are as they entered."""
    cell_name = "kimi-linear-train-8k"
    new = {"kda_attention_share.train", "kda_scan_share.train",
           "kda_scan_roofline.train", "kda_chunk_us.train",
           "kda_grid_steps_per_chunk.train"}
    appended = {"mfu.train", "device_idle_share.train",
                "compiles_in_window.train", "host_dispatch_ms.train",
                "moe_share.train", "expert_matmul_roofline.train",
                "mla_attention_share.train", "mla_expand_share.train",
                "mla_attention_roofline.train",
                "mla_flash_grid_steps_per_tile.train",
                "unattributed_share.train", "copy_wait_share.train",
                "embed_share.train", "layer_loop_share.train",
                "lm_head_loss_share.train"}
    not_joined = {"mla_flash_fwd_tile_us.train", "qk_rope_share.train",
                  "shared_expert_share.train", "dense_mlp_share.train",
                  "mtp_share.train"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in new | {"kda_surround_share.train"}:
        assert entries[name]["workloads"] == [cell_name]
        assert entries[name]["moves"] == "train_samples_per_s"
    joined = [m["name"] for m in manifest["per_layer"]
              if cell_name in m["workloads"]]
    assert len(set(joined)) == len(joined)
    assert new | appended | {"kda_surround_share.train"} <= set(joined)
    assert not set(joined) & not_joined
    for name in joined:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    cell = next(w for w in manifest["workloads"] if w["name"] == cell_name)
    assert cell["chips"] == 1 and cell["traffic"] == "steps-causal-8k"
    assert cell["config"] == "kimi-linear-48b-a3b-ep32"
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "linear_attn_config"]
    for kind, name in (("jobs", "kimi_linear"),
                       ("jobs", "kimi_linear_control"),
                       ("flops", "kimi_linear")):
        assert os.path.exists(os.path.join(BENCH, kind, name + ".py"))
