"""Cells end to end on the CPU, in a temporary copy of the benchmark.

The rehearsal cells, their configurations and one per-layer metric enter
the copy as new files and manifest entries (``overlay.py``): that every
test below passes is the data-driven requirement, held by a test.  The
runs double as the agreement of each plain reference with the program at
tiny widths: a cell's ``correct`` is that comparison.
"""

import json
import os
import re
import shutil

import pytest

from conftest import ROOT, run_cell

DEVICE_METRICS = {"mfu.train", "device_idle_share.train",
                  "collective_share.train", "peak_hbm_gb.train",
                  "device_idle_share.serve", "paged_kernel_share.serve",
                  "peak_hbm_gb.serve"}


def logged(lines, pattern):
    for line in lines:
        m = re.search(pattern, line)
        if m:
            return m
    raise AssertionError(f"no line matches {pattern!r}")


def test_added_files_edit_nothing_that_was_there(bench_copy):
    """The copy differs from the committed benchmark only by new files and
    by entries appended to the manifest."""
    committed = os.path.join(ROOT, "benchmarks")
    for folder, _, files in os.walk(committed):
        if "__pycache__" in folder or "/tests" in folder + "/":
            continue
        for name in files:
            path = os.path.join(folder, name)
            twin = os.path.join(bench_copy, "benchmarks",
                                os.path.relpath(path, committed))
            with open(path, "rb") as a, open(twin, "rb") as b:
                assert a.read() == b.read(), path
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        before = json.load(f)
    with open(os.path.join(bench_copy, "BENCHMARK.json")) as f:
        after = json.load(f)
    for key in ("command", "paths", "run_seconds"):
        assert after[key] == before[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        kept = after[group][:len(before[group])]
        for old, new in zip(before[group], kept):
            grown = dict(new, workloads=old["workloads"]) \
                if "workloads" in old else new
            assert grown == old
            assert new.get("workloads", [])[:len(old.get("workloads", []))] \
                == old.get("workloads", [])


def test_a_cell_that_names_tpu_gives_no_result_on_the_cpu(bench_copy):
    rc, lines, err = run_cell(bench_copy, "resnet50-train-1chip", 0)
    assert rc != 0
    assert not lines[-1].startswith("{")
    assert "platform" in err


def test_nothing_runs_without_the_program(bench_copy, tmp_path):
    """Alone with ``BENCHMARK.json`` and ``benchmarks/``: no result."""
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "resnet-tiny-train-cpu1", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=bench_copy, capture_output=True, text=True,
        env=dict(env, JAX_PLATFORMS="cpu"), timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("devices", [1, 4])
def test_train_cell_and_its_reference(run_in_copy, devices):
    """The bare-``optax`` step against ``shard_step`` +
    ``DistributedOptimizer`` on one and on four virtual devices."""
    cell = f"resnet-tiny-train-cpu{devices}"
    last, lines = run_in_copy(cell, 0, devices=devices)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert last["metrics"]["train_samples_per_s"]["value"] > 0
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == devices
    m = logged(lines, r"errors \[(.*?)\] tolerance .* shards on (\d+)")
    assert int(m.group(2)) == devices
    assert float(m.group(1).split(",")[0]) < 1e-5   # step one: same sums


def test_train_cell_traced_reports_counts_but_no_device_metric(run_in_copy):
    last, _ = run_in_copy("resnet-tiny-train-cpu4", 1, devices=4)
    metrics = last["metrics"]
    assert last["correct"] is True
    assert metrics["compiles_in_window.train"]["value"] == 0
    assert metrics["allreduce_ops.train"]["value"] > 0
    assert metrics["steps_in_window.train"]["value"] == last["attempted"]
    assert not DEVICE_METRICS & set(metrics)   # absent, never zero
    assert "busy_s" not in last["device"] and "breakdown" not in last


def test_serve_cell_and_its_reference(run_in_copy):
    """The plain GPT-2 forward against ``/score`` and ``/generate``."""
    last, lines = run_in_copy("gpt2-tiny-serve-cpu", 0, seconds=3)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 18          # 6 requests/s for 3 s
    assert set(last["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                    "serve_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    worst = float(logged(lines, r"worst error (\S+),").group(1))
    assert worst < 1e-4                     # float32 against float32


def test_serve_cell_traced(run_in_copy):
    last, _ = run_in_copy("gpt2-tiny-serve-cpu", 1, seconds=3)
    metrics = last["metrics"]
    assert last["correct"] is True
    for name in ("loadgen_late_p95_ms.serve", "queue_ms.serve",
                 "batch_occupancy.serve", "decode_step_ms.serve",
                 "compiles_in_window.serve"):
        assert name in metrics
    assert metrics["compiles_in_window.serve"]["value"] == 0
    assert not DEVICE_METRICS & set(metrics)


def test_peaks_table_refuses_an_unknown_device():
    from harness.peaks import peaks_of
    assert peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        peaks_of("TPU v9 imaginary")


def test_flops_of_resnet50_from_shapes():
    from harness import manifest as mf
    flops = mf.load_module("flops", "resnet50")
    config = mf.load_json("configs", "resnet50.json")
    assert abs(flops.forward_macs(config) / 4.09e9 - 1) < 0.01
    assert flops.train_flops_per_sample(config) == \
        6 * flops.forward_macs(config)
