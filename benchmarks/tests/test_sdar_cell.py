"""The block-diffusion job through ``runners/train.py`` on the CPU: the
rehearsal cell ``sdar-tiny-train-cpu1`` enters a copy of the benchmark as
new files and manifest entries (``cells/manifest_entries_sdar.json`` on top
of ``overlay.py``'s), as the real cell entered the benchmark."""

import json
import os

import pytest

from conftest import HERE, run_cell

CELL = "sdar-tiny-train-cpu1"


@pytest.fixture(scope="module")
def sdar_copy(tmp_path_factory):
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_sdar")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    with open(os.path.join(HERE, "cells",
                           "manifest_entries_sdar.json")) as f:
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


def run(copy, trace):
    return run_named(copy, CELL, trace)


def test_cell_and_its_reference(sdar_copy):
    last, lines = run(sdar_copy, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {"train_samples_per_s", "setup_s"}
    routing = [line for line in lines if "routing:" in line]
    assert len(routing) == 2 and "0.000 % of its choices" in routing[1]
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and gradients[0].endswith("all inside")


def test_control_in_a_lower_precision_comes_out_not_correct(sdar_copy):
    """The cell's own files with the reference, its matrices rounded to
    ``correct.control_dtype``, in the program's place (``control.py``,
    ``jobs/sdar_moe_control.py``): the loss stays inside its limit, the
    gradients do not, and the runner's comparison says so."""
    import control
    last, lines = run_named(sdar_copy, control.add_control(sdar_copy, CELL),
                            0)
    assert last["correct"] is False and last["failed"] == 0
    gradients = [line for line in lines if "gradients:" in line]
    assert len(gradients) == 1 and "OUTSIDE: " in gradients[0]
    compared = next(line for line in lines if "bench: correct:" in line)
    assert "reference [inf, " in compared


def test_cell_traced_reports_counts_but_no_device_metric(sdar_copy):
    last, _ = run(sdar_copy, 1)
    assert last["correct"] is True
    assert last["metrics"]["compiles_in_window.train"]["value"] == 0
    assert last["metrics"]["steps_in_window.train"]["value"] == \
        last["attempted"]
    # No device plane on the CPU: the new readers are absent, never zero.
    assert not {"bd_attention_share.train", "moe_share.train",
                "bd_attention_roofline.train",
                "expert_matmul_roofline.train", "mfu.train"} \
        & set(last["metrics"])
