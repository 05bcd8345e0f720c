"""``qk_rope_share.train`` (PR 36) in the CPU rehearsal: the reader has its
file and its appended entry, which lists exactly the two cells whose
decoders run the pass of ``parallel/qk_rope.py``; joined to the
block-diffusion and the window-attention rehearsal cells it runs with them
in a traced run and reads nothing there (no device trace on the CPU, and
the rehearsal's heads of 8 take no kernel); on a hand-built trace it is the
time under ``hvd::qk_rope`` over all operations' time, and ``None`` where
the program writes no such span (the parent)."""

import json
import os
import types

import pytest

from conftest import HERE, ROOT, run_cell

NAME = "qk_rope_share.train"
CELLS = ["sdar30b-train-blockdiff-4k", "trinity-mini-train-8k"]
REHEARSALS = {"sdar-tiny-train-cpu1": "manifest_entries_sdar.json",
              "trinity-tiny-train-cpu1": "manifest_entries_trinity.json"}


def test_the_reader_has_its_file_and_its_appended_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [m for m in manifest["per_layer"] if m["name"] == NAME] == [{
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_samples_per_s", "workloads": CELLS}]
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       NAME + ".py"))


@pytest.fixture(scope="module")
def copy_with_the_reader(tmp_path_factory):
    """``overlay.py``'s copy with both rehearsal cells, the reader listed
    for each."""
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_qk_rope")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for cell, entries in REHEARSALS.items():
        with open(os.path.join(HERE, "cells", entries)) as f:
            added = json.load(f)
        manifest["configs"] += added["configs"]
        manifest["workloads"] += added["workloads"]
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            if metric["name"] in set(added["extend"]) | {NAME}:
                metric["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)
    return copy


@pytest.mark.parametrize("cell", sorted(REHEARSALS))
def test_traced_rehearsal_leaves_the_metric_out_on_the_cpu(
        copy_with_the_reader, cell):
    rc, lines, err = run_cell(copy_with_the_reader, cell, 1, seconds=1)
    assert rc == 0, err[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True
    assert NAME not in last["metrics"]
    assert last["metrics"]["compiles_in_window.train"]["value"] == 0


def test_the_reader_is_the_time_under_the_span_or_nothing():
    from harness import manifest as mf
    from harness import scope_times
    read = mf.load_module("layer_metrics", NAME).read
    span = "jit(local_step)/decoder/hvd::bd_attention/hvd::qk_rope/"
    names = {"custom-call.1": span + "hvd_qk_rope_fwd/pallas_call",
             "custom-call.2": span + "hvd_qk_rope_bwd/pallas_call",
             "fusion.3": span + "reduce_sum",
             "custom-call.4": span[:-len("hvd::qk_rope/")]
             + "hvd_flash_fwd/pallas_call"}
    codes = {name: name.split(".")[0] for name in names}
    event = "%{0} = f32[8]{{0}} op(%x)".format
    devices = {"/device:TPU:0": {
        "ops": [(event(name), 0, ns) for name, ns in zip(
            names, (30, 50, 20, 300))],
        "modules": [("jit_local_step(5)", 0, 1000)]}}
    table = scope_times.reduce(devices, names, codes, scope_times.KERNELS)
    run = types.SimpleNamespace(scopes={"scope_times": table})
    assert read(run) == pytest.approx(25.0)
    parent = scope_times.reduce(
        devices, {k: v.replace("hvd::qk_rope/", "") for k, v in
                  names.items()}, codes, scope_times.KERNELS)
    assert read(types.SimpleNamespace(scopes={"scope_times": parent})) is None
    assert read(types.SimpleNamespace(scopes={}, results={})) is None
