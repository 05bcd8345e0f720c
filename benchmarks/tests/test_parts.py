"""The partition's reader (``harness/parts.py``) and its five metrics
(PR 35) in the CPU rehearsal: the readers join two rehearsal cells as new
manifest entries, run with them in a traced run, and read nothing where
there is no device trace (a CPU figure is never a device metric).  The
reader's arithmetic on a hand-built step is held by the program's own
tests (``tests/test_step_scopes.py``, which load this module by name)."""

import json
import os

import pytest

from conftest import HERE, ROOT, run_cell

READERS = {"unattributed_share.train": "device",
           "copy_wait_share.train": "device",
           "embed_share.train": "models",
           "layer_loop_share.train": "models",
           "lm_head_loss_share.train": "models"}
TRAINING = ["resnet50-train-1chip", "resnet50-train-dp4",
            "sdar30b-train-blockdiff-4k", "trinity-mini-train-8k",
            "joyai-flash-train-mtp-8k"]
REHEARSALS = {"resnet-tiny-train-cpu1": None,
              "sdar-tiny-train-cpu1": "manifest_entries_sdar.json"}


@pytest.fixture(scope="module")
def parts_copy(tmp_path_factory):
    """``overlay.py``'s copy with the block-diffusion rehearsal cell, and
    the five readers listed for both rehearsal cells."""
    import overlay
    copy = overlay.make_copy(str(tmp_path_factory.mktemp("bench_parts")))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for cell, entries in REHEARSALS.items():
        extend = set(READERS)
        if entries:
            with open(os.path.join(HERE, "cells", entries)) as f:
                added = json.load(f)
            manifest["configs"] += added["configs"]
            manifest["workloads"] += added["workloads"]
            extend |= set(added["extend"])
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            if metric["name"] in extend:
                metric["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=1)
    return copy


def test_every_reader_has_its_file_and_its_appended_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for m in (entries[name] for name in READERS):
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        assert m == {"name": m["name"], "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": READERS[m["name"]],
                     "moves": "train_samples_per_s",
                     "workloads": TRAINING if m["layer"] == "device"
                     else TRAINING[2:]}


@pytest.mark.parametrize("cell", sorted(REHEARSALS))
def test_traced_rehearsal_reads_none_of_them_on_the_cpu(parts_copy, cell):
    rc, lines, err = run_cell(parts_copy, cell, 1, seconds=1)
    assert rc == 0, err[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True
    assert not set(READERS) & set(last["metrics"])
    assert last["metrics"]["compiles_in_window.train"]["value"] == 0


def test_the_reader_takes_no_name_of_a_part_from_itself():
    """Every part's name comes from the program's exported tuples."""
    with open(os.path.join(os.path.dirname(HERE), "harness",
                           "parts.py")) as f:
        code = f.read().split('"""', 2)[2]
    for name in ("hvd::embed", "hvd::moe", "hvd::optimizer", "stage1",
                 "hvd::lm_head_loss", "hvd::allreduce"):
        assert name not in code
