"""The AFMoE job of ``benchmarks/jobs/afmoe.py`` around its model: the
program through ``hvd.shard_step`` and ``DistributedOptimizer``, the
reference's judgement of a first step, and the rehearsal cell of
``benchmarks/tests/test_trinity_cell.py``; the rehearsal configuration (a
dense layer, a window and a full expert layer) on the CPU, float32."""

import importlib.util
import json
import math
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import afmoe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED, BATCH = 11, 2


@pytest.fixture(scope="module")
def job():
    sys.path.insert(0, BENCH)       # the job finds ``harness`` by name
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_jobs_afmoe", os.path.join(BENCH, "jobs", "afmoe.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "trinity-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def seeded(job, config):
    return (job.seeded_params(config, SEED),
            job.seeded_batch(config, SEED, BATCH))


@pytest.fixture(scope="module")
def first(job, config, seeded):
    """The model's gradients and routing on the seeded state."""
    params, batch = seeded
    (_, aux), grads = jax.value_and_grad(
        lambda p: afmoe.loss_fn(p, *batch, job.model_config(config)),
        has_aux=True)(params)
    return jax.tree_util.tree_map(np.asarray, grads), aux


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_step_through_shard_step_and_distributed_optimizer(hvd8, job,
                                                           config):
    """The job's program on the 8-device CPU mesh, a sequence a slot,
    against the reference's AdamW step on one device."""
    program = job.Program(config, 1, SEED)
    state = program.fresh_state()
    got = []
    for _ in range(2):
        *state, loss = program.step(*state, *program.batch)
        got.append(float(loss))
    want = job.reference_losses(config, SEED, program.global_batch, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert got[1] < got[0]
    routed, chosen, gradients = program.first
    z = job.sizes(config)
    assert routed.shape == (2,) and chosen.shape == (
        2, 8 * z["length"], z["top_k"])
    assert sorted(leaves(gradients)) == sorted(leaves(
        jax.eval_shape(lambda: job.seeded_params(config, SEED))))
    assert len(gradients["runs"]) == 3 and "mlp_up" in gradients["runs"][0]


def test_the_references_update_by_leaf_is_adamws_first_step(job, config,
                                                            seeded):
    import optax
    params = job.unstacked(seeded[0])
    grads = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.random.RandomState(8).randn(*a.shape),
                              jnp.float32), params)
    opt = job.make_optimizer(config)
    updates, _ = opt.update(grads, opt.init(params), params)
    want = optax.apply_updates(params, updates)
    keep = jax.tree_util.tree_map(jnp.copy, (params, grads))
    got = job.update_by_leaf(opt, *keep)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)
    with pytest.raises(ValueError, match="first step"):
        job.reference_losses(config, SEED, BATCH, 3)


@pytest.mark.parametrize("fault,leaf", [
    (None, None), ("scaled", "head"), ("scaled", "router"),
    ("zero", "shared_down"), ("zero", "mlp_up"), ("absent", "wg"),
    ("choices", None)])
def test_a_gradient_outside_its_limit_fails_the_loss_comparison(
        job, config, first, fault, leaf):
    """The runner compares losses only: a first step with a gradient leaf
    outside ``correct.gradient_limits`` gets ``inf`` to agree with, and so
    does one whose choices of experts are not the reference's (the
    reference follows them, so with no limit on the gradients the choices'
    own limit is what fails)."""
    grads, aux = first
    grads = dict(grads, runs=[dict(run) for run in grads["runs"]])
    for holder in [grads] + grads["runs"]:
        if leaf in holder and fault == "scaled":
            holder[leaf] = 1.01 * holder[leaf]
        elif leaf in holder and fault == "zero":
            holder[leaf] = np.zeros_like(holder[leaf])
    limits = dict(config["correct"]["gradient_limits"])
    if fault == "absent":
        del limits[leaf]
    chosen = np.asarray(aux.chosen)
    if fault == "choices":
        limits = dict.fromkeys(limits, math.inf)
        chosen = chosen.copy()
        chosen[:, ::50] = (chosen[:, ::50] + 1) % job.sizes(config)["routed"]
    config = dict(config, correct=dict(config["correct"],
                                       gradient_limits=limits))
    job._first_steps[SEED, BATCH] = job.FirstStep(
        np.asarray(aux.routed_here), chosen, grads)
    losses = job.reference_losses(config, SEED, BATCH, 2)
    assert (SEED, BATCH) not in job._first_steps
    assert math.isfinite(losses[1])
    assert math.isinf(losses[0]) == (fault is not None)


@pytest.mark.parametrize("test", [
    "test_cell_and_its_reference",
    "test_cell_traced_reports_counts_but_no_device_metric",
    "test_control_in_a_lower_precision_comes_out_not_correct",
    "test_operations_count_the_pairs_the_masks_keep"])
def test_rehearsal_cell_through_the_train_runner(test):
    """``benchmarks/tests/test_trinity_cell.py`` (the rehearsal cell of
    ``benchmarks/tests/cells/`` through ``runners/train.py``, in a child
    process) as counted cases of this suite."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "HVD_TPU_EMULATE_RANKS")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", "-p",
         "no:cacheprovider", "-p", "no:xdist",
         f"benchmarks/tests/test_trinity_cell.py::{test}"],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]


def test_every_reader_of_the_cell_has_its_file_and_its_entry():
    """What ``benchmarks/tests/test_trinity_cell.py::
    test_every_new_reader_has_its_file_and_its_entry`` holds, without its
    count of the metrics that list the cell (14 when the cell was added;
    a later PR appends the cell to a new metric's list, as PR 35 did to
    five): PR 31's eight readers are there and are the cell's own, and
    every metric that lists the cell has its reader."""
    new = {"window_attention_share.train", "full_attention_share.train",
           "shared_expert_share.train", "dense_mlp_share.train",
           "mixed_attention_roofline.train", "mixed_flash_fwd_tile_us.train",
           "mixed_flash_grid_steps_per_tile.train",
           "window_tiles_kept_share.train"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in new:
        assert entries[name]["workloads"] == ["trinity-mini-train-8k"]
        assert entries[name]["moves"] == "train_samples_per_s"
    joined = [m["name"] for m in manifest["per_layer"]
              if "trinity-mini-train-8k" in m["workloads"]]
    assert len(joined) >= 14 and len(set(joined)) == len(joined)
    assert new <= set(joined)
    for name in joined:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name


@pytest.mark.parametrize("kept,tile_runs", [
    ((afmoe.SLIDING, afmoe.FULL), 53_248), ((afmoe.FULL,), 89_088)])
def test_forward_kernel_time_over_the_tile_runs_the_program_keeps(
        monkeypatch, kept, tile_runs):
    """``mixed_flash_fwd_tile_us.train`` on a hand-built trace under the
    cell's own files, as ``benchmarks/tests/test_trinity_cell.py::
    test_forward_kernel_time_over_the_tile_runs_of_a_mixed_stack`` builds
    it (that case pins ``KEPT_ATTENTION == (FULL,)``): a layer's forward
    kernel is counted twice where its type is not kept across the
    recomputation, so the reader follows the program's constant."""
    monkeypatch.syspath_prepend(BENCH)
    from harness import manifest as mf
    from harness import scope_times
    monkeypatch.setattr(afmoe, "KEPT_ATTENTION", kept)
    with open(os.path.join(BENCH, "configs", "trinity-mini-ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads",
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
    # 4 sequences x 32 heads x (4 window layers x 70 tiles + the full
    # layer's 136), a type's tiles twice where it is not kept.
    window, full = (2 - (kind in kept) for kind in (afmoe.SLIDING,
                                                    afmoe.FULL))
    assert 4 * 32 * (4 * 70 * window + 136 * full) == tile_runs
    assert read(run) == pytest.approx(240_000 / 1e3 / (2 * tile_runs))
