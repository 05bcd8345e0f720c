"""The Kimi-Linear job of ``benchmarks/jobs/kimi_linear.py`` around its
model: the program through ``hvd.shard_step`` and ``DistributedOptimizer``,
and the reference's judgement of a first step; the rehearsal configuration
(a layer of each of the cell's three kinds) on the CPU, float32."""

import json
import math
import os

import jax
import numpy as np
import pytest

from horovod_tpu.models import kimi_linear

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SEED, BATCH = 11, 2


@pytest.fixture(scope="module")
def job(bench_job):
    return bench_job("kimi_linear")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "kimi-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def first(job, config):
    """The model's gradients and routing on the seeded state."""
    params = job.seeded_params(config, SEED)
    batch = job.seeded_batch(config, SEED, BATCH)
    (_, aux), grads = jax.value_and_grad(
        lambda p: kimi_linear.loss_fn(p, *batch, job.model_config(config)),
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
        2, 8 * z["length"], z["top_k"])     # the two expert layers
    assert sorted(leaves(gradients)) == sorted(leaves(
        jax.eval_shape(lambda: job.seeded_params(config, SEED))))
    assert "mlp_up" in gradients["runs"][0] \
        and "A_log" in gradients["runs"][1] \
        and "mla_wq" in gradients["runs"][2]


@pytest.mark.parametrize("fault,leaf", [
    (None, None), ("scaled", "head"), ("zero", "A_log"),
    ("scaled", "w_fb"), ("scaled", "conv_k"), ("zero", "dt_bias"),
    ("absent", "o_norm"), ("scaled", "mla_wq"), ("choices", None)])
def test_a_gradient_outside_its_limit_fails_the_loss_comparison(
        job, config, first, fault, leaf):
    """The runner compares losses only: a first step with a gradient leaf
    outside ``correct.gradient_limits`` (the new leaves of Kimi Delta
    Attention among them) gets ``inf`` to agree with, and so does one whose
    choices of experts are not the reference's."""
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
