"""SDAR-MoE under block diffusion: the model of
``horovod_tpu/models/sdar_moe.py`` against the plain reference of
``benchmarks/jobs/sdar_moe.py``, at tiny sizes on the CPU, float32."""

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import sdar_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED, BATCH = 11, 2


def load(kind, name):
    path = os.path.join(ROOT, "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def job():
    return load("jobs", "sdar_moe")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "tests", "cells", "configs",
                           "sdar-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def seeded(job, config):
    return (job.seeded_params(config, SEED),
            job.seeded_batch(config, SEED, BATCH))


@pytest.fixture(scope="module")
def both(job, config, seeded):
    """Loss and gradients of model and reference, each by ``jax.grad``."""
    params, batch = seeded
    cfg = job.model_config(config)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: sdar_moe.loss_fn(p, *batch, cfg), has_aux=True)(params)
    want_loss, want = jax.value_and_grad(
        lambda p: job.reference_loss(config, p, *batch))(params)
    return loss, grads, aux, want_loss, want


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


LEAVES = ["['embed']", "['final_norm']", "['head']"] + [
    f"['layers']['{n}']" for n in (
        "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "moe_norm",
        "router", "w_gate", "w_up", "w_down")]


def test_loss_equals_the_reference(both):
    loss, _, _, want_loss, _ = both
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    assert 1.0 < float(loss) < 20.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(both, leaf):
    _, grads, _, _, want = both
    got, want = leaves(grads)[leaf], leaves(want)[leaf]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_the_tree_is_the_one_the_model_describes(job, config, seeded):
    z = job.sizes(config)
    n, d, hd, f = z["layers"], z["d"], z["head_dim"], z["width"]
    assert {name: leaf.shape for name, leaf in leaves(seeded[0]).items()} \
        == dict({"['embed']": (z["vocab"], d), "['final_norm']": (d,),
                 "['head']": (d, z["vocab"])}, **{
            f"['layers']['{name}']": (n,) + shape for name, shape in dict(
                attn_norm=(d,), moe_norm=(d,), q_norm=(hd,), k_norm=(hd,),
                wq=(d, z["heads"] * hd), wk=(d, z["kv_heads"] * hd),
                wv=(d, z["kv_heads"] * hd), wo=(z["heads"] * hd, d),
                router=(d, z["routed"]), w_gate=(z["held"], d, f),
                w_up=(z["held"], d, f), w_down=(z["held"], f, d)).items()})
    assert sorted(leaves(seeded[0])) == sorted(LEAVES)
    assert all(a.dtype == jnp.float32 for a in leaves(seeded[0]).values())


def test_aux_counts_the_pairs_routed_to_the_held_experts(job, config, both,
                                                         seeded):
    _, _, aux, _, _ = both
    z = job.sizes(config)
    positions = BATCH * 2 * z["length"]
    assert aux.chosen.shape == (z["layers"], positions, z["top_k"])
    here = ((aux.chosen >= z["first"])
            & (aux.chosen < z["first"] + z["held"])).sum(axis=(1, 2))
    np.testing.assert_array_equal(aux.routed_here, here)
    assert 0 < int(here.min()) and int(here.max()) < positions * z["top_k"]
    reference = job.ReferenceSteps(config, BATCH)
    _, _, chosen = reference.loss_and_grads(job.unstacked(seeded[0]),
                                            *seeded[1])
    assert job.choices_that_differ(aux.chosen, chosen) == 0.0


def test_reference_by_layers_equals_reference_whole(job, config, both,
                                                    seeded):
    """What runs on the chip (a sequence and a layer at a time, by
    ``jax.vjp``) against ``jax.grad`` of the whole plain loss."""
    _, _, _, want_loss, want = both
    reference = job.ReferenceSteps(config, BATCH)
    loss, grads, _ = reference.loss_and_grads(job.unstacked(seeded[0]),
                                              *seeded[1])
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    stacked = dict(grads, layers={
        name: jnp.stack([layer[name] for layer in grads["layers"]])
        for name in grads["layers"][0]})
    for name, got in leaves(stacked).items():
        np.testing.assert_allclose(
            got, leaves(want)[name], rtol=1e-4,
            atol=1e-6 * float(jnp.abs(leaves(want)[name]).max()) + 1e-9,
            err_msg=name)


def test_corruption_masks_by_block_and_weighs_by_one_over_t(job, config):
    xt, x0, weight = job.seeded_batch(config, 3, 64)
    assert xt.shape == x0.shape == weight.shape == (64, 32)
    assert (x0 < 39).all()
    masked = np.asarray(xt == 39)
    np.testing.assert_array_equal(masked, np.asarray(weight) > 0)
    np.testing.assert_array_equal(np.asarray(xt)[~masked],
                                  np.asarray(x0)[~masked])
    by_block = np.asarray(weight).reshape(64, 8, 4)
    for row in by_block.reshape(-1, 4):        # one t a block
        assert len({round(float(w), 4) for w in row if w > 0}) <= 1
    assert (np.asarray(weight)[masked] >= 1.0).all()
    # E[weight] = E[(1 / t) 1(masked)] = 1 a position.
    assert abs(float(weight.mean()) - 1.0) < 0.15


def test_sliced_vocabulary_loss_in_chunks(job, config, seeded):
    """The head's loss over the slice held here, a chunk at a time, equals
    the whole float32 log-softmax over the slice."""
    params, _ = seeded
    cfg = job.model_config(config)
    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.randn(64, 32), jnp.float32)
    targets = jnp.asarray(rng.randint(0, 39, 64), jnp.int32)
    weight = jnp.asarray(rng.rand(64) * (rng.rand(64) < 0.5), jnp.float32)
    import dataclasses
    got = sdar_moe.head_loss(params, hidden, targets, weight,
                             dataclasses.replace(cfg, loss_chunk=16))
    normed = hidden * jax.lax.rsqrt(
        jnp.mean(hidden ** 2, -1, keepdims=True) + 1e-6)
    logp = jax.nn.log_softmax(normed @ params["head"], axis=-1)
    want = -(weight * logp[jnp.arange(64), targets]).sum()
    assert logp.shape[1] == config["vocab_size"] == 40
    np.testing.assert_allclose(got, want, rtol=1e-5)
    with pytest.raises(ValueError, match="chunk"):
        sdar_moe.head_loss(params, hidden[:50], targets[:50], weight[:50],
                           dataclasses.replace(cfg, loss_chunk=16))


def test_step_through_shard_step_and_distributed_optimizer(hvd8, job,
                                                           config):
    """The job's program on the 8-device CPU mesh, a sequence a slot,
    against the reference's AdamW steps on one device."""
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
        2, 8 * 2 * z["length"], z["top_k"])
    assert sorted(leaves(gradients)) == sorted(LEAVES)


def test_gradient_errors_by_leaf(job, both):
    """One number a kind of leaf, the layers together, whether a tree
    holds them stacked or as a list."""
    _, grads, _, _, want = both
    errors = job.gradient_errors(grads, job.unstacked(want))
    assert sorted(errors) == sorted(
        name.split("'")[-2] for name in LEAVES)
    assert 0 < max(errors.values()) < 1e-5
    off = dict(want, head=1.25 * want["head"], layers=dict(
        want["layers"], wo=jnp.zeros_like(want["layers"]["wo"])))
    errors = job.gradient_errors(off, want)
    assert errors.pop("head") == pytest.approx(0.25)
    assert errors.pop("wo") == pytest.approx(1.0)
    assert set(errors.values()) == {0.0}


@pytest.mark.parametrize("fault,leaf", [
    (None, None), ("scaled", "head"), ("scaled", "router"),
    ("zero", "w_down"), ("zero", "embed"), ("absent", "wq")])
def test_a_gradient_outside_its_limit_fails_the_loss_comparison(
        job, config, seeded, both, fault, leaf):
    """The runner compares losses only: a first step with a gradient leaf
    outside ``correct.gradient_limits`` gets ``inf`` to agree with."""
    _, grads, aux, _, _ = both
    grads = jax.tree_util.tree_map(np.asarray, grads)
    holder = grads["layers"] if leaf in grads["layers"] else grads
    if fault == "scaled":
        holder[leaf] = 1.01 * holder[leaf]
    elif fault == "zero":
        holder[leaf] = np.zeros_like(holder[leaf])
    limits = dict(config["correct"]["gradient_limits"])
    if fault == "absent":
        del limits[leaf]
    config = dict(config, correct=dict(config["correct"],
                                       gradient_limits=limits))
    job._first_steps[SEED, BATCH] = job.FirstStep(
        np.asarray(aux.routed_here), np.asarray(aux.chosen), grads)
    losses = job.reference_losses(config, SEED, BATCH, 2)
    assert (SEED, BATCH) not in job._first_steps
    assert math.isfinite(losses[1])
    assert math.isinf(losses[0]) == (fault is not None)


@pytest.mark.parametrize("case", [0, 1, 2])
def test_rehearsal_cell_through_the_train_runner(case):
    """``benchmarks/tests/test_sdar_cell.py`` (the rehearsal cell of
    ``benchmarks/tests/cells/`` through ``runners/train.py``, in a child
    process) as a counted case of this suite."""
    import subprocess
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "HVD_TPU_EMULATE_RANKS")}
    test = ("test_cell_and_its_reference",
            "test_cell_traced_reports_counts_but_no_device_metric",
            "test_control_in_a_lower_precision_comes_out_not_correct")[case]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", "-p",
         "no:cacheprovider", "-p", "no:xdist",
         f"benchmarks/tests/test_sdar_cell.py::{test}"],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
