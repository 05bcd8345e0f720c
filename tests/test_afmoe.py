"""AFMoE (Trinity-Mini's block): the model of
``horovod_tpu/models/afmoe.py`` against the plain reference of
``benchmarks/jobs/afmoe.py``, at tiny widths on the CPU, float32, with the
published layer pattern: 2 dense + 6 expert layers of types s s s f s s s
f."""

import dataclasses
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import afmoe
from horovod_tpu.parallel.qk_rope import rope_tables

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED, BATCH = 11, 2
S, F = afmoe.SLIDING, afmoe.FULL


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
    """The rehearsal configuration with the published pattern."""
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "trinity-tiny.json")) as f:
        config = json.load(f)
    return dict(config, num_hidden_layers=8, num_dense_layers=2,
                layer_types=[S, S, S, F, S, S, S, F])


@pytest.fixture(scope="module")
def small(job):
    """The rehearsal configuration as it is: a dense layer, a window and a
    full expert layer."""
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "trinity-tiny.json")) as f:
        config = json.load(f)
    return config, job.seeded_params(config, SEED), \
        job.seeded_batch(config, SEED, BATCH)


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
        lambda p: afmoe.loss_fn(p, *batch, cfg), has_aux=True)(params)
    want_loss, want = jax.value_and_grad(
        lambda p: job.reference_loss(config, p, *batch))(params)
    return loss, grads, aux, want_loss, want


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


ATTENTION = ("attn_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
             "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo")
DENSE = ATTENTION + ("mlp_gate", "mlp_up", "mlp_down")
EXPERTS = ATTENTION + ("router", "shared_gate", "shared_up", "shared_down",
                       "w_gate", "w_up", "w_down")
# The pattern's runs: two dense window layers, then by kind of expert layer
# 1 window, 1 full, 3 window, 1 full.
RUNS = [(DENSE, 2), (EXPERTS, 1), (EXPERTS, 1), (EXPERTS, 3), (EXPERTS, 1)]
LEAVES = ["['embed']", "['final_norm']", "['head']"] + [
    f"['runs'][{i}]['{name}']" for i, (names, _) in enumerate(RUNS)
    for name in names]


def test_the_published_pattern_is_five_runs_of_three_kinds(job, config):
    cfg = job.model_config(config)
    assert afmoe.layer_runs(cfg) == [
        (True, True, 2), (False, True, 1), (False, False, 1),
        (False, True, 3), (False, False, 1)]
    assert afmoe.layer_runs(cfg) == job.runs(job.sizes(config))
    assert cfg.num_hidden_layers == 8
    with pytest.raises(ValueError, match="layer_types"):
        afmoe.layer_runs(dataclasses.replace(cfg, layer_types=("linear",)))


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
    d, hd = z["d"], z["head_dim"]
    shapes = dict(
        attn_norm=(d,), post_attn_norm=(d,), pre_mlp_norm=(d,),
        post_mlp_norm=(d,), q_norm=(hd,), k_norm=(hd,),
        wq=(d, z["heads"] * hd), wg=(d, z["heads"] * hd),
        wk=(d, z["kv_heads"] * hd), wv=(d, z["kv_heads"] * hd),
        wo=(z["heads"] * hd, d), mlp_gate=(d, z["dense_width"]),
        mlp_up=(d, z["dense_width"]), mlp_down=(z["dense_width"], d),
        router=(d, z["routed"]), shared_gate=(d, z["shared_width"]),
        shared_up=(d, z["shared_width"]), shared_down=(z["shared_width"], d),
        w_gate=(z["held"], d, z["width"]), w_up=(z["held"], d, z["width"]),
        w_down=(z["held"], z["width"], d))
    want = {"['embed']": (z["vocab"], d), "['final_norm']": (d,),
            "['head']": (d, z["vocab"])}
    for i, (names, n) in enumerate(RUNS):
        want.update({f"['runs'][{i}]['{name}']": (n,) + shapes[name]
                     for name in names})
    assert {k: v.shape for k, v in leaves(seeded[0]).items()} == want
    assert sorted(leaves(seeded[0])) == sorted(LEAVES)
    assert all(a.dtype == jnp.float32 for a in leaves(seeded[0]).values())


def test_every_chips_router_columns_sum_to_zero(job, config, seeded):
    """The seeded router: a direction all positions share moves a chip's
    experts against each other, not the chip's load."""
    z = job.sizes(config)
    for run in seeded[0]["runs"]:
        if "router" in run:
            blocks = run["router"].reshape(-1, z["d"], z["routed"]
                                           // z["held"], z["held"])
            assert float(jnp.abs(blocks.sum(-1)).max()) < 1e-6
            assert float(run["router"].std()) == pytest.approx(
                (1 - 1 / z["held"]) ** 0.5 / z["d"] ** 0.5, rel=0.25)


def test_aux_counts_the_pairs_routed_to_the_held_experts(job, config, both,
                                                         seeded):
    _, _, aux, _, _ = both
    z = job.sizes(config)
    positions = BATCH * z["length"]
    assert aux.chosen.shape == (6, positions, z["top_k"])   # expert layers
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
    ``jax.vjp``) against ``jax.grad`` of the whole plain loss; and the
    forward pass alone gives the same loss."""
    _, _, _, want_loss, want = both
    reference = job.ReferenceSteps(config, BATCH)
    params = job.unstacked(seeded[0])
    loss, grads, _ = reference.loss_and_grads(params, *seeded[1])
    assert abs(loss - float(want_loss)) < 1e-5 * float(want_loss)
    assert reference.loss(params, *seeded[1]) == pytest.approx(loss,
                                                               rel=1e-6)
    errors = job.gradient_errors(grads, want)
    assert sorted(errors) == sorted(
        set(EXPERTS + DENSE) | {"embed", "final_norm", "head"})
    assert 0 <= max(errors.values()) < 1e-4


def test_reference_under_imposed_choices(job, config, seeded):
    """Its own choices imposed change nothing; other choices are weighed by
    the layer's own scores and applied in the chosen experts' place, and the
    reference still reports the choices it would have made."""
    reference = job.ReferenceSteps(config, BATCH)
    params = job.unstacked(seeded[0])
    loss, grads, chosen = reference.loss_and_grads(params, *seeded[1])
    again, same, _ = reference.loss_and_grads(params, *seeded[1],
                                              imposed=chosen)
    assert again == loss
    assert max(job.gradient_errors(same, grads).values()) == 0.0
    z = job.sizes(config)
    others = (chosen + 1) % z["routed"]
    moved, other_grads, own = reference.loss_and_grads(
        params, *seeded[1], imposed=others)
    assert moved != loss
    assert min(job.gradient_errors(other_grads, grads)[name]
               for name in ("router", "w_up", "wq")) > 1e-3
    # The first expert layer sees the same input whatever is imposed on it.
    np.testing.assert_array_equal(own[0], chosen[0])


def test_full_layers_get_no_positions(job, config, seeded):
    """A ``full_attention`` layer's attention half is the same function
    whatever positions it is handed, bit for bit; a ``sliding_attention``
    layer's reads them."""
    cfg = job.model_config(config)
    run = jax.tree_util.tree_map(lambda a: a[0], seeded[0]["runs"][2])
    x = jnp.asarray(np.random.RandomState(5).randn(64, 32), jnp.float32)
    tables = lambda positions: rope_tables(positions, cfg.head_dim,
                                           cfg.rope_theta)
    here, there = tables(jnp.arange(64)), tables(jnp.arange(64) + 1000)
    full = [afmoe._attention_half(cfg, False, p, x, run)
            for p in (here, there)]
    np.testing.assert_array_equal(*full)
    window = [afmoe._attention_half(cfg, True, p, x, run)
              for p in (here, there)]
    # Rotary positions are relative: shifted alike they change nothing but
    # rounding, and they are there: against no rotation the result differs.
    np.testing.assert_allclose(*window, rtol=1e-3, atol=1e-4)
    assert float(jnp.abs(window[0] - afmoe._attention_half(
        cfg, True, tables(jnp.zeros(64, jnp.int32)), x, run)).max()) > 1e-3


def test_window_layers_read_the_window_and_nothing_before_it(job, config,
                                                             seeded):
    """Change the first 8 positions: under a window of 16 a
    ``sliding_attention`` layer's output from position 24 on is what it
    was, a ``full_attention`` layer's is not."""
    cfg = job.model_config(config)
    run = jax.tree_util.tree_map(lambda a: a[0], seeded[0]["runs"][2])
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(64, 32), jnp.float32)
    y = x.at[:8].set(jnp.asarray(rng.randn(8, 32), jnp.float32))
    positions = rope_tables(jnp.arange(64), cfg.head_dim, cfg.rope_theta)
    window = [afmoe._attention_half(cfg, True, positions, v, run)
              for v in (x, y)]
    np.testing.assert_allclose(window[0][24:], window[1][24:], atol=1e-6)
    assert float(jnp.abs(window[0][8:23] - window[1][8:23]).max()) > 1e-4
    full = [afmoe._attention_half(cfg, False, positions, v, run)
            for v in (x, y)]
    assert float(jnp.abs(full[0][24:] - full[1][24:]).max()) > 1e-4


def test_an_expert_bias_chooses_and_takes_no_gradient(job, small):
    """A tree that holds ``expert_bias``: model and reference choose by
    ``score + bias``, agree in loss, and the bias's gradient is zero."""
    config, params, batch = small
    cfg = job.model_config(config)
    rng = np.random.RandomState(7)
    biased = dict(params, runs=[
        run if "router" not in run else dict(run, expert_bias=jnp.asarray(
            rng.randn(run["router"].shape[0], 8) * 0.3, jnp.float32))
        for run in params["runs"]])
    (loss, aux), grads = jax.value_and_grad(
        lambda p: afmoe.loss_fn(p, *batch, cfg), has_aux=True)(biased)
    want = job.reference_loss(config, biased, *batch)
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    plain = afmoe.loss_fn(params, *batch, cfg)[1]
    assert (np.asarray(aux.chosen) != np.asarray(plain.chosen)).mean() > 0.05
    for run in grads["runs"]:
        if "expert_bias" in run:
            np.testing.assert_array_equal(run["expert_bias"], 0)


def test_keeping_the_flash_output_or_not_changes_nothing(job, small,
                                                         monkeypatch):
    """Which layer types keep their flash output across the recomputation
    is memory against time, never a result."""
    config, params, batch = small
    cfg = job.model_config(config)

    def step(kept):
        monkeypatch.setattr(afmoe, "KEPT_ATTENTION", kept)
        return jax.value_and_grad(
            lambda p: afmoe.loss_fn(p, *batch, cfg), has_aux=True)(params)

    assert afmoe.KEPT_ATTENTION == (S, F)
    (loss, _), grads = step((S, F))
    for kept in [(F,), (S,)]:
        (got, _), got_grads = step(kept)
        assert float(got) == pytest.approx(float(loss), rel=1e-6)
        for name, leaf in leaves(got_grads).items():
            np.testing.assert_allclose(leaf, leaves(grads)[name], rtol=1e-4,
                                       atol=1e-7, err_msg=f"{kept} {name}")


def published_config():
    """The catalog's ``config`` where the guides are installed, and the
    benchmark's configuration with its ``published`` values put back."""
    with open(os.path.join(BENCH, "configs", "trinity-mini-ep8.json")) as f:
        ours = json.load(f)
    found = [dict(ours, **ours["published"])]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            found += [row["config"] for row in map(json.loads, f)
                      if row["name"] == "Trinity-Mini"]
    return found


def test_the_published_defaults_are_the_catalogs_config():
    cfg = afmoe.AfmoeConfig()
    fields = {f.name for f in dataclasses.fields(cfg)} | {
        "num_hidden_layers"}
    published = published_config()
    assert len(published) == 1 + os.path.exists(CATALOG)
    for config in published:
        shared = fields & set(config)
        assert shared >= {
            "vocab_size", "hidden_size", "layer_types", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "num_experts_per_tok", "num_shared_experts", "sliding_window",
            "rope_theta", "rms_norm_eps", "score_func", "route_norm",
            "route_scale", "mup_enabled", "num_hidden_layers"}
        for name in shared:
            value = getattr(cfg, name)
            assert (list(value) if isinstance(value, tuple) else value) \
                == config[name], name
        assert config["layer_types"] == [S, S, S, F] * 8
        assert cfg.experts_held == config["num_experts"]


def test_the_benchmarks_configuration_is_the_catalogs_but_for_its_cut():
    """Every key of the catalog's ``config`` is in the file under the same
    key with the same value, but for those ``reduced`` lists; the cut keeps
    a dense layer and a whole period, 16 experts and an eighth of the
    vocabulary, and no width."""
    ours, *catalog = published_config()
    with open(os.path.join(BENCH, "configs", "trinity-mini-ep8.json")) as f:
        cut = json.load(f)
    assert cut["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    for config in catalog:
        assert {k: v for k, v in config.items() if cut[k] != v}.keys() \
            == set(cut["reduced"])
        assert cut["published"] == {k: config[k] for k in cut["reduced"]}
    assert (cut["num_hidden_layers"], cut["num_dense_layers"],
            cut["num_experts"], cut["vocab_size"]) == (5, 1, 16, 25024)
    assert cut["layer_types"] == [S, S, S, S, F]
    assert cut["vocab_size"] * 8 == ours["vocab_size"]
    for width, value in dict(
            hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
            head_dim=128, intermediate_size=6144, moe_intermediate_size=1024,
            num_experts_per_tok=8, num_shared_experts=1, sliding_window=2048,
            route_scale=2.826, rms_norm_eps=1e-5).items():
        assert cut[width] == value, width


def test_the_share_holds_705_million_parameters(job):
    with open(os.path.join(BENCH, "configs", "trinity-mini-ep8.json")) as f:
        config = json.load(f)
    shapes = jax.eval_shape(lambda: job.seeded_params(config, 0))
    count = lambda tree: sum(math.prod(a.shape)
                             for a in jax.tree_util.tree_leaves(tree))
    assert [count(run) for run in shapes["runs"]] == [
        65_020_160, 3 * 134_488_320, 134_488_320]
    assert count(shapes["embed"]) + count(shapes["head"]) == 102_498_304
    assert count(shapes) == 705_473_792
