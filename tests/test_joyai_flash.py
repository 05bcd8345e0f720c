"""JoyAI-LLM-Flash (latent attention, a sigmoid router with a shared expert,
the multi-token-prediction module): the model of
``horovod_tpu/models/joyai_flash.py`` against the plain reference of
``benchmarks/jobs/joyai_flash.py``, at tiny widths on the CPU, float32, in
the published ratios: the rotary part half the rest of a key, keys one and a
half times the values."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import decoder, joyai_flash

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REAL = os.path.join(BENCH, "configs", "joyai-llm-flash-ep16.json")
SEED, BATCH = 11, 2


@pytest.fixture(scope="module")
def job(bench_job):
    return bench_job("joyai_flash")


@pytest.fixture(scope="module")
def config():
    """The rehearsal configuration: a dense layer, two expert layers and
    the MTP module; 4 of 8 experts held."""
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "joyai-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def seeded(job, config):
    return (job.seeded_params(config, SEED),
            job.seeded_batch(config, SEED, BATCH))


@pytest.fixture(scope="module")
def cfg(job, config):
    return job.model_config(config)


def total(config, parts):
    return parts[0] + config["assumed"]["mtp_loss_weight"]["value"] \
        * parts[1]


@pytest.fixture(scope="module")
def both(job, config, cfg, seeded):
    """Losses and gradients of model and reference, each by ``jax.grad``."""
    params, batch = seeded
    (_, (aux, main, ahead)), grads = jax.value_and_grad(
        lambda p: joyai_flash.loss_fn(p, *batch, cfg), has_aux=True)(params)
    (_, want_parts), want = jax.value_and_grad(
        lambda p: (lambda parts: (total(config, parts), parts))(
            job.reference_loss(config, p, *batch)), has_aux=True)(params)
    return (main, ahead), grads, aux, want_parts, want


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


MLA = ("attn_norm", "mlp_norm", "w_qa", "qa_norm", "w_qb", "w_kva",
       "kva_norm", "w_kvb", "wo")
DENSE = MLA + ("mlp_gate", "mlp_up", "mlp_down")
EXPERTS = MLA + ("router", "shared_gate", "shared_up", "shared_down",
                 "w_gate", "w_up", "w_down")
MTP = ("enorm", "hnorm", "w_eh", "mtp_norm")
LEAVES = ["['embed']", "['final_norm']", "['head']"] \
    + [f"['runs'][0]['{name}']" for name in DENSE] \
    + [f"['runs'][1]['{name}']" for name in EXPERTS] \
    + [f"['mtp']['{name}']" for name in MTP] \
    + [f"['mtp']['block']['{name}']" for name in EXPERTS]


def test_both_losses_equal_the_reference(both):
    got, _, _, want, _ = both
    for mine, theirs in zip(got, want):
        assert abs(float(mine) - float(theirs)) < 2e-5 * float(theirs)
        assert 1.0 < float(mine) < 20.0
    assert float(got[0]) != float(got[1])


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(both, leaf):
    _, grads, _, _, want = both
    got, want = leaves(grads)[leaf], leaves(want)[leaf]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_the_tree_is_the_one_the_model_describes(job, config, seeded):
    z = job.sizes(config)
    d, heads = z["d"], z["heads"]
    shapes = dict(
        attn_norm=(d,), mlp_norm=(d,), w_qa=(d, z["q_rank"]),
        qa_norm=(z["q_rank"],),
        w_qb=(z["q_rank"], heads * (z["nope"] + z["rope"])),
        w_kva=(d, z["kv_rank"] + z["rope"]), kva_norm=(z["kv_rank"],),
        w_kvb=(z["kv_rank"], heads * (z["nope"] + z["v_dim"])),
        wo=(heads * z["v_dim"], d), mlp_gate=(d, z["dense_width"]),
        mlp_up=(d, z["dense_width"]), mlp_down=(z["dense_width"], d),
        router=(d, z["routed"]), shared_gate=(d, z["shared_width"]),
        shared_up=(d, z["shared_width"]), shared_down=(z["shared_width"], d),
        w_gate=(z["held"], d, z["width"]), w_up=(z["held"], d, z["width"]),
        w_down=(z["held"], z["width"], d))
    want = {"['embed']": (z["vocab"], d), "['final_norm']": (d,),
            "['head']": (d, z["vocab"]), "['mtp']['enorm']": (d,),
            "['mtp']['hnorm']": (d,), "['mtp']['mtp_norm']": (d,),
            "['mtp']['w_eh']": (2 * d, d)}
    want.update({f"['runs'][0]['{n}']": (1,) + shapes[n] for n in DENSE})
    want.update({f"['runs'][1]['{n}']": (2,) + shapes[n] for n in EXPERTS})
    want.update({f"['mtp']['block']['{n}']": (1,) + shapes[n]
                 for n in EXPERTS})
    assert {k: v.shape for k, v in leaves(seeded[0]).items()} == want
    assert sorted(leaves(seeded[0])) == sorted(LEAVES)
    assert all(a.dtype == jnp.float32 for a in leaves(seeded[0]).values())
    # The published ratios: the rotary part half the rest of a key, keys
    # one and a half times the values.
    assert z["nope"] == 2 * z["rope"] == z["v_dim"]
    assert 2 * (z["nope"] + z["rope"]) == 3 * z["v_dim"]


def test_every_chips_router_columns_sum_to_zero(job, config, seeded):
    z = job.sizes(config)
    for run in (seeded[0]["runs"][1], seeded[0]["mtp"]["block"]):
        blocks = run["router"].reshape(-1, z["d"], z["routed"] // z["held"],
                                       z["held"])
        assert float(jnp.abs(blocks.sum(-1)).max()) < 1e-6


def test_aux_counts_the_expert_layers_and_the_mtp_block_last(job, config,
                                                             both, seeded):
    _, _, aux, _, _ = both
    z = job.sizes(config)
    positions = BATCH * z["length"]
    assert aux.chosen.shape == (3, positions, z["top_k"])
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
    ``jax.vjp``, the MTP module's cotangent joining the main head's on the
    stack's last state) against ``jax.grad`` of the whole plain loss; and
    the forward pass alone gives the same losses."""
    _, _, _, want_parts, want = both
    weight = config["assumed"]["mtp_loss_weight"]["value"]
    reference = job.ReferenceSteps(config, BATCH)
    params = job.unstacked(seeded[0])
    parts, grads, _ = reference.loss_and_grads(params, *seeded[1])
    assert parts[0] == pytest.approx(float(want_parts[0]), rel=1e-5)
    assert parts[1] == pytest.approx(weight * float(want_parts[1]), rel=1e-5)
    assert reference.loss(params, *seeded[1]) == pytest.approx(parts,
                                                               rel=1e-6)
    errors = job.gradient_errors(grads, want)
    assert sorted(errors) == sorted(
        set(EXPERTS + DENSE + MTP) | {"embed", "final_norm", "head"}
        | {"mtp." + name for name in EXPERTS})
    assert 0 <= max(errors.values()) < 1e-4


def test_reference_under_imposed_choices(job, config, seeded):
    """Its own choices imposed change nothing; other choices are weighed by
    the layer's own scores and applied in the chosen experts' place, in the
    MTP module's block too."""
    reference = job.ReferenceSteps(config, BATCH)
    params = job.unstacked(seeded[0])
    parts, grads, chosen = reference.loss_and_grads(params, *seeded[1])
    again, same, _ = reference.loss_and_grads(params, *seeded[1],
                                              imposed=chosen)
    assert again == parts
    assert max(job.gradient_errors(same, grads).values()) == 0.0
    z = job.sizes(config)
    others = chosen.copy()
    others[-1] = (others[-1] + 1) % z["routed"]     # the block's alone
    moved, other_grads, own = reference.loss_and_grads(
        params, *seeded[1], imposed=others)
    assert moved[0] == parts[0] and moved[1] != parts[1]
    errors = job.gradient_errors(other_grads, grads)
    assert min(errors[n] for n in ("mtp.router", "mtp.w_up", "w_eh")) > 1e-3
    np.testing.assert_array_equal(own, chosen)


def test_the_mtp_rows_without_the_dummy_row_read_the_same(job, config,
                                                          seeded):
    """The published module has ``S - 1`` rows; the program's and the
    reference's ``S``-th is seen by no other row and weighed by no loss."""
    params, (tokens,) = seeded
    grad = lambda dummy: jax.value_and_grad(
        lambda p: total(config, job.reference_loss(config, p, tokens[:1],
                                                   dummy_row=dummy)))(params)
    (with_row, grads), (without, want) = grad(True), grad(False)
    assert float(with_row) == pytest.approx(float(without), rel=1e-6)
    assert max(job.gradient_errors(grads, want).values()) < 1e-5


def test_mtp_row_i_is_judged_on_token_i_plus_2(seeded, cfg):
    """Change token ``j``: as a target it moves row ``j - 2`` of the MTP
    loss and row ``j - 1`` of the main loss, and as an input no row before
    ``j - 1`` of the module (which reads token ``i + 1`` at row ``i``) and
    none before ``j`` of the stack."""
    params, (tokens,) = seeded
    tokens = tokens[:1]
    seq, j = tokens.shape[1], 40

    def rows(tokens):
        """``(main nll, MTP nll)`` by row, of the first sequence."""
        hidden, _ = joyai_flash.hidden_states(params, tokens, cfg)
        y, _ = joyai_flash.mtp_hidden_states(params, tokens, hidden, cfg)
        nll = lambda states, norm, targets: -jnp.take_along_axis(
            jax.nn.log_softmax(decoder.rms_norm(
                states, norm, cfg.rms_norm_eps) @ params["head"]),
            targets[:, None], axis=-1)[:, 0]
        return (nll(hidden[0], params["final_norm"],
                    jnp.roll(tokens[0], -1)),
                nll(y[0], params["mtp"]["mtp_norm"],
                    jnp.roll(tokens[0], -2)))

    moved = tokens.at[0, j].set((tokens[0, j] + 1) % cfg.vocab_size)
    (main, ahead), (main2, ahead2) = rows(tokens), rows(moved)
    changed = lambda a, b: np.flatnonzero(np.abs(np.asarray(a - b)) > 1e-6)
    assert changed(main, main2).min() == j - 1
    assert changed(ahead, ahead2).min() == j - 2
    # Row j - 2 of the module moved by its target alone: its state did not.
    hidden, _ = joyai_flash.hidden_states(params, tokens, cfg)
    hidden2, _ = joyai_flash.hidden_states(params, moved, cfg)
    y = [joyai_flash.mtp_hidden_states(params, t, h, cfg)[0][0]
         for t, h in ((tokens, hidden), (moved, hidden2))]
    assert changed(y[0].sum(-1), y[1].sum(-1)).min() == j - 1
    assert changed(hidden[0].sum(-1), hidden2[0].sum(-1)).min() == j
    # The losses are the means over S - 1 and S - 2 rows.
    got_main, got_ahead, _ = joyai_flash.losses(params, tokens, cfg)
    assert float(got_main) == pytest.approx(float(main[:seq - 1].mean()),
                                            rel=1e-5)
    assert float(got_ahead) == pytest.approx(float(ahead[:seq - 2].mean()),
                                             rel=1e-5)


def test_lambda_0_gives_the_main_loss_and_its_gradient(seeded, cfg, both):
    """``embed`` and ``head`` carry both sources: with ``lambda`` 0 they
    (and every leaf of the stack) have the main loss's gradient, the MTP
    module's leaves none; at the configuration's ``lambda`` the module's
    loss adds its own to both, and none to the stack's last norm."""
    params, batch = seeded
    grad = lambda **kw: jax.value_and_grad(
        lambda p: joyai_flash.loss_fn(p, *batch, dataclasses.replace(
            cfg, mtp_loss_weight=0.0, **kw))[0])(params)
    loss0, g0 = grad()
    main_alone, g_main = grad(num_nextn_predict_layers=0)
    assert float(loss0) == float(main_alone) == float(both[0][0])
    for name, leaf in leaves(g_main).items():
        if "mtp" in name:
            np.testing.assert_array_equal(leaves(g0)[name], 0)
        else:
            np.testing.assert_allclose(leaves(g0)[name], leaf, rtol=1e-5,
                                       atol=1e-9, err_msg=name)
    only = jax.tree_util.tree_map(jnp.subtract, both[1], g0)  # L_mtp's
    for name in ("['embed']", "['head']", "['runs'][1]['w_kvb']"):
        assert float(jnp.abs(leaves(only)[name]).max()) > 1e-7, name
        assert float(jnp.abs(leaves(g0)[name]).max()) > 1e-6, name
    assert float(jnp.abs(leaves(only)["['final_norm']"]).max()) < 1e-9


@pytest.mark.parametrize("rope,heads", [(64, 3), (8, 1)])
def test_interleaved_rotary_is_complex_multiplication(job, rope, heads):
    """Pairs ``(2i, 2i + 1)`` times ``exp(1j t theta ** (-2i / rope))``, in
    the model and in the reference."""
    theta, seq = 32000000.0, 50
    rng = np.random.RandomState(3)
    x = rng.randn(seq, heads, rope).astype(np.float32)
    positions = np.arange(seq)
    turn = np.exp(1j * positions[:, None] * theta ** (
        -np.arange(0, rope, 2) / rope)[None, :])
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn[:, None, :]
    want = np.stack([z.real, z.imag], axis=-1).reshape(x.shape)
    got = joyai_flash.rotary_interleaved(jnp.asarray(x),
                                         jnp.asarray(positions), theta)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(job.rotary_pairs(jnp.asarray(x), theta), want,
                               rtol=1e-4, atol=1e-5)
    # Not the half-split convention of decoder.rotary.
    half = decoder.rotary(jnp.asarray(x)[None], jnp.asarray(positions), theta)[0]
    assert float(jnp.abs(half - want).max()) > 0.1


def test_rotary_touches_the_rotary_dimensions_and_no_others(job, config,
                                                            seeded):
    """Shift every position: the rotary parts of queries and keys turn
    together, so the layer reads the same (relative positions); zero the
    rotary columns of ``w_qb`` and positions are read by nothing: the 128
    other dimensions of a key and the values carry none."""
    cfg = job.model_config(config)
    run = jax.tree_util.tree_map(lambda a: a[0], seeded[0]["runs"][1])
    x = jnp.asarray(np.random.RandomState(5).randn(64, 32), jnp.float32)
    here, there = jnp.arange(64), jnp.arange(64) + 1000
    half = lambda p, positions: joyai_flash._attention_half(cfg, positions,
                                                            x, p)
    np.testing.assert_allclose(half(run, here), half(run, there), rtol=1e-3,
                               atol=1e-4)
    assert float(jnp.abs(half(run, here) - half(
        run, jnp.zeros(64, jnp.int32))).max()) > 1e-3
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    columns = np.arange(run["w_qb"].shape[1]) % (nope + rope) >= nope
    blind = dict(run, w_qb=run["w_qb"] * ~columns)
    np.testing.assert_array_equal(half(blind, here),
                                  half(blind, jnp.zeros(64, jnp.int32)))


def test_a_selection_bias_chooses_and_takes_no_gradient(job, config, seeded,
                                                        cfg):
    """A tree that holds ``e_score_correction_bias``: model and reference
    choose by ``score + bias``, agree in loss, and the bias's gradient is
    zero."""
    params, batch = seeded
    rng = np.random.RandomState(7)
    bias = lambda run: dict(run, e_score_correction_bias=jnp.asarray(
        rng.randn(run["router"].shape[0], 8) * 0.3, jnp.float32))
    biased = dict(params, runs=[params["runs"][0], bias(params["runs"][1])],
                  mtp=dict(params["mtp"],
                           block=bias(params["mtp"]["block"])))
    (loss, (aux, *_)), grads = jax.value_and_grad(
        lambda p: joyai_flash.loss_fn(p, *batch, cfg), has_aux=True)(biased)
    want = total(config, job.reference_loss(config, biased, *batch))
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    plain = joyai_flash.loss_fn(params, *batch, cfg)[1][0]
    assert (np.asarray(aux.chosen) != np.asarray(plain.chosen)).mean() > 0.05
    np.testing.assert_array_equal(
        grads["runs"][1]["e_score_correction_bias"], 0)
    np.testing.assert_array_equal(
        grads["mtp"]["block"]["e_score_correction_bias"], 0)


def test_keeping_the_flash_output_or_not_changes_nothing(seeded, cfg,
                                                         monkeypatch):
    """Whether a layer keeps its flash output across the recomputation is
    memory against time, never a result."""
    params, batch = seeded

    def step(keep):
        monkeypatch.setattr(joyai_flash, "KEEP_ATTENTION", keep)
        return jax.value_and_grad(
            lambda p: joyai_flash.loss_fn(p, *batch, cfg)[0])(params)

    (loss, grads), (got, got_grads) = step(False), step(True)
    assert float(got) == pytest.approx(float(loss), rel=1e-6)
    for name, leaf in leaves(got_grads).items():
        np.testing.assert_allclose(leaf, leaves(grads)[name], rtol=1e-4,
                                   atol=1e-7, err_msg=name)


def test_the_sixteen_shares_add_up(job):
    """The routed parts that all shares of a layer give, with the shared
    expert counted once, equal the uncut reference layer; here 4 shares of
    4 of 16 experts."""
    with open(os.path.join(BENCH, "tests", "cells", "configs",
                           "joyai-tiny.json")) as f:
        config = json.load(f)
    shares, held = 4, 4
    whole = dict(config, n_routed_experts=shares * held,
                 published=dict(config["published"],
                                n_routed_experts=shares * held),
                 deployment=dict(config["deployment"], first_expert=0))
    params = job.seeded_params(whole, SEED)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["runs"][1])
    z = job.sizes(whole)
    x = jnp.asarray(np.random.RandomState(9).randn(64, z["d"]), jnp.float32)
    mask = jnp.asarray(np.tril(np.ones((64, 64), bool)))
    with jax.default_matmul_precision("highest"):
        uncut, chosen = job.reference_layer(z, False, mask, layer, x)
    cfg = job.model_config(whole)
    positions = jnp.arange(64)
    h = joyai_flash._attention_half(cfg, positions, x, layer)
    summed, routed = None, 0
    for share in range(shares):
        mine = dict(layer, **{name: layer[name][share * held:
                                                (share + 1) * held]
                              for name in ("w_gate", "w_up", "w_down")})
        out, (here, picked) = decoder.expert_half(
            dataclasses.replace(cfg, experts_held=held,
                                first_expert=share * held), h, mine,
            cfg.num_experts_per_tok)
        np.testing.assert_array_equal(picked, chosen)
        routed += int(here)
        # out = h + shared + this share's routed part.
        summed = out if summed is None else summed + out
    assert routed == chosen.size
    m = decoder.rms_norm(h, layer["mlp_norm"], cfg.rms_norm_eps)
    shared = decoder.gated_mlp(m, layer["shared_gate"], layer["shared_up"],
                               layer["shared_down"], cfg.dtype)
    np.testing.assert_allclose(summed - (shares - 1) * (h + shared), uncut,
                               rtol=2e-4, atol=2e-5)


def published_config():
    """The benchmark's configuration with its ``published`` values put
    back, and the catalog's ``config`` where the guides are installed."""
    with open(REAL) as f:
        ours = json.load(f)
    found = [dict(ours, **ours["published"])]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            found += [row["config"] for row in map(json.loads, f)
                      if row["name"] == "JoyAI-LLM-Flash"]
    return found


def test_the_published_defaults_are_the_catalogs_config():
    cfg = joyai_flash.JoyaiFlashConfig()
    fields = {f.name for f in dataclasses.fields(cfg)}
    published = published_config()
    assert len(published) == 1 + os.path.exists(CATALOG)
    for config in published:
        shared = fields & set(config)
        assert shared >= {
            "vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "intermediate_size",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rms_norm_eps",
            "num_nextn_predict_layers"}
        for name in shared:
            assert getattr(cfg, name) == config[name], name
        assert config["qk_head_dim"] == cfg.qk_nope_head_dim \
            + cfg.qk_rope_head_dim == 192
        assert cfg.experts_held == config["n_routed_experts"]


def test_the_benchmarks_configuration_is_the_catalogs_but_for_its_cut(job):
    """Every key of the catalog's ``config`` is in the file under the same
    key with the same value, but for those ``reduced`` lists; the cut keeps
    the dense layer, four expert layers and the MTP module, 16 experts and
    an eighth of the vocabulary, and no width."""
    ours, *catalog = published_config()
    with open(REAL) as f:
        cut = json.load(f)
    assert cut["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for config in catalog:
        assert {k: v for k, v in config.items() if cut[k] != v}.keys() \
            == set(cut["reduced"])
        assert cut["published"] == {k: config[k] for k in cut["reduced"]}
    assert (cut["num_hidden_layers"], cut["n_routed_experts"],
            cut["vocab_size"]) == (5, 16, 16160)
    assert cut["vocab_size"] * 8 == ours["vocab_size"]
    assert cut["deployment"]["chips_that_share_a_layer"] == 16 \
        == ours["n_routed_experts"] // cut["n_routed_experts"]
    for width, value in dict(
            hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, intermediate_size=7168,
            moe_intermediate_size=768, num_experts_per_tok=8,
            n_shared_experts=1, routed_scaling_factor=2.5,
            rms_norm_eps=1e-6, rope_theta=32000000,
            num_nextn_predict_layers=1).items():
        assert cut[width] == value, width
    assert set(cut["correct"]["gradient_limits"]) == set(
        EXPERTS + DENSE + MTP) | {"embed", "final_norm", "head"} | {
            "mtp." + name for name in EXPERTS}
    with pytest.raises(ValueError, match="is built"):
        job.sizes(dict(cut, n_group=8))


def test_the_share_holds_680_million_parameters(job):
    with open(REAL) as f:
        config = json.load(f)
    shapes = jax.eval_shape(lambda: job.seeded_params(config, 0))
    count = lambda tree: sum(math.prod(a.shape)
                             for a in jax.tree_util.tree_leaves(tree))
    mla = count({name: shapes["runs"][0][name] for name in MLA[2:]})
    assert mla == 26_347_520
    assert [count(run) for run in shapes["runs"]] == [
        70_391_808, 4 * 107_091_968]
    assert count(shapes["mtp"]) == 115_486_720
    assert count(shapes["embed"]) + count(shapes["head"]) == 66_191_360
    assert count(shapes) == 680_439_808 \
        == config["deployment"]["parameters_here"]
