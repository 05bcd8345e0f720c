"""Kimi-Linear (Kimi Delta Attention in three layers of four, latent
attention without positions in the fourth, a sigmoid router with a shared
expert behind a leading dense layer): the model of
``horovod_tpu/models/kimi_linear.py`` against the plain reference of
``benchmarks/jobs/kimi_linear.py`` (KDA a token at a time), at tiny widths
on the CPU, float32, a layer of each of the cell's three kinds."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import decoder, kimi_linear
from horovod_tpu.parallel import flash, kda

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REAL = os.path.join(BENCH, "configs", "kimi-linear-48b-a3b-ep32.json")
TINY = os.path.join(BENCH, "tests", "cells", "configs", "kimi-tiny.json")
SEED, BATCH = 11, 2


@pytest.fixture(scope="module")
def job(bench_job):
    return bench_job("kimi_linear")


@pytest.fixture(scope="module")
def config():
    """The rehearsal configuration: three layers (KDA and a dense MLP; KDA
    with experts; latent attention with experts); 4 of 8 experts held."""
    with open(TINY) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def seeded(job, config):
    return (job.seeded_params(config, SEED),
            job.seeded_batch(config, SEED, BATCH))


@pytest.fixture(scope="module")
def cfg(job, config):
    return job.model_config(config)


@pytest.fixture(scope="module")
def both(job, config, cfg, seeded):
    """Loss and gradients of model and reference, each by ``jax.grad``."""
    params, batch = seeded
    (loss, aux), grads = jax.value_and_grad(
        lambda p: kimi_linear.loss_fn(p, *batch, cfg), has_aux=True)(params)
    want, want_grads = jax.value_and_grad(
        lambda p: job.reference_loss(config, p, *batch))(params)
    return loss, grads, aux, want, want_grads


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


NORMS = ("attn_norm", "mlp_norm")
KDA = ("w_q", "w_k", "w_v", "conv_q", "conv_k", "conv_v", "A_log",
       "dt_bias", "w_fa", "w_fb", "w_b", "w_ga", "w_gb", "o_norm", "w_o")
MLA = ("mla_wq", "w_kva", "kva_norm", "w_kvb", "wo")
DENSE = ("mlp_gate", "mlp_up", "mlp_down")
EXPERTS = ("router", "shared_gate", "shared_up", "shared_down", "w_gate",
           "w_up", "w_down")
#: (mixer's leaves, second half's leaves, layers) of the three runs.
RUNS = [(KDA, DENSE, 1), (KDA, EXPERTS, 1), (MLA, EXPERTS, 1)]
LEAVES = ["['embed']", "['final_norm']", "['head']"] + [
    f"['runs'][{i}]['{name}']" for i, (mixer, mlp, _) in enumerate(RUNS)
    for name in NORMS + mixer + mlp]


def test_the_loss_equals_the_reference(both):
    loss, _, _, want, _ = both
    assert abs(float(loss) - float(want)) < 2e-5 * float(want)
    assert 1.0 < float(loss) < 20.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf_equals_the_reference(both, leaf):
    _, grads, _, _, want = both
    got, want = leaves(grads)[leaf], leaves(want)[leaf]
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_the_tree_is_the_one_the_model_describes(job, config, seeded, cfg):
    z = job.sizes(config)
    d, heads = z["d"], z["heads"]
    wide, rank = z["kda_heads"] * z["kda_dim"], z["gate_rank"]
    shapes = dict(
        attn_norm=(d,), mlp_norm=(d,), w_q=(d, wide), w_k=(d, wide),
        w_v=(d, wide), conv_q=(wide, 4), conv_k=(wide, 4),
        conv_v=(wide, 4), A_log=(z["kda_heads"],), dt_bias=(wide,),
        w_fa=(d, rank), w_fb=(rank, wide), w_b=(d, z["kda_heads"]),
        w_ga=(d, rank), w_gb=(rank, wide), o_norm=(z["kda_dim"],),
        w_o=(wide, d), mla_wq=(d, heads * (z["nope"] + z["rope"])),
        w_kva=(d, z["kv_rank"] + z["rope"]), kva_norm=(z["kv_rank"],),
        w_kvb=(z["kv_rank"], heads * (z["nope"] + z["v_dim"])),
        wo=(heads * z["v_dim"], d), mlp_gate=(d, z["dense_width"]),
        mlp_up=(d, z["dense_width"]), mlp_down=(z["dense_width"], d),
        router=(d, z["routed"]), shared_gate=(d, z["shared_width"]),
        shared_up=(d, z["shared_width"]), shared_down=(z["shared_width"], d),
        w_gate=(z["held"], d, z["width"]), w_up=(z["held"], d, z["width"]),
        w_down=(z["held"], z["width"], d))
    want = {"['embed']": (z["vocab"], d), "['final_norm']": (d,),
            "['head']": (d, z["vocab"])}
    for i, (mixer, mlp, n) in enumerate(RUNS):
        want.update({f"['runs'][{i}]['{name}']": (n,) + shapes[name]
                     for name in NORMS + mixer + mlp})
    assert {k: v.shape for k, v in leaves(seeded[0]).items()} == want
    assert all(a.dtype == jnp.float32 for a in leaves(seeded[0]).values())
    # The runs are the model's own, in published order.
    assert kimi_linear.layer_runs(cfg) == [
        ("kda", True, 1), ("kda", False, 1), ("mla", False, 1)] \
        == job.runs(z)
    # The seeded decays: A in [1, 16) a head, softplus(dt_bias) in [1e-3,
    # 0.1) a channel.
    for run in (0, 1):
        rate = np.exp(seeded[0]["runs"][run]["A_log"])
        dt = jax.nn.softplus(seeded[0]["runs"][run]["dt_bias"])
        assert 1.0 <= rate.min() and rate.max() < 16.0
        assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) < 0.1001


def test_layer_lists_that_do_not_split_the_stack_are_refused(cfg):
    with pytest.raises(ValueError, match="do not split"):
        kimi_linear.layer_runs(dataclasses.replace(cfg, kda_layers=(1,)))
    with pytest.raises(ValueError, match="do not split"):
        kimi_linear.layer_runs(dataclasses.replace(
            cfg, full_attn_layers=(2, 3)))


def test_every_chips_router_columns_sum_to_zero(job, config, seeded):
    z = job.sizes(config)
    for run in seeded[0]["runs"][1:]:
        blocks = run["router"].reshape(-1, z["d"], z["routed"] // z["held"],
                                       z["held"])
        assert float(jnp.abs(blocks.sum(axis=-1)).max()) < 1e-6


def test_aux_counts_the_expert_layers_in_their_order(job, config, both,
                                                     seeded):
    _, _, aux, _, _ = both
    z = job.sizes(config)
    positions = BATCH * z["length"]
    assert aux.chosen.shape == (2, positions, z["top_k"])
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
    ``jax.vjp``) against ``jax.grad`` of the whole plain loss; the forward
    pass alone gives the same loss; its own choices imposed change
    nothing."""
    _, _, _, want, want_grads = both
    reference = job.ReferenceSteps(config, BATCH)
    params = job.unstacked(seeded[0])
    loss, grads, chosen = reference.loss_and_grads(params, *seeded[1])
    assert loss == pytest.approx(float(want), rel=1e-5)
    assert reference.loss(params, *seeded[1]) == pytest.approx(loss,
                                                               rel=1e-6)
    errors = job.gradient_errors(grads, want_grads)
    assert sorted(errors) == sorted(
        set(NORMS + KDA + MLA + DENSE + EXPERTS)
        | {"embed", "final_norm", "head"})
    assert 0 <= max(errors.values()) < 1e-4
    again, same, _ = reference.loss_and_grads(params, *seeded[1],
                                              imposed=chosen)
    assert again == loss
    assert max(job.gradient_errors(same, grads).values()) == 0.0


@pytest.fixture(scope="module")
def kept_and_not(job, config):
    """``{kept KDA layers: (loss, gradients)}`` of the jitted step with the
    tiny stack's layer 1 kept (the default: its layer 2 is not) and with
    none, at one KDA head of 128 lanes: the width at which the passes round
    the scan are the Pallas kernels the cell runs.  Below it they are plain
    ``jnp``, and XLA's CPU backend fuses the L2 norm of ``q`` and ``k``
    differently in the forward pass and in the recomputation, so those two
    differ in their last bits between the passes whatever is kept."""
    assert 1 in kimi_linear.KEPT_KDA and 2 not in kimi_linear.KEPT_KDA
    wide = json.loads(json.dumps(config))
    wide["linear_attn_config"].update(head_dim=128, num_heads=1)
    cfg = job.model_config(wide)
    params = job.seeded_params(wide, SEED)
    batch = job.seeded_batch(wide, SEED, BATCH)
    step = jax.value_and_grad(
        lambda p: kimi_linear.loss_fn(p, *batch, cfg)[0])
    found = {kimi_linear.KEPT_KDA: jax.jit(step)(params)}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kimi_linear, "KEPT_KDA", ())
        found[()] = jax.jit(step)(params)
    return found


@pytest.mark.parametrize("leaf", ["loss"] + LEAVES)
def test_keeping_the_scans_residuals_changes_no_bit(kept_and_not, leaf):
    """The kept output and states are the forward kernel's own: where the
    recomputation repeats the forward pass bit for bit, as XLA's CPU
    backend does here, the loss and every gradient leaf are the same bits
    with and without them."""
    (loss, grads), (loss_none, grads_none) = kept_and_not.values()
    if leaf == "loss":
        assert np.asarray(loss).tobytes() == np.asarray(loss_none).tobytes()
    else:
        assert float(jnp.abs(leaves(grads)[leaf]).max()) > 0
        np.testing.assert_array_equal(leaves(grads)[leaf],
                                      leaves(grads_none)[leaf])


def _one_layer(cfg, seeded, run, layers):
    """``(the layer's function, its input x [S, hidden], its parameters)``
    for the first layer of run ``run`` of the tiny stack."""
    mixer, dense, _ = kimi_linear.layer_runs(cfg)[run]
    layer = kimi_linear._layer(cfg, mixer, dense, layers)
    p = jax.tree.map(lambda a: a[0], seeded[0]["runs"][run])
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (seeded[1][0].shape[1], cfg.hidden_size))
    return lambda x, p: jnp.sum(layer(x, p)[0]), x.astype(cfg.dtype), p


def _kernel_calls(jaxpr, name):
    from horovod_tpu.analysis.jaxpr_check import _sub_jaxprs
    calls = 0
    for eqn in jaxpr.eqns:
        calls += eqn.primitive.name == "pallas_call" \
            and eqn.params.get("name") == name
        calls += sum(_kernel_calls(getattr(sub, "jaxpr", sub), name)
                     for sub in _sub_jaxprs(eqn))
    return calls


@pytest.mark.parametrize("layers,forward", [(range(1, 2), 1),
                                            (range(2, 3), 2)],
                         ids=["kept", "not_kept"])
def test_a_kept_layer_runs_the_forward_scan_kernel_once(cfg, seeded, layers,
                                                        forward):
    """``jax.grad`` of a KDA layer's body holds one call of ``hvd_kda_fwd``
    where the layer is kept and two (the forward pass and the
    recomputation) where it is not; the backward kernel once either way."""
    f, x, p = _one_layer(cfg, seeded, 0, layers)
    jaxpr = jax.make_jaxpr(jax.grad(f))(x, p).jaxpr
    assert _kernel_calls(jaxpr, "hvd_kda_fwd") == forward
    assert _kernel_calls(jaxpr, "hvd_kda_bwd") == 1


@pytest.mark.parametrize("run,layers,names", [
    (0, range(1, 2), ("hvd_kda_states",)), (1, range(2, 3), ()),
    (2, range(3, 4), ("hvd_flash_lse",))], ids=["kda_kept", "kda", "mla"])
def test_each_layer_keeps_its_own_kernels_residuals_only(cfg, seeded, capsys,
                                                         run, layers, names):
    """What a layer hands its backward pass beyond its arguments: a kept
    KDA layer its scan's output and states, another KDA layer nothing, a
    latent layer its flash output and logsumexp.  A ``custom_vjp``'s first
    output shows as the ``reduce_precision`` that wraps it, so the names
    seen are the second outputs'."""
    f, x, p = _one_layer(cfg, seeded, run, layers)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(f, x, p)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line]
    assert len(kept) == 2 * len(names)
    seen = {name for line in kept
            for name in kda.SAVED + flash.SAVED if f"'{name}'" in line}
    assert seen == set(names)


def test_the_recurrence_is_the_delta_rule_token_by_token(job):
    """The reference's scan against the state written out in numpy."""
    rng = np.random.RandomState(5)
    seq, heads, d = 6, 2, 4
    q, k, v = (rng.randn(seq, heads, d).astype(np.float32)
               for _ in range(3))
    g = -np.abs(rng.randn(seq, heads, d)).astype(np.float32)
    beta = rng.rand(seq, heads).astype(np.float32)
    state, want = np.zeros((heads, d, d)), []
    for t in range(seq):
        for h in range(heads):
            decayed = np.exp(g[t, h])[:, None] * state[h]
            state[h] = decayed + beta[t, h] * np.outer(
                k[t, h], v[t, h] - k[t, h] @ decayed)
        want.append(np.einsum("hkv,hk->hv", state, q[t]))
    with jax.default_matmul_precision("highest"):
        got = job.recurrence(*map(jnp.asarray, (q, k, v, g, beta)))
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-5, atol=1e-6)


def test_short_convolution_is_causal_from_a_zero_history(job):
    x = jnp.asarray(np.random.RandomState(1).randn(10, 3), jnp.float32)
    w = jnp.asarray(np.random.RandomState(2).randn(3, 4), jnp.float32)
    want = np.zeros((10, 3), np.float32)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += np.asarray(w)[:, j] * np.asarray(x)[t - 3 + j]
    want = np.asarray(jax.nn.silu(want))
    np.testing.assert_allclose(kimi_linear.short_conv(x, w), want,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(job.short_conv(x, w), want, rtol=1e-5,
                               atol=1e-6)


def test_the_latent_layers_shared_key_is_not_rotated(job, config, seeded,
                                                     cfg):
    """``mla_use_nope``: a layer's output does not depend on where its
    sequence starts, i.e. the same tokens give the same first rows whatever
    follows, and shifting content by a position shifts the output (no
    position enters but through the causal mask)."""
    layer = jax.tree_util.tree_map(lambda a: a[0], seeded[0]["runs"][2])
    z = job.sizes(config)
    x = jnp.asarray(np.random.RandomState(3).randn(64, z["d"]), jnp.float32)
    got = kimi_linear._mla_half(cfg, x, layer)
    # Two copies of the first 32 rows one after the other: with rotary
    # positions the second copy's self-attention would differ from the
    # first's by more than what the first copy adds as context; without, a
    # sequence of 32 alone reads exactly the first 32 rows.
    alone = kimi_linear._mla_half(cfg, x[:32], layer)
    np.testing.assert_allclose(got[:32], alone, rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        want = job.mla_mixer(z, layer, x, job.causal(64))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_a_selection_bias_chooses_and_takes_no_gradient(job, config, seeded,
                                                        cfg):
    params, batch = seeded
    z = job.sizes(config)
    bias = np.zeros((z["routed"],), np.float32)
    bias[z["first"]] = 10.0      # every position now chooses this expert
    runs = [dict(run) for run in params["runs"]]
    for run in runs[1:]:
        run["e_score_correction_bias"] = jnp.broadcast_to(
            bias, (run["router"].shape[0], z["routed"]))
    biased = dict(params, runs=runs)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: kimi_linear.loss_fn(p, *batch, cfg), has_aux=True)(biased)
    assert bool((aux.chosen == z["first"]).any(axis=-1).all())
    want = job.reference_loss(config, biased, *batch)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    for run in grads["runs"][1:]:
        assert float(jnp.abs(run["e_score_correction_bias"]).max()) == 0.0


def test_the_thirty_two_shares_add_up(job):
    """Over all 32 shares of a layer at a small size (1 of 32 experts
    each), the routed parts summed and what every chip computes alike (the
    KDA mixer, the shared expert) counted once equal the uncut reference
    layer."""
    with open(TINY) as f:
        config = json.load(f)
    shares, held = 32, 1
    whole = dict(config, num_experts=shares * held,
                 published=dict(config["published"],
                                num_experts=shares * held),
                 deployment=dict(config["deployment"], first_expert=0))
    params = job.seeded_params(whole, SEED)
    layer = jax.tree_util.tree_map(lambda a: a[0], params["runs"][1])
    z = job.sizes(whole)
    x = jnp.asarray(np.random.RandomState(9).randn(64, z["d"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, chosen = job.reference_layer(z, "kda", False, None, layer, x)
    cfg = job.model_config(whole)
    h = kimi_linear._kda_half(cfg, x, layer)

    @jax.jit
    def share(first, mine):
        # ``first_expert`` is static in the model; the router's columns are
        # rolled instead so that every share is "experts 0 to held" of its
        # own router and one program serves all 32.
        rolled = dict(layer, router=jnp.roll(layer["router"], -first,
                                             axis=1), **mine)
        return decoder.expert_half(
            dataclasses.replace(cfg, experts_held=held, first_expert=0),
            h, rolled, cfg.num_experts_per_token)

    summed, routed = None, 0
    for n in range(shares):
        mine = {name: layer[name][n * held:(n + 1) * held]
                for name in ("w_gate", "w_up", "w_down")}
        out, (here, picked) = share(n * held, mine)
        np.testing.assert_array_equal(
            np.sort((np.asarray(picked) + n * held) % (shares * held)),
            np.sort(np.asarray(chosen)))
        routed += int(here)
        # out = h + shared + this share's routed part.
        summed = out if summed is None else summed + out
    assert routed == chosen.size
    m = decoder.rms_norm(h, layer["mlp_norm"], cfg.rms_norm_eps)
    shared = decoder.gated_mlp(m, layer["shared_gate"], layer["shared_up"],
                               layer["shared_down"], cfg.dtype)
    # 32 sums of values of a few units each, in float32.
    np.testing.assert_allclose(summed - (shares - 1) * (h + shared), uncut,
                               rtol=5e-4, atol=1e-4)


def published_config():
    """The benchmark's configuration with its ``published`` values put
    back, and the catalog's ``config`` where the guides are installed."""
    with open(REAL) as f:
        ours = json.load(f)
    back = dict(ours, **ours["published"])
    back["linear_attn_config"] = dict(
        ours["linear_attn_config"], **ours["published"]["linear_attn_config"])
    found = [back]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            found += [row["config"] for row in map(json.loads, f)
                      if row["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    return found


def test_the_published_defaults_are_the_catalogs_config():
    cfg = kimi_linear.KimiLinearConfig()
    fields = {f.name for f in dataclasses.fields(cfg)}
    published = published_config()
    assert len(published) == 1 + os.path.exists(CATALOG)
    for config in published:
        shared = fields & set(config)
        assert shared >= {
            "vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "intermediate_size",
            "moe_intermediate_size", "num_experts", "num_experts_per_token",
            "num_shared_experts", "routed_scaling_factor",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rms_norm_eps"}
        for name in shared:
            assert getattr(cfg, name) == config[name], name
        linear = config["linear_attn_config"]
        assert cfg.kda_layers == tuple(linear["kda_layers"])
        assert cfg.full_attn_layers == tuple(linear["full_attn_layers"])
        assert (cfg.kda_num_heads, cfg.kda_head_dim,
                cfg.short_conv_kernel_size) == (
            linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
        assert cfg.experts_held == config["num_experts"]
        assert config["q_lora_rank"] is None and config["mla_use_nope"]
    assert len(kimi_linear.layer_runs(cfg)) == 15


def test_the_benchmarks_configuration_is_the_catalogs_but_for_its_cut(job):
    """Every key of the catalog's ``config`` is in the file under the same
    key with the same value, but for those ``reduced`` lists (the two layer
    lists under their group's key); the cut keeps published layers 1 to 5,
    8 experts and an eighth of the vocabulary, and no width."""
    ours, *catalog = published_config()
    with open(REAL) as f:
        cut = json.load(f)
    assert cut["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "linear_attn_config"]
    for config in catalog:
        assert {k: v for k, v in config.items() if cut[k] != v}.keys() \
            == set(cut["reduced"])
        group = config["linear_attn_config"]
        assert {k for k, v in group.items()
                if cut["linear_attn_config"][k] != v} == {
            "kda_layers", "full_attn_layers"}
        assert cut["published"] == dict(
            {k: config[k] for k in cut["reduced"][:3]},
            linear_attn_config={k: group[k] for k in ("kda_layers",
                                                      "full_attn_layers")})
    assert (cut["num_hidden_layers"], cut["num_experts"],
            cut["vocab_size"]) == (5, 8, 20480)
    assert cut["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5]
    assert cut["linear_attn_config"]["full_attn_layers"] == [4]
    assert cut["vocab_size"] * 8 == ours["vocab_size"]
    assert cut["deployment"]["chips_that_share_a_layer"] == 32 \
        == ours["num_experts"] // cut["num_experts"]
    for width, value in dict(
            hidden_size=2304, num_attention_heads=32, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            intermediate_size=9216, moe_intermediate_size=1024,
            num_experts_per_token=8, num_shared_experts=1,
            routed_scaling_factor=2.446, rms_norm_eps=1e-5,
            first_k_dense_replace=1).items():
        assert cut[width] == value, width
    assert set(cut["correct"]["gradient_limits"]) == set(
        NORMS + KDA + MLA + DENSE + EXPERTS) | {"embed", "final_norm",
                                                "head"}
    assert all(entry.get("why") for entry in cut["assumed"].values())
    with pytest.raises(ValueError, match="is built"):
        job.sizes(dict(cut, num_expert_group=8))
    with pytest.raises(ValueError, match="is built"):
        job.sizes(dict(cut, q_lora_rank=1536))


def test_the_share_holds_602_million_parameters(job):
    with open(REAL) as f:
        config = json.load(f)
    shapes = jax.eval_shape(lambda: job.seeded_params(config, 0))
    count = lambda tree: sum(math.prod(a.shape)
                             for a in jax.tree_util.tree_leaves(tree))
    assert count({n: shapes["runs"][0][n] for n in KDA}) == 39_514_272
    assert count({n: shapes["runs"][2][n] for n in MLA}) == 29_114_880
    assert [count(run) for run in shapes["runs"]] == [
        103_219_872, 2 * 103_809_696, 93_410_304, 103_809_696]
    assert count(shapes["embed"]) + count(shapes["head"]) == 94_371_840
    assert count(shapes) == 602_433_408 \
        == config["deployment"]["parameters_here"]
