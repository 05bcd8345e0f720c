"""The JoyAI-LLM-Flash training job (next-token loss plus the depth-1
multi-token-prediction loss) and its plain reference.

The program's side is the normal path: ``horovod_tpu/models/joyai_flash.py``
(latent attention through the flash kernels with keys wider than values,
the dropless expert layer with a sigmoid router and a shared expert beside
it, the MTP module on the shared embedding and head) under
``jax.value_and_grad``, AdamW through ``hvd.DistributedOptimizer`` inside
``hvd.shard_step``, state donated, one batch that lives on the device, as
``jobs/afmoe.py``, whose log lines, optimizer, judgement of gradient leaves,
gated unit and update by leaf this job takes as they are.

The reference (everything from ``rotary_pairs`` down) is the published
model written out in ``jax.numpy``, float32, every product at
``jax.default_matmul_precision("highest")``; it imports nothing from
``horovod_tpu.models`` or ``horovod_tpu.parallel`` and shares only the
layout of the parameter tree (``seeded_params``) and the batch.

The equations (``config``, whose keys are DeepSeek-V3's; † marks what the
published ``config.json`` does not carry and ``assumed`` states).  No bias
anywhere, RMSNorm eps ``rms_norm_eps``.  ``x0 = E[tokens]``.  Every layer:
``a = RMSNorm_in(x)``; ``c_q = RMSNorm_qa(a W_qa)`` (``q_lora_rank``); ``q =
c_q W_qb``, a head ``qk_nope_head_dim`` without positions and
``qk_rope_head_dim`` with; ``a W_kva`` splits into ``c_kv``
(``kv_lora_rank``) and ``k_rope``, ONE head of ``qk_rope_head_dim`` shared
by all query heads; ``RMSNorm_kva(c_kv) W_kvb`` gives a head its
``k_nope`` and its ``v`` (``v_head_dim``); ``q_rope`` and ``k_rope`` are
rotated by their positions over their own dimensions alone, pairs ``(2i, 2i
+ 1)`` (``rope_interleave``), theta ``rope_theta``, no scaling
(``rope_scaling`` null); ``s_ij = (q_nope_i . k_nope_j + q_rope_i .
k_rope_j) / sqrt(qk_head_dim)`` for ``j <= i``; ``o = softmax_j(s) v``; ``h =
x + o W_o``; ``m = RMSNorm_post(h)``.  The first ``first_k_dense_replace``
layers: ``f = (silu(m W1) * (m W3)) W2`` of width ``intermediate_size``.
The others (``moe_layer_freq`` 1): ``s = sigmoid(m Wr)`` over all published
experts (``scoring_func``); the ``num_experts_per_tok`` largest of ``s +
e_score_correction_bias`` (``topk_method`` ``noaux_tc`` with ``n_group`` =
``topk_group`` = 1: no groups; the bias† zero here, no gradient); ``w =
routed_scaling_factor x s[chosen] / (sum s[chosen] + 1e-20†)``
(``norm_topk_prob``); ``f = Shared(m) + sum_e w_e Expert_e(m)``, both gated
SiLU of width ``moe_intermediate_size``.  ``x' = h + f``.  ``L_main``: the
mean over the ``S - 1`` predictions of a sequence and over the sequences of
``-log softmax(RMSNorm_f(x_L) W_head)_i[t_{i+1}]``.  The MTP module
(``num_nextn_predict_layers`` 1; DeepSeek-V3, arXiv:2412.19437, section
2.2): ``u_i = [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(x_L,i)] W_eh``† for ``i < S
- 1`` with ``E`` the shared embedding and ``x_L`` taken before
``RMSNorm_f``†; ``y = Block_mtp(u)``, one further expert layer with its own
weights, causal; ``L_mtp``: the mean over ``i < S - 2`` of ``-log
softmax(RMSNorm_mtp(y) W_head)_i[t_{i+2}]`` with the shared head.  ``loss =
L_main + lambda† x L_mtp``, cross-entropy alone (no balancing loss†).

Departures from the published description: the sum over the chosen experts
runs over those HELD HERE (``n_routed_experts`` of them from
``deployment.first_expert``; what the absent ones would add is left out,
here and in the program alike, and the shared expert is whole); the
vocabulary is the slice held here (ids drawn from it, logits and loss over
it); and the MTP block runs over all ``S`` rows, the last a dummy fed
``E[t_0]`` that causal attention shows to no other row and no loss weighs
(``reference_loss(..., dummy_row=False)`` is the module over ``S - 1`` rows,
and the tests hold the two to each other), so that its choices line up with
the program's.  What ``model_type`` ``joyai_llm_flash`` may do beyond
DeepSeek-V3's keys is not in the catalog and cannot be checked here.

Every held expert is applied to every position and weighted by the routing
(zero where not chosen).  So that it fits one chip at the published widths
the reference runs a sequence at a time and a layer at a time (each layer's
gradient by ``jax.vjp`` of that layer from its saved input:
``ReferenceSteps``), attention two heads at a time and the experts one
after the other; ``reference_loss`` is the same functions composed whole,
and the tests hold the two to each other.
"""

import functools
import gc
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from harness import manifest as mf

afmoe = mf.load_module("jobs", "afmoe")
FirstStep, log, log_memory, highest = (
    afmoe.FirstStep, afmoe.log, afmoe.log_memory, afmoe.highest)
make_optimizer, choices_that_differ, first_gradients, leaves_outside = (
    afmoe.make_optimizer, afmoe.choices_that_differ, afmoe.first_gradients,
    afmoe.leaves_outside)
rms_norm, expert, gated, update_by_leaf = (
    afmoe.rms_norm, afmoe.expert, afmoe.gated, afmoe.update_by_leaf)

#: First steps by ``(seed, global batch)``: where ``Program.step`` and
#: ``reference_losses`` meet, as in ``jobs/sdar_moe.py``.
_first_steps = {}
BUILT = dict(scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
             topk_group=1, norm_topk_prob=True, moe_layer_freq=1,
             rope_interleave=True, rope_scaling=None, hidden_act="silu",
             attention_bias=False, tie_word_embeddings=False,
             num_nextn_predict_layers=1)


# -- what program and reference share: the tree's layout and the batch -------

def sizes(config: dict) -> dict:
    other = {k: config[k] for k, v in BUILT.items() if config[k] != v}
    if other:
        raise ValueError(f"{other}: only {BUILT} is built")
    assumed = config["assumed"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], dense_width=config["intermediate_size"],
        width=config["moe_intermediate_size"],
        shared_width=(config["moe_intermediate_size"]
                      * config["n_shared_experts"]),
        routed=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"],
        first=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]),
        route_scale=config["routed_scaling_factor"],
        mtp_weight=assumed["mtp_loss_weight"]["value"],
        length=assumed["sequence_length"]["value"])


def seeded_params(config: dict, seed: int, sharding=None) -> dict:
    """The parameter tree from the seed, float32, a run's layers stacked on
    a leading axis (``assumed.init``): normal(0, 1 / sqrt(fan_in))
    matrices, unit norms, an embedding of normal(0, 1) rows, router columns
    of normal(0, ``router_init.scale`` / sqrt(hidden)) with the mean of
    every chip's block of columns taken off (``assumed.router_init``, as
    ``jobs/afmoe.py`` has it and for its reason: the columns a chip holds
    sum to zero, so a direction that the positions of a layer share moves a
    chip's experts against each other and not the chip's load); no
    ``e_score_correction_bias`` (zero, frozen); made where ``sharding``
    says."""
    z = sizes(config)
    d, heads = z["d"], z["heads"]
    router_scale = config["assumed"]["router_init"]["value"]["scale"]
    chips = z["routed"] // z["held"]

    @functools.partial(jax.jit, out_shardings=sharding)
    def make(key):
        keys = (jax.random.fold_in(key, i) for i in itertools.count())

        def matrix(*shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) \
                * (scale / fan_in ** 0.5)

        ones = lambda *shape: jnp.ones(shape, jnp.float32)

        def run(dense, n):
            layer = {
                "attn_norm": ones(n, d), "mlp_norm": ones(n, d),
                "w_qa": matrix(n, d, z["q_rank"], fan_in=d),
                "qa_norm": ones(n, z["q_rank"]),
                "w_qb": matrix(n, z["q_rank"],
                               heads * (z["nope"] + z["rope"]),
                               fan_in=z["q_rank"]),
                "w_kva": matrix(n, d, z["kv_rank"] + z["rope"], fan_in=d),
                "kva_norm": ones(n, z["kv_rank"]),
                "w_kvb": matrix(n, z["kv_rank"],
                                heads * (z["nope"] + z["v_dim"]),
                                fan_in=z["kv_rank"]),
                "wo": matrix(n, heads * z["v_dim"], d,
                             fan_in=heads * z["v_dim"]),
            }
            if dense:
                f = z["dense_width"]
                return dict(layer,
                            mlp_gate=matrix(n, d, f, fan_in=d),
                            mlp_up=matrix(n, d, f, fan_in=d),
                            mlp_down=matrix(n, f, d, fan_in=f))
            f, s, held = z["width"], z["shared_width"], z["held"]
            router = matrix(n, d, chips, held, fan_in=d, scale=router_scale)
            return dict(
                layer,
                router=(router - router.mean(axis=-1, keepdims=True)
                        ).reshape(n, d, z["routed"]),
                shared_gate=matrix(n, d, s, fan_in=d),
                shared_up=matrix(n, d, s, fan_in=d),
                shared_down=matrix(n, s, d, fan_in=s),
                w_gate=matrix(n, held, d, f, fan_in=d),
                w_up=matrix(n, held, d, f, fan_in=d),
                w_down=matrix(n, held, f, d, fan_in=f))

        return {"embed": jax.random.normal(next(keys), (z["vocab"], d),
                                           jnp.float32),
                "runs": [run(True, z["dense_layers"]),
                         run(False, z["layers"] - z["dense_layers"])],
                "final_norm": ones(d),
                "head": matrix(d, z["vocab"], fan_in=d),
                "mtp": {"enorm": ones(d), "hnorm": ones(d),
                        "w_eh": matrix(2 * d, d, fan_in=2 * d),
                        "block": run(False, 1), "mtp_norm": ones(d)}}

    return make(jax.random.PRNGKey(seed))


def seeded_batch(config: dict, seed: int, batch: int):
    """``(tokens [batch, S],)``: ids drawn evenly from the slice of the
    vocabulary held here, one document a sequence."""
    z = sizes(config)
    return (jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1),
        (batch, z["length"]), 0, z["vocab"], jnp.int32),)


# -- the program ---------------------------------------------------------------

def model_config(config: dict):
    from horovod_tpu.models.joyai_flash import JoyaiFlashConfig
    z, assumed = sizes(config), config["assumed"]
    return JoyaiFlashConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        num_hidden_layers=z["layers"],
        first_k_dense_replace=z["dense_layers"],
        intermediate_size=z["dense_width"],
        moe_intermediate_size=z["width"], n_routed_experts=z["routed"],
        num_experts_per_tok=z["top_k"],
        n_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=z["route_scale"],
        num_attention_heads=z["heads"], q_lora_rank=z["q_rank"],
        kv_lora_rank=z["kv_rank"], qk_nope_head_dim=z["nope"],
        qk_rope_head_dim=z["rope"], v_head_dim=z["v_dim"],
        rope_theta=z["theta"], rms_norm_eps=z["eps"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=z["mtp_weight"], experts_held=z["held"],
        first_expert=z["first"],
        dtype=jnp.dtype(config["compute_dtype"]),
        attention_tile=assumed["attention_tile"]["value"],
        loss_chunk=assumed["loss_chunk"]["value"])


class Program:
    """The system under test: ``step(*state, *batch) -> (*state, loss)``
    over the initialised ``hvd`` world, ``images_per_chip`` sequences a
    slot.  ``first`` is the :class:`FirstStep` of the first step this
    program ran, which the runner makes from the seeded state: pairs routed
    to the held experts and every position's choices, an expert layer and
    the MTP block last, and the gradient its optimizer took, on the host."""

    def __init__(self, config: dict, images_per_chip: int, seed: int):
        import horovod_tpu as hvd
        from horovod_tpu.models import joyai_flash
        self.config, self.seed = config, seed
        self.global_batch = images_per_chip * hvd.num_slots()
        self.batch = jax.device_put(
            seeded_batch(config, seed, self.global_batch),
            hvd.parallel.data_parallel_sharding())
        cfg = model_config(config)
        self.optimizer = opt = hvd.DistributedOptimizer(
            make_optimizer(config))

        def local_step(params, opt_state, tokens):
            (loss, (aux, *parts)), grads = jax.value_and_grad(
                lambda p: joyai_flash.loss_fn(p, tokens, cfg),
                has_aux=True)(params)
            loss, *parts = (hvd.allreduce(x, op=hvd.Average)
                            for x in (loss, *parts))
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, \
                jnp.stack(parts), aux.routed_here[None], aux.chosen[None]

        # check_vma: see jobs/sdar_moe.py (Pallas's interpreter off the TPU).
        self.compiled = hvd.shard_step(
            local_step,
            in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P(), P(), P(), P("hvd"), P("hvd")),
            donate_argnums=(0, 1),
            check_vma=jax.default_backend() == "tpu")
        self.first = None

    def step(self, params, opt_state, *batch):
        params, opt_state, loss, parts, routed, chosen = self.compiled(
            params, opt_state, *batch)
        if self.first is None:
            routed, chosen = np.asarray(routed), np.asarray(chosen)
            log(f"losses: program, first step L_main {float(parts[0]):.6f} "
                f"L_mtp {float(parts[1]):.6f}")
            # [slots, layers, ...]: a slot's sequences follow the one before.
            self.first = _first_steps[self.seed, self.global_batch] = \
                FirstStep(routed.sum(axis=0),
                          np.concatenate(list(chosen), axis=1),
                          first_gradients(self.config, opt_state))
        return params, opt_state, loss

    def fresh_state(self):
        """The seeded state, replicated over the mesh as the step returns
        it, made in place (a ``device_put`` of the finished state would
        hold it twice)."""
        import horovod_tpu as hvd
        replicated = hvd.parallel.replicated_sharding()
        params = seeded_params(self.config, self.seed, replicated)
        state = params, jax.jit(self.optimizer.init,
                                out_shardings=replicated)(params)
        log_memory("with the program's seeded state")
        return state

    def hlo_text(self, state) -> str:
        """The compiled step as text, for the scopes' names."""
        return self.compiled.lower(*state, *self.batch).compile().as_text()


# -- the plain reference -------------------------------------------------------

def rotary_pairs(x, theta):
    """``x [S, heads, rope]`` rotated by its positions, every pair ``(x[2i],
    x[2i + 1])`` as one complex number times ``exp(1j t theta ** (-2i /
    rope))``; written out in real parts (the TPU has no complex unit)."""
    seq, _, rope = x.shape
    inv_freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


@jax.checkpoint
def attend(q_nope, q_rope, k_nope, k_rope, v, mask):
    """Dense masked softmax attention of a few heads: ``q_nope``, ``k_nope``
    ``[S, n, nope]``, ``q_rope [S, n, rope]``, ``v [S, n, v]`` a head each,
    and the one rotary key ``k_rope [S, rope]`` that they share; the score
    is the sum of the two dot products."""
    scores = (jnp.einsum("qnd,knd->nqk", q_nope, k_nope)
              + jnp.einsum("qnd,kd->nqk", q_rope, k_rope)) \
        / np.sqrt(q_nope.shape[-1] + q_rope.shape[-1])
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v)


def reference_layer(z: dict, dense: bool, mask, p: dict, x, imposed=None):
    """One layer on one sequence ``x [S, hidden]`` under the boolean ``mask
    [S, S]``; returns ``(y, chosen [S, top_k])``, the layer's own choices,
    empty for a dense layer.  ``imposed [S, top_k]``: the experts that are
    weighed and applied in the chosen ones' place (a judged first step's:
    ``reference_losses``); every score and weight is still this layer's
    own."""
    seq = x.shape[0]
    heads, nope, rope = z["heads"], z["nope"], z["rope"]
    a = rms_norm(x, p["attn_norm"], z["eps"])
    c_q = rms_norm(a @ p["w_qa"], p["qa_norm"], z["eps"])
    q = (c_q @ p["w_qb"]).reshape(seq, heads, nope + rope)
    kva = a @ p["w_kva"]
    c_kv = rms_norm(kva[:, :z["kv_rank"]], p["kva_norm"], z["eps"])
    kv = (c_kv @ p["w_kvb"]).reshape(seq, heads, nope + z["v_dim"])
    q_rope = rotary_pairs(q[..., nope:], z["theta"])
    k_rope = rotary_pairs(kva[:, None, z["kv_rank"]:], z["theta"])[:, 0]
    # Two heads at a time, one after the other (jobs/sdar_moe.py).
    n = min(2, heads)
    pieces = lambda t: t.reshape(seq, heads // n, n, -1).transpose(
        1, 0, 2, 3)
    attended = jax.lax.map(
        lambda piece: attend(piece[0], piece[1], piece[2], k_rope, piece[3],
                             mask),
        (pieces(q[..., :nope]), pieces(q_rope), pieces(kv[..., :nope]),
         pieces(kv[..., nope:])))
    h = x + attended.transpose(1, 0, 2, 3).reshape(seq, -1) @ p["wo"]

    m = rms_norm(h, p["mlp_norm"], z["eps"])
    if dense:
        return h + gated(m, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), \
            jnp.zeros((seq, 0), jnp.int32)
    scores = jax.nn.sigmoid(m @ p["router"])
    bias = jax.lax.stop_gradient(p["e_score_correction_bias"]) \
        if "e_score_correction_bias" in p else 0.0
    _, chosen = jax.lax.top_k(scores + bias, z["top_k"])
    used = chosen if imposed is None else imposed
    weights = jnp.take_along_axis(scores, used, axis=-1)
    weights = z["route_scale"] * weights / (
        jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)

    def add_expert(acc, held):
        e, w_gate, w_up, w_down = held
        gate = jnp.sum(jnp.where(used == z["first"] + e, weights, 0.0),
                       axis=-1, keepdims=True)
        return acc + gate * expert(m, w_gate, w_up, w_down), None

    # The shared expert once, then every held expert on every position.
    f = gated(m, p["shared_gate"], p["shared_up"], p["shared_down"])
    f, _ = jax.lax.scan(add_expert, f, (
        jnp.arange(z["held"]), p["w_gate"], p["w_up"], p["w_down"]))
    return h + f, chosen


def reference_head(z: dict, norm, head, x, tokens, ahead: int):
    """``sum_{i < S - ahead} -log softmax(W_head RMSNorm(x_i))[t_{i +
    ahead}]``."""
    logits = rms_norm(x[:-ahead], norm, z["eps"]) @ head
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[ahead:, None], axis=-1)[:, 0])


def mtp_input(z: dict, embed, mtp: dict, x, tokens, dummy_row=True):
    """``u``: row ``i`` is ``[RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(x_i)]
    W_eh``.  With the dummy row all ``S`` rows, the last fed ``E[t_0]``;
    without, the ``S - 1`` rows of the published module."""
    ahead = jnp.roll(tokens, -1) if dummy_row else tokens[1:]
    return jnp.concatenate(
        [rms_norm(embed[ahead], mtp["enorm"], z["eps"]),
         rms_norm(x[:len(ahead)], mtp["hnorm"], z["eps"])],
        axis=-1) @ mtp["w_eh"]


def unstacked(params: dict) -> dict:
    """The tree with ``layers``, a flat list of the layers' own dicts in
    order, in place of ``runs``, and the MTP block as its one layer's own
    dict; a tree that has ``layers`` as it is."""
    if "layers" in params:
        return params
    per_layer = lambda run: [
        {name: a[i] for name, a in run.items()}
        for i in range(next(iter(run.values())).shape[0])]
    layers = [layer for run in params["runs"] for layer in per_layer(run)]
    mtp = dict(params["mtp"], block=per_layer(params["mtp"]["block"])[0])
    return dict({k: v for k, v in params.items() if k != "runs"},
                layers=layers, mtp=mtp)


@highest
def reference_loss(config: dict, params: dict, tokens, dummy_row=True):
    """``(L_main, L_mtp)`` of a batch, whole: for ``jax.grad`` at small
    sizes."""
    z = sizes(config)
    batch, length = tokens.shape
    mask = jnp.asarray(np.tril(np.ones((length, length), bool)))
    params = unstacked(params)
    mtp = params["mtp"]
    main = ahead = 0.0
    for b in range(batch):
        x = params["embed"][tokens[b]]
        for i, p in enumerate(params["layers"]):
            x, _ = reference_layer(z, i < z["dense_layers"], mask, p, x)
        main += reference_head(z, params["final_norm"], params["head"], x,
                               tokens[b], 1)
        u = mtp_input(z, params["embed"], mtp, x, tokens[b], dummy_row)
        y, _ = reference_layer(z, False, mask[:len(u), :len(u)],
                               mtp["block"], u)
        # Rows i < S - 2 on token i + 2, with or without the dummy row.
        ahead += reference_head(z, mtp["mtp_norm"], params["head"],
                                y[:length - 1], tokens[b][1:], 1)
    return main / (batch * (length - 1)), ahead / (batch * (length - 2))


class ReferenceSteps:
    """The reference's loss and gradients a sequence and a layer at a
    time: a dense and an expert layer are each compiled once and run for
    every such layer (the MTP block among them) and every sequence, and a
    layer's gradient comes from ``jax.vjp`` of that layer at its saved
    input.  The tree is ``unstacked``'s."""

    def __init__(self, config: dict, batch: int):
        z = self.z = sizes(config)
        length = z["length"]
        self.dense = [i < z["dense_layers"] for i in range(z["layers"])]
        # The mask is an argument: closed over, its 64 MB would be compiled
        # into every executable (jobs/afmoe.py).
        self.mask = jnp.asarray(np.tril(np.ones((length, length), bool)))
        self.forward, self.backward = {}, {}
        for dense in set(self.dense) | {False}:
            layer = functools.partial(highest(reference_layer), z, dense)
            self.forward[dense] = jax.jit(layer)

            def backward(mask, p, x, dy, acc, imposed, layer=layer):
                _, vjp, _ = jax.vjp(
                    lambda p, x: layer(mask, p, x, imposed), p, x,
                    has_aux=True)
                dp, dx = vjp(dy)
                return jax.tree_util.tree_map(jnp.add, acc, dp), dx

            self.backward[dense] = jax.jit(backward, donate_argnums=(4,))

        scales = {1: 1.0 / (batch * (length - 1)),
                  2: z["mtp_weight"] / (batch * (length - 2))}

        def head_loss(ahead, norm, head, x, tokens):
            return scales[ahead] * highest(reference_head)(
                z, norm, head, x, tokens, ahead)

        def head_step(ahead, norm, head, x, tokens, acc):
            loss, grads = jax.value_and_grad(
                functools.partial(head_loss, ahead), argnums=(0, 1, 2))(
                    norm, head, x, tokens)
            return loss, jax.tree_util.tree_map(
                jnp.add, acc, grads[:2]), grads[2]

        self.head_loss = jax.jit(head_loss, static_argnums=(0,))
        self.head = jax.jit(head_step, static_argnums=(0,),
                            donate_argnums=(5,))
        self.embed = jax.jit(lambda embed, tokens: embed[tokens])
        self.embed_grad = jax.jit(
            lambda acc, tokens, dx: acc.at[tokens].add(dx),
            donate_argnums=(0,))
        join = lambda embed, mtp, x, tokens: highest(mtp_input)(
            z, embed, mtp, x, tokens)
        self.join = jax.jit(join)

        def join_backward(embed, mtp, x, tokens, du, d_embed, acc):
            _, vjp = jax.vjp(lambda e, m, x: join(e, m, x, tokens), embed,
                             mtp, x)
            de, dm, dx = vjp(du)
            return d_embed + de, jax.tree_util.tree_map(jnp.add, acc, dm), dx

        self.join_backward = jax.jit(join_backward, donate_argnums=(5, 6))

    @staticmethod
    def joined(mtp: dict) -> dict:
        """The leaves of the MTP module that ``mtp_input`` reads."""
        return {name: mtp[name] for name in ("enorm", "hnorm", "w_eh")}

    def through(self, params: dict, tokens, imposed=()):
        """``(inputs of every layer and of the head, the MTP block's input
        and output, choices of every expert layer and of the block)`` of
        one sequence; ``imposed``: a ``[S, top_k]`` an expert layer and
        the block, in order, or none."""
        inputs, picks = [self.embed(params["embed"], tokens)], []
        imposed = iter(imposed)
        for dense, p in zip(self.dense, params["layers"]):
            y, pick = self.forward[dense](
                self.mask, p, inputs[-1],
                None if dense else next(imposed, None))
            inputs.append(y)
            if not dense:
                picks.append(np.asarray(pick))
        u = self.join(params["embed"], self.joined(params["mtp"]),
                      inputs[-1], tokens)
        y, pick = self.forward[False](self.mask, params["mtp"]["block"], u,
                                      next(imposed, None))
        return inputs, u, y, picks + [np.asarray(pick)]

    def loss(self, params: dict, tokens):
        """``(L_main, lambda x L_mtp)`` by the forward pass alone."""
        main = ahead = 0.0
        for sequence in tokens:
            inputs, _, y, _ = self.through(params, sequence)
            main += float(self.head_loss(
                1, params["final_norm"], params["head"], inputs[-1],
                sequence))
            ahead += float(self.head_loss(
                2, params["mtp"]["mtp_norm"], params["head"], y, sequence))
        return main, ahead

    def loss_and_grads(self, params: dict, tokens, imposed=None):
        """``((L_main, lambda x L_mtp), grads, chosen [expert layers + 1,
        batch * S, top_k])``, the reference's own choices, the MTP block's
        last; ``imposed``, of ``chosen``'s shape: the experts every
        position goes through in their place."""
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        d_embed, d_layers = zeros["embed"], zeros["layers"]
        d_norm, d_head = zeros["final_norm"], zeros["head"]
        d_mtp_norm, d_block = zeros["mtp"]["mtp_norm"], zeros["mtp"]["block"]
        d_join = self.joined(zeros["mtp"])
        del zeros
        main = ahead = 0.0
        chosen, length = [], tokens.shape[1]
        mtp = params["mtp"]
        for b, sequence in enumerate(tokens):
            mine = [] if imposed is None else [
                jnp.asarray(layer[b * length:(b + 1) * length], jnp.int32)
                for layer in imposed]
            inputs, u, y, picks = self.through(params, sequence, mine)
            # The MTP module first: its head, its block, its join, whose
            # cotangent on x_L adds to the main head's.
            loss, (d_mtp_norm, d_head), dy = self.head(
                2, mtp["mtp_norm"], params["head"], y, sequence,
                (d_mtp_norm, d_head))
            ahead += float(loss)
            d_block, du = self.backward[False](
                self.mask, mtp["block"], u, dy, d_block,
                mine.pop() if mine else None)
            d_embed, d_join, dx_mtp = self.join_backward(
                params["embed"], self.joined(mtp), inputs[-1], sequence, du,
                d_embed, d_join)
            loss, (d_norm, d_head), dx = self.head(
                1, params["final_norm"], params["head"], inputs.pop(),
                sequence, (d_norm, d_head))
            main += float(loss)
            dx = dx + dx_mtp
            del u, y, dy, du, dx_mtp
            for i, dense in reversed(list(enumerate(self.dense))):
                d_layers[i], dx = self.backward[dense](
                    self.mask, params["layers"][i], inputs.pop(), dx,
                    d_layers[i], None if dense or not mine else mine.pop())
            d_embed = self.embed_grad(d_embed, sequence, dx)
            chosen.append(np.stack(picks))
        return (main, ahead), {
            "embed": d_embed, "layers": d_layers, "final_norm": d_norm,
            "head": d_head,
            "mtp": dict(d_join, block=d_block, mtp_norm=d_mtp_norm)}, \
            np.concatenate(chosen, axis=1)


def gradient_errors(got: dict, want: dict) -> dict:
    """``|got - want| / |want|`` in the 2-norm for every kind of leaf of
    the parameter tree: a layer's leaf over all the stack's layers that
    have it, the MTP module's own leaves under their names and its block's
    as ``mtp.<leaf>`` (the module's gradients are ``lambda`` times the
    size: among the stack's they would not be seen); either tree may hold
    its layers in ``runs`` or as ``layers``.  A layer at a time: a tree may
    live on the host."""
    @jax.jit
    def squares(a, b):
        return jnp.sum((a - b) ** 2), jnp.sum(b ** 2)

    sums = {}

    def add(name, a, b):
        off, size = squares(jnp.asarray(a), jnp.asarray(b))
        sums[name] = np.add(sums.get(name, (0.0, 0.0)),
                            (float(off), float(size)))

    got, want = unstacked(got), unstacked(want)
    for name in want:
        if name not in ("layers", "mtp"):
            add(name, got[name], want[name])
    for mine, theirs in zip(got["layers"], want["layers"]):
        for name in theirs:
            add(name, mine[name], theirs[name])
    for name in want["mtp"]:
        if name != "block":
            add(name, got["mtp"][name], want["mtp"][name])
    for name in want["mtp"]["block"]:
        add("mtp." + name, got["mtp"]["block"][name],
            want["mtp"]["block"][name])
    return {name: float(np.sqrt(off / size))
            for name, (off, size) in sorted(sums.items())}


def reference_losses(config: dict, seed: int, global_batch: int,
                     steps: int):
    """Losses (``L_main + lambda x L_mtp``) of ``steps`` (at most 2) plain
    AdamW steps from the seeded state on the seeded batch, on one device:
    loss and gradients of the seeded state, AdamW's first update, the loss
    of the updated state (a forward pass alone: nobody reads a second
    gradient).

    Where a program has left its first step for this seed and batch
    (``_first_steps``), the reference judges it as ``jobs/afmoe.py`` does:
    it runs every position through THAT step's choices of experts, weighed
    by its own scores; the choices themselves are held to the reference's
    own by ``correct.choices_limit``; every gradient leaf of the first step
    is held to ``correct.gradient_limits``; and a leaf or the choices
    outside turn the first loss into ``inf``, which the runner's one
    comparison fails."""
    if steps > 2:
        raise ValueError("the reference keeps no optimizer state past "
                         "AdamW's first step: correct.steps is 1 or 2")
    log_memory("before the reference")
    first = _first_steps.pop((seed, global_batch), None)
    tokens, = seeded_batch(config, seed, global_batch)
    params = unstacked(seeded_params(config, seed))
    reference = ReferenceSteps(config, global_batch)
    parts, grads, chosen = reference.loss_and_grads(
        params, tokens, None if first is None else first.chosen)
    weight = config["assumed"]["mtp_loss_weight"]["value"]
    log(f"losses: reference, first step L_main {parts[0]:.6f} L_mtp "
        f"{parts[1] / weight if weight else 0.0:.6f}")
    losses = [sum(parts)]
    differ = log_routing(config, chosen, first)
    if first is not None:
        outside = leaves_outside(
            config, gradient_errors(first.gradients, grads))
        if outside or not differ <= config["correct"]["choices_limit"]:
            losses[0] = math.inf
    log_memory("after the reference's first step")
    if steps == 2:
        params = update_by_leaf(make_optimizer(config), params, grads)
        losses.append(sum(reference.loss(params, tokens)))
    del params, grads, reference
    gc.collect()
    log_memory("after the reference")
    return losses


def log_routing(config: dict, reference_chosen, first=None):
    """Logs the pairs routed to the held experts, by the reference and by
    the first step judged; returns the share of that step's choices that
    are not the reference's (``None`` without one)."""
    z = sizes(config)
    here = ((reference_chosen >= z["first"])
            & (reference_chosen < z["first"] + z["held"])).sum(axis=(1, 2))
    log(f"routing: reference, pairs routed to the {z['held']} held experts "
        f"by expert layer, the MTP block last, {here.tolist()} of "
        f"{reference_chosen[0].size} each, "
        f"{reference_chosen[0].size * z['held'] // z['routed']} even")
    if first is None:
        return None
    by_layer = [round(100 * choices_that_differ(mine, theirs), 3)
                for mine, theirs in zip(first.chosen, reference_chosen)]
    differ = choices_that_differ(first.chosen, reference_chosen)
    log(f"routing: program, pairs routed to the held experts by expert "
        f"layer {first.routed.tolist()}; {100 * differ:.3f} % of its "
        f"choices are not the reference's (limit "
        f"{100 * config['correct']['choices_limit']:g}), by expert layer "
        f"{by_layer}; the gradients below under its choices")
    return differ
