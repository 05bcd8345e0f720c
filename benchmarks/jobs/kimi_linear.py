"""The Kimi-Linear training job (causal next-token loss) and its plain
reference.

The program's side is the normal path: ``horovod_tpu/models/kimi_linear.py``
(Kimi Delta Attention through the chunked scan kernels of
``parallel/kda.py``, latent attention without positions through the flash
kernels, the dropless expert layer with a sigmoid router and a shared expert
beside it) under ``jax.value_and_grad``, AdamW through
``hvd.DistributedOptimizer`` inside ``hvd.shard_step``, state donated, one
batch that lives on the device, as ``jobs/afmoe.py``, whose log lines,
optimizer, judgement of gradient leaves, gated unit and update by leaf this
job takes as they are.

The reference (everything from ``recurrence`` down) is the published model
written out in ``jax.numpy``, float32, every product at
``jax.default_matmul_precision("highest")``; it imports nothing from
``horovod_tpu.models`` or ``horovod_tpu.parallel`` and shares only the
layout of the parameter tree (``seeded_params``) and the batch.

The equations (``config``; † marks what the published ``config.json`` does
not carry and ``assumed`` states).  No bias anywhere, RMSNorm eps
``rms_norm_eps``.  ``x0 = E[tokens]``.  Every layer: ``a = RMSNorm_in(x)``.
A layer of ``linear_attn_config.kda_layers`` (counted from 1), ``H =
num_heads`` heads of ``d = head_dim``: ``q~, k~, v~ = SiLU†(conv(a W_q)),
SiLU(conv(a W_k)), SiLU(conv(a W_v))`` with ``conv`` causal, depthwise, of
width ``short_conv_kernel_size``, from a zero history; ``q, k`` = ``q~, k~``
over their 2-norm a head (``sqrt(|.|^2 + 1e-6†)``), ``q`` times ``d^-1/2``;
``g = -exp(A_log_h) softplus((a W_fa) W_fb + dt_bias)``† the log of the
decay, a channel; ``beta = sigmoid(a W_b)``† a head; from ``S_0 = 0`` (keys
by values) ``S'_t = Diag(exp g_t) S_{t-1}``, ``S_t = S'_t + beta_t k_t (v_t
- k_t^T S'_t)^T``, ``o_t = S_t^T q_t``; ``y = RMSNorm_o(o) sigmoid((a W_ga)
W_gb)``†; ``h = x + y W_o``.  A layer of ``full_attn_layers``: latent
attention with ``q_lora_rank`` null (``q = a W_q``, a head
``qk_nope_head_dim + qk_rope_head_dim``), ``a W_kva`` split into ``c_kv``
(``kv_lora_rank``) and ONE head ``k_pe`` of ``qk_rope_head_dim`` shared by
all query heads and, ``mla_use_nope``, NOT rotated; ``RMSNorm_kva(c_kv)
W_kvb`` gives a head its ``k_nope`` and its ``v`` (``v_head_dim``); ``o =
softmax_{j <= i}(q_i . [k_nope ; k_pe]_j / sqrt(192)) v``; ``h = x + o
W_o``.  ``m = RMSNorm_post(h)``.  The first ``first_k_dense_replace``
layers: ``f = (silu(m W1) * (m W3)) W2`` of width ``intermediate_size``.
The others: ``s = sigmoid(m Wr)`` over all published experts
(``moe_router_activation_func``); the ``num_experts_per_token`` largest of
``s + e_score_correction_bias`` (``num_expert_group`` = ``topk_group`` = 1:
no groups; the bias† zero here, no gradient); ``w = routed_scaling_factor x
s[chosen] / (sum s[chosen] + 1e-20†)`` (``moe_renormalize``); ``f =
Shared(m) + sum_e w_e Expert_e(m)``, both gated SiLU of width
``moe_intermediate_size``.  ``x' = h + f``.  The loss: the mean over the ``S
- 1`` predictions of a sequence and over the sequences of ``-log
softmax(RMSNorm_f(x_L) W_head)_i[t_{i+1}]``, cross-entropy alone.

Departures from the published description: the sum over the chosen experts
runs over those HELD HERE (``num_experts`` of them from
``deployment.first_expert``; what the absent ones would add is left out,
here and in the program alike, and the shared expert is whole); the
vocabulary is the slice held here (ids drawn from it, logits and loss over
it).

KDA is the recurrence itself, a token at a time under ``lax.scan``, no chunk
algebra; its backward pass keeps the states of ``SEGMENT`` tokens at a time
(``jax.checkpoint`` over segments of the scan: all 8,192 states of 32 x 128
x 128 float32 would be 17 GB).  Every held expert is applied to every
position and weighted by the routing (zero where not chosen).  So that it
fits one chip at the published widths the reference runs a sequence at a
time and a layer at a time (each layer's gradient by ``jax.vjp`` of that
layer from its saved input: ``ReferenceSteps``), latent attention two heads
at a time and the experts one after the other; ``reference_loss`` is the
same functions composed whole, and the tests hold the two to each other.
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
rms_norm, expert, gated, update_by_leaf, unstacked, gradient_errors = (
    afmoe.rms_norm, afmoe.expert, afmoe.gated, afmoe.update_by_leaf,
    afmoe.unstacked, afmoe.gradient_errors)
reference_head = afmoe.reference_head   # reads ``z["eps"]`` alone
attend = mf.load_module("jobs", "joyai_flash").attend

KDA, MLA = "kda", "mla"
#: Tokens of the recurrence whose states its backward pass keeps at a time.
SEGMENT = 256
#: First steps by ``(seed, global batch)``: where ``Program.step`` and
#: ``reference_losses`` meet, as in ``jobs/sdar_moe.py``.
_first_steps = {}
BUILT = dict(moe_router_activation_func="sigmoid", num_expert_group=1,
             topk_group=1, moe_renormalize=True, moe_layer_freq=1,
             mla_use_nope=True, q_lora_rank=None, rope_scaling=None,
             hidden_act="silu", tie_word_embeddings=False,
             num_nextn_predict_layers=0)


# -- what program and reference share: the tree's layout and the batch -------

def sizes(config: dict) -> dict:
    other = {k: config[k] for k, v in BUILT.items() if config[k] != v}
    if other:
        raise ValueError(f"{other}: only {BUILT} is built")
    linear, assumed = config["linear_attn_config"], config["assumed"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["num_hidden_layers"],
        kda_layers=tuple(linear["kda_layers"]),
        full_layers=tuple(linear["full_attn_layers"]),
        kda_heads=linear["num_heads"], kda_dim=linear["head_dim"],
        conv=linear["short_conv_kernel_size"],
        gate_rank=assumed["gate_rank"]["value"],
        l2_eps=assumed["l2_norm_eps"]["value"],
        dense_layers=config["first_k_dense_replace"],
        heads=config["num_attention_heads"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], dense_width=config["intermediate_size"],
        width=config["moe_intermediate_size"],
        shared_width=(config["moe_intermediate_size"]
                      * config["num_shared_experts"]),
        routed=config["published"]["num_experts"],
        held=config["num_experts"],
        first=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_token"], eps=config["rms_norm_eps"],
        route_scale=config["routed_scaling_factor"],
        length=assumed["sequence_length"]["value"])


def kinds(z: dict) -> list:
    """``(mixer, dense?)`` of every layer, in published order."""
    return [(KDA if i + 1 in z["kda_layers"] else MLA,
             i < z["dense_layers"]) for i in range(z["layers"])]


def runs(z: dict) -> list:
    """``[(mixer, dense?, layers)]``: stretches of consecutive layers of
    one kind, which the tree stacks (``models/kimi_linear.py``, the
    tree)."""
    return [(*kind, len(list(run)))
            for kind, run in itertools.groupby(kinds(z))]


def seeded_params(config: dict, seed: int, sharding=None) -> dict:
    """The parameter tree from the seed, float32, a run's layers stacked on
    a leading axis (``assumed.init``): normal(0, 1 / sqrt(fan_in))
    matrices (a convolution's fan-in is its width), unit norms, an embedding
    of normal(0, 1) rows, ``A_log`` the log of uniform(1, 16) a head,
    ``dt_bias`` the inverse softplus of log-uniform(1e-3, 0.1) a channel
    (``assumed.decay_init``), router columns of normal(0,
    ``router_init.scale`` / sqrt(hidden)) with the mean of every chip's
    block of columns taken off (``assumed.router_init``, as
    ``jobs/afmoe.py`` has it and for its reason); no
    ``e_score_correction_bias`` (zero, frozen); made where ``sharding``
    says."""
    z = sizes(config)
    d, heads = z["d"], z["heads"]
    kda_heads, kda_dim, rank = z["kda_heads"], z["kda_dim"], z["gate_rank"]
    wide = kda_heads * kda_dim
    router_scale = config["assumed"]["router_init"]["value"]["scale"]
    decay = config["assumed"]["decay_init"]["value"]
    chips = z["routed"] // z["held"]

    @functools.partial(jax.jit, out_shardings=sharding)
    def make(key):
        keys = (jax.random.fold_in(key, i) for i in itertools.count())

        def matrix(*shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) \
                * (scale / fan_in ** 0.5)

        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        uniform = lambda shape, low, high: jax.random.uniform(
            next(keys), shape, jnp.float32, low, high)

        def run(mixer, dense, n):
            layer = {"attn_norm": ones(n, d), "mlp_norm": ones(n, d)}
            if mixer == KDA:
                dt = jnp.exp(uniform((n, wide), math.log(decay["dt"][0]),
                                     math.log(decay["dt"][1])))
                layer.update(
                    w_q=matrix(n, d, wide, fan_in=d),
                    w_k=matrix(n, d, wide, fan_in=d),
                    w_v=matrix(n, d, wide, fan_in=d),
                    conv_q=matrix(n, wide, z["conv"], fan_in=z["conv"]),
                    conv_k=matrix(n, wide, z["conv"], fan_in=z["conv"]),
                    conv_v=matrix(n, wide, z["conv"], fan_in=z["conv"]),
                    A_log=jnp.log(uniform((n, kda_heads), *decay["A"])),
                    dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                    w_fa=matrix(n, d, rank, fan_in=d),
                    w_fb=matrix(n, rank, wide, fan_in=rank),
                    w_b=matrix(n, d, kda_heads, fan_in=d),
                    w_ga=matrix(n, d, rank, fan_in=d),
                    w_gb=matrix(n, rank, wide, fan_in=rank),
                    o_norm=ones(n, kda_dim),
                    w_o=matrix(n, wide, d, fan_in=wide))
            else:
                layer.update(
                    mla_wq=matrix(n, d, heads * (z["nope"] + z["rope"]),
                                  fan_in=d),
                    w_kva=matrix(n, d, z["kv_rank"] + z["rope"], fan_in=d),
                    kva_norm=ones(n, z["kv_rank"]),
                    w_kvb=matrix(n, z["kv_rank"],
                                 heads * (z["nope"] + z["v_dim"]),
                                 fan_in=z["kv_rank"]),
                    wo=matrix(n, heads * z["v_dim"], d,
                              fan_in=heads * z["v_dim"]))
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
                "runs": [run(*kind) for kind in runs(z)],
                "final_norm": ones(d),
                "head": matrix(d, z["vocab"], fan_in=d)}

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
    from horovod_tpu.models.kimi_linear import KimiLinearConfig
    z, assumed = sizes(config), config["assumed"]
    return KimiLinearConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        num_hidden_layers=z["layers"], kda_layers=z["kda_layers"],
        full_attn_layers=z["full_layers"], kda_num_heads=z["kda_heads"],
        kda_head_dim=z["kda_dim"], short_conv_kernel_size=z["conv"],
        first_k_dense_replace=z["dense_layers"],
        intermediate_size=z["dense_width"],
        moe_intermediate_size=z["width"], num_experts=z["routed"],
        num_experts_per_token=z["top_k"],
        num_shared_experts=config["num_shared_experts"],
        routed_scaling_factor=z["route_scale"],
        num_attention_heads=z["heads"], kv_lora_rank=z["kv_rank"],
        qk_nope_head_dim=z["nope"], qk_rope_head_dim=z["rope"],
        v_head_dim=z["v_dim"], rms_norm_eps=z["eps"], l2_norm_eps=z["l2_eps"],
        experts_held=z["held"], first_expert=z["first"],
        dtype=jnp.dtype(config["compute_dtype"]),
        attention_tile=assumed["attention_tile"]["value"],
        kda_chunk=assumed["kda_chunk"]["value"],
        loss_chunk=assumed["loss_chunk"]["value"])


class Program:
    """The system under test: ``step(*state, *batch) -> (*state, loss)``
    over the initialised ``hvd`` world, ``images_per_chip`` sequences a
    slot.  ``first`` is the :class:`FirstStep` of the first step this
    program ran, which the runner makes from the seeded state: pairs routed
    to the held experts and every position's choices, an expert layer, and
    the gradient its optimizer took, on the host."""

    def __init__(self, config: dict, images_per_chip: int, seed: int):
        import horovod_tpu as hvd
        from horovod_tpu.models import kimi_linear
        self.config, self.seed = config, seed
        self.global_batch = images_per_chip * hvd.num_slots()
        self.batch = jax.device_put(
            seeded_batch(config, seed, self.global_batch),
            hvd.parallel.data_parallel_sharding())
        cfg = model_config(config)
        self.optimizer = opt = hvd.DistributedOptimizer(
            make_optimizer(config))

        def local_step(params, opt_state, tokens):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: kimi_linear.loss_fn(p, tokens, cfg),
                has_aux=True)(params)
            loss = hvd.allreduce(loss, op=hvd.Average)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, \
                aux.routed_here[None], aux.chosen[None]

        # check_vma: see jobs/sdar_moe.py (Pallas's interpreter off the TPU).
        self.compiled = hvd.shard_step(
            local_step,
            in_specs=(P(), P(), P("hvd")),
            out_specs=(P(), P(), P(), P("hvd"), P("hvd")),
            donate_argnums=(0, 1),
            check_vma=jax.default_backend() == "tpu")
        self.first = None

    def step(self, params, opt_state, *batch):
        params, opt_state, loss, routed, chosen = self.compiled(
            params, opt_state, *batch)
        if self.first is None:
            routed, chosen = np.asarray(routed), np.asarray(chosen)
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

def recurrence(q, k, v, g, beta):
    """Kimi Delta Attention's state a head, a token at a time: ``q, k, v, g
    [S, H, d]``, ``beta [S, H]`` -> ``o [S, H, d]``.  ``S_0 = 0`` (keys by
    values); ``S' = Diag(exp g_t) S``; ``S = S' + beta_t k_t (v_t - k_t^T
    S')^T``; ``o_t = S^T q_t``.  The backward pass keeps the states of
    ``SEGMENT`` tokens at a time."""
    seq, heads, d = q.shape
    segment = math.gcd(seq, SEGMENT)

    def token(state, row):
        q_t, k_t, v_t, g_t, beta_t = row
        state = jnp.exp(g_t)[:, :, None] * state
        error = v_t - jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + beta_t[:, None, None] * k_t[:, :, None] \
            * error[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    pieces = lambda x: x.reshape(seq // segment, segment, *x.shape[1:])
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, rows: jax.lax.scan(token, state, rows)),
        jnp.zeros((heads, d, d), jnp.float32),
        tuple(pieces(x) for x in (q, k, v, g, beta)))
    return o.reshape(seq, heads, d)


def short_conv(x, weight):
    """``SiLU(y)``, ``y_t = sum_j weight[:, j] x_{t - (width - 1) + j}``
    with ``x`` zero before the sequence: ``x [S, P]``, ``weight [P,
    width]``."""
    seq, width = x.shape[0], weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
    return jax.nn.silu(sum(padded[j:j + seq] * weight[:, j]
                           for j in range(width)))


def kda_mixer(z: dict, p: dict, x):
    """``x + y W_o`` of one sequence through Kimi Delta Attention."""
    seq = x.shape[0]
    heads, d = z["kda_heads"], z["kda_dim"]
    by_head = lambda t: t.reshape(seq, heads, d)
    unit = lambda t: t / jnp.sqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + z["l2_eps"])
    a = rms_norm(x, p["attn_norm"], z["eps"])
    q = unit(by_head(short_conv(a @ p["w_q"], p["conv_q"]))) * d ** -0.5
    k = unit(by_head(short_conv(a @ p["w_k"], p["conv_k"])))
    v = by_head(short_conv(a @ p["w_v"], p["conv_v"]))
    g = -jnp.exp(p["A_log"])[None, :, None] * by_head(
        jax.nn.softplus((a @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(a @ p["w_b"])
    o = recurrence(q, k, v, g, beta)
    y = rms_norm(o, p["o_norm"], z["eps"]).reshape(seq, -1) \
        * jax.nn.sigmoid((a @ p["w_ga"]) @ p["w_gb"])
    return x + y @ p["w_o"]


def mla_mixer(z: dict, p: dict, x, mask):
    """``x + o W_o`` of one sequence through latent attention without
    positions, under the boolean ``mask [S, S]``."""
    seq = x.shape[0]
    heads, nope = z["heads"], z["nope"]
    a = rms_norm(x, p["attn_norm"], z["eps"])
    q = (a @ p["mla_wq"]).reshape(seq, heads, nope + z["rope"])
    kva = a @ p["w_kva"]
    c_kv = rms_norm(kva[:, :z["kv_rank"]], p["kva_norm"], z["eps"])
    kv = (c_kv @ p["w_kvb"]).reshape(seq, heads, nope + z["v_dim"])
    # Two heads at a time, one after the other (jobs/sdar_moe.py).
    n = min(2, heads)
    pieces = lambda t: t.reshape(seq, heads // n, n, -1).transpose(
        1, 0, 2, 3)
    attended = jax.lax.map(
        lambda piece: attend(piece[0], piece[1], piece[2],
                             kva[:, z["kv_rank"]:], piece[3], mask),
        (pieces(q[..., :nope]), pieces(q[..., nope:]),
         pieces(kv[..., :nope]), pieces(kv[..., nope:])))
    return x + attended.transpose(1, 0, 2, 3).reshape(seq, -1) @ p["wo"]


def reference_layer(z: dict, mixer: str, dense: bool, mask, p: dict, x,
                    imposed=None):
    """One layer on one sequence ``x [S, hidden]`` (``mask``: the latent
    layer's boolean ``[S, S]``); returns ``(y, chosen [S, top_k])``, the
    layer's own choices, empty for a dense layer.  ``imposed [S, top_k]``:
    the experts that are weighed and applied in the chosen ones' place (a
    judged first step's: ``reference_losses``); every score and weight is
    still this layer's own."""
    seq = x.shape[0]
    h = kda_mixer(z, p, x) if mixer == KDA else mla_mixer(z, p, x, mask)
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


def causal(length: int):
    return jnp.asarray(np.tril(np.ones((length, length), bool)))


@highest
def reference_loss(config: dict, params: dict, tokens):
    """The loss of a batch, whole: for ``jax.grad`` at small sizes."""
    z = sizes(config)
    batch, length = tokens.shape
    mask = causal(length)
    params = unstacked(params)
    total = 0.0
    for b in range(batch):
        x = params["embed"][tokens[b]]
        for (mixer, dense), p in zip(kinds(z), params["layers"]):
            x, _ = reference_layer(z, mixer, dense, mask, p, x)
        total += reference_head(z, params["final_norm"], params["head"], x,
                                tokens[b])
    return total / (batch * (length - 1))


class ReferenceSteps:
    """The reference's loss and gradients a sequence and a layer at a
    time: each kind of layer (mixer, second half) is compiled once and run
    for every such layer and every sequence, and a layer's gradient comes
    from ``jax.vjp`` of that layer at its saved input.  The tree is
    ``unstacked``'s."""

    def __init__(self, config: dict, batch: int):
        z = self.z = sizes(config)
        self.kinds = kinds(z)
        self.scale = 1.0 / (batch * (z["length"] - 1))
        # The mask is an argument: closed over, its 64 MB would be compiled
        # into every executable (jobs/afmoe.py).
        self.mask = causal(z["length"])
        self.forward, self.backward = {}, {}
        for kind in set(self.kinds):
            layer = functools.partial(highest(reference_layer), z, *kind)
            self.forward[kind] = jax.jit(layer)

            def backward(mask, p, x, dy, acc, imposed, layer=layer):
                _, vjp, _ = jax.vjp(
                    lambda p, x: layer(mask, p, x, imposed), p, x,
                    has_aux=True)
                dp, dx = vjp(dy)
                return jax.tree_util.tree_map(jnp.add, acc, dp), dx

            self.backward[kind] = jax.jit(backward, donate_argnums=(4,))

        head_loss = lambda f, h, x, tokens: self.scale * highest(
            reference_head)(z, f, h, x, tokens)
        self.head_loss = jax.jit(head_loss)

        def head(final_norm, head, x, tokens, acc):
            loss, grads = jax.value_and_grad(head_loss, argnums=(0, 1, 2))(
                final_norm, head, x, tokens)
            return loss, jax.tree_util.tree_map(
                jnp.add, acc, grads[:2]), grads[2]

        self.head = jax.jit(head, donate_argnums=(4,))
        self.embed = jax.jit(lambda embed, tokens: embed[tokens])
        self.embed_grad = jax.jit(
            lambda acc, tokens, dx: acc.at[tokens].add(dx),
            donate_argnums=(0,))

    def through(self, params: dict, tokens, imposed=()):
        """``(inputs of every layer and of the head, choices of every
        expert layer)`` of one sequence; ``imposed``: a ``[S, top_k]`` an
        expert layer, in order, or none."""
        inputs, picks = [self.embed(params["embed"], tokens)], []
        imposed = iter(imposed)
        for kind, p in zip(self.kinds, params["layers"]):
            y, pick = self.forward[kind](
                self.mask, p, inputs[-1],
                None if kind[1] else next(imposed, None))
            inputs.append(y)
            if not kind[1]:
                picks.append(np.asarray(pick))
        return inputs, picks

    def loss(self, params: dict, tokens) -> float:
        """The forward pass alone."""
        return sum(float(self.head_loss(
            params["final_norm"], params["head"],
            self.through(params, sequence)[0][-1], sequence))
            for sequence in tokens)

    def loss_and_grads(self, params: dict, tokens, imposed=None):
        """``(loss, grads, chosen [expert layers, batch * S, top_k])``, the
        reference's own choices; ``imposed``, of ``chosen``'s shape: the
        experts every position goes through in their place."""
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        d_embed, d_layers = zeros["embed"], zeros["layers"]
        d_top = (zeros["final_norm"], zeros["head"])
        total, chosen = 0.0, []
        length = tokens.shape[1]
        for b, sequence in enumerate(tokens):
            mine = [] if imposed is None else [
                jnp.asarray(layer[b * length:(b + 1) * length], jnp.int32)
                for layer in imposed]
            inputs, picks = self.through(params, sequence, mine)
            loss, d_top, dx = self.head(
                params["final_norm"], params["head"], inputs.pop(),
                sequence, d_top)
            total += float(loss)
            for i, kind in reversed(list(enumerate(self.kinds))):
                d_layers[i], dx = self.backward[kind](
                    self.mask, params["layers"][i], inputs.pop(), dx,
                    d_layers[i], None if kind[1] or not mine else mine.pop())
            d_embed = self.embed_grad(d_embed, sequence, dx)
            chosen.append(np.stack(picks))
        return total, {"embed": d_embed, "layers": d_layers,
                       "final_norm": d_top[0], "head": d_top[1]}, \
            np.concatenate(chosen, axis=1)


def reference_losses(config: dict, seed: int, global_batch: int,
                     steps: int):
    """Losses of ``steps`` (at most 2) plain AdamW steps from the seeded
    state on the seeded batch, on one device: loss and gradients of the
    seeded state, AdamW's first update, the loss of the updated state (a
    forward pass alone: nobody reads a second gradient).

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
    loss, grads, chosen = reference.loss_and_grads(
        params, tokens, None if first is None else first.chosen)
    losses = [loss]
    differ = log_routing(config, chosen, first)
    if first is not None:
        outside = leaves_outside(
            config, gradient_errors(first.gradients, grads))
        if outside or not differ <= config["correct"]["choices_limit"]:
            losses[0] = math.inf
    log_memory("after the reference's first step")
    if steps == 2:
        params = update_by_leaf(make_optimizer(config), params, grads)
        losses.append(reference.loss(params, tokens))
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
        f"by expert layer {here.tolist()} of {reference_chosen[0].size} "
        f"each, {reference_chosen[0].size * z['held'] // z['routed']} even")
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
