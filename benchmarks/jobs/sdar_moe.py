"""The SDAR-MoE block-diffusion training job and its plain reference.

The program's side is the normal path: ``horovod_tpu/models/sdar_moe.py``
(flash kernels under the block-diffusion mask, the dropless expert layer)
under ``jax.value_and_grad``, AdamW through ``hvd.DistributedOptimizer``
inside ``hvd.shard_step``, state donated, one batch that lives on the
device, as ``jobs/resnet.py``.  Weights, tokens and their corruption come
from the seed, so every step does the same work.

The reference (everything from ``reference_layer`` down) is the published
model written out in ``jax.numpy``, float32, every product at
``jax.default_matmul_precision("highest")``; it imports nothing from
``horovod_tpu.models`` or ``horovod_tpu.parallel``, and shares only the
layout of the parameter tree (``seeded_params``) and the batch.

The equations.  Every layer (``config``): ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``, eps ``rms_norm_eps``, no bias anywhere.
``Attn``: ``q = W_q x`` (``num_attention_heads`` x ``head_dim``), ``k = W_k
x``, ``v = W_v x`` (``num_key_value_heads`` x ``head_dim``); RMSNorm with a
learned weight over the ``head_dim`` of each query and each key head;
rotary embedding over all of ``head_dim`` (pairs ``(i, i + head_dim / 2)``,
theta ``rope_theta``); query head ``h`` reads key/value head ``h //
(heads / kv heads)``; ``softmax(q k^T / sqrt(head_dim) + mask) v``;
``W_o``.  ``MoE``: ``p = softmax(W_r x)`` over all published experts; the
``num_experts_per_tok`` largest; their ``p`` divided by their sum
(``norm_topk_prob``); ``sum_e p_e W_down,e (silu(W_gate,e x) * W_up,e
x)`` over the chosen experts THAT ARE HELD HERE (``num_experts`` of them
from ``deployment.first_expert``): what the absent experts would add is
left out, here and in the program alike.  No shared expert, no auxiliary
loss.  Block diffusion (``assumed``; BD3-LM, arXiv:2503.09573): ``L`` clean
tokens ``x0`` in blocks of ``block_length``; block ``b`` draws ``t_b``
uniformly from (0, 1] and masks each token with probability ``t_b``; the
model runs on the ``2L`` positions ``[xt ; x0]``, position ``i`` of either
copy at rotary position ``i``; query ``q`` reads key ``k`` iff both are
noised and in one block, or ``q`` is noised, ``k`` clean and ``block(k) <
block(q)``, or both are clean and ``block(k) <= block(q)``.  Loss = ``(1 /
(batch x L)) sum_b (1 / t_b) sum_{i in b, masked} -log softmax(W_head
RMSNorm(y_i))[x0_i]`` over the noised copy, the logits over the slice of
the vocabulary held here, no next-token shift.

Every held expert is applied to every position and weighted by the routing
(zero where not chosen).  So that it fits one chip at the published widths
the reference runs a sequence at a time and a layer at a time (each layer's
gradient by ``jax.vjp`` of that layer from its saved input:
``ReferenceSteps``), attention two query heads at a time and the experts
one after the other (two plain loops, ``lax.map`` and ``lax.scan``:
unrolled, the pieces were scheduled side by side, 13.5 GB, and compiled for
minutes); ``reference_loss`` is the same functions composed whole, and the
tests hold the two to each other.
"""

import collections
import functools
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

#: What a program's first step from the seeded state leaves for the
#: reference to judge: every position's chosen experts and the pairs routed
#: to the held ones, a layer, and the gradient of every parameter, on the
#: host.
FirstStep = collections.namedtuple("FirstStep", "routed chosen gradients")

#: First steps by ``(seed, global batch)``.  ``runners/train.py`` runs the
#: program's check steps, then calls ``reference_losses`` with the seed and
#: the batch and nothing of the program: they meet here, and
#: ``reference_losses`` takes the entry away.
_first_steps = {}


def log(*parts):
    print("bench:", *parts, flush=True)


def log_memory(where: str) -> None:
    """Live and peak bytes on the first device, into the log: 8.8 GB of
    state and as much again in the reference leave little room."""
    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory {where}: " + ", ".join(
        f"{name} {stats[name] / 1e9:.2f} GB" for name in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved") if name in stats))


# -- what program and reference share: the tree's layout and the batch -------

def sizes(config: dict) -> dict:
    published = config["published"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        width=config["moe_intermediate_size"],
        routed=published["num_experts"], held=config["num_experts"],
        first=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]),
        block=config["assumed"]["block_length"]["value"],
        length=config["assumed"]["sequence_length"]["value"])


def seeded_params(config: dict, seed: int, sharding=None) -> dict:
    """The parameter tree from the seed, float32, layers stacked on a
    leading axis: normal(0, 1 / sqrt(fan_in)) matrices, unit norms,
    normal(0, 1) embedding; made where ``sharding`` says (the default
    device without one).

    The router (``assumed.router_init``): independent columns, normal(0,
    ``scale`` / sqrt(hidden)), but for one direction.  At seeded weights a
    position's state is its token's embedding and little else, so every
    ``[MASK]`` position, a quarter of all, has ONE state, and with it one
    set of experts: the load here would be ``k`` pairs for each of them,
    ``k`` of those ``top_k`` experts held here, 0 to ``top_k`` by the seed
    (PERF.md, PR 27).  So the columns are made orthogonal to ``[MASK]``'s
    embedding, and along it ``top_k`` experts, one on each chip of the
    deployment, get the logit ``scale * (mask_logit + mask_logit_spread *
    normal)`` and the others 0: that one state sends one pair to every
    chip, as the router's balancing loss would have taught it, and every
    other position routes by the independent columns."""
    z = sizes(config)
    d, hd, f, n = z["d"], z["head_dim"], z["width"], z["layers"]
    router = config["assumed"]["router_init"]["value"]
    chips = z["routed"] // z["held"]

    @functools.partial(jax.jit, out_shardings=sharding)
    def make(key):
        keys = iter(jax.random.split(key, 16))

        def matrix(*shape, fan_in, scale=1.0):
            return jax.random.normal(next(keys), shape, jnp.float32) \
                * (scale / fan_in ** 0.5)

        ones = lambda *shape: jnp.ones(shape, jnp.float32)
        embed = jax.random.normal(next(keys), (z["vocab"], d), jnp.float32)
        mask = embed[-1] / jnp.linalg.norm(embed[-1])
        columns = matrix(n, d, z["routed"], fan_in=d, scale=router["scale"])
        # The t-th of [MASK]'s experts: a seeded one of chip t mod chips.
        theirs = jnp.arange(z["top_k"]) % chips * z["held"] \
            + jax.random.randint(next(keys), (n, z["top_k"]), 0, z["held"])
        along = jnp.zeros((n, z["routed"])).at[
            jnp.arange(n)[:, None], theirs].set(router["scale"] * (
                router["mask_logit"] + router["mask_logit_spread"]
                * jax.random.normal(next(keys), (n, z["top_k"]))))
        return {
            "embed": embed,
            "layers": {
                "attn_norm": ones(n, d),
                "wq": matrix(n, d, z["heads"] * hd, fan_in=d),
                "wk": matrix(n, d, z["kv_heads"] * hd, fan_in=d),
                "wv": matrix(n, d, z["kv_heads"] * hd, fan_in=d),
                "q_norm": ones(n, hd), "k_norm": ones(n, hd),
                "wo": matrix(n, z["heads"] * hd, d,
                             fan_in=z["heads"] * hd),
                "moe_norm": ones(n, d),
                "router": columns + mask[:, None] * (
                    along / d ** 0.5 - jnp.einsum("d,nde->ne", mask,
                                                  columns))[:, None, :],
                "w_gate": matrix(n, z["held"], d, f, fan_in=d),
                "w_up": matrix(n, z["held"], d, f, fan_in=d),
                "w_down": matrix(n, z["held"], f, d, fan_in=f),
            },
            "final_norm": ones(d),
            "head": matrix(d, z["vocab"], fan_in=d),
        }

    return make(jax.random.PRNGKey(seed))


def seeded_batch(config: dict, seed: int, batch: int):
    """``(xt, x0, weight)``, each ``[batch, L]``: clean ids drawn from the
    slice of the vocabulary held here (its last id, ``[MASK]``, left
    out), their block-wise corruption, and ``1 / t_b`` at the masked
    positions (0 elsewhere)."""
    z = sizes(config)
    length, block, mask_id = z["length"], z["block"], z["vocab"] - 1

    @jax.jit
    def make(key):
        k_ids, k_t, k_u = jax.random.split(key, 3)
        x0 = jax.random.randint(k_ids, (batch, length), 0, mask_id,
                                jnp.int32)
        t = 1.0 - jax.random.uniform(k_t, (batch, length // block))
        t = jnp.repeat(t, block, axis=1)
        masked = jax.random.uniform(k_u, (batch, length)) < t
        return (jnp.where(masked, mask_id, x0),
                x0, jnp.where(masked, 1.0 / t, 0.0).astype(jnp.float32))

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), 1))


def make_optimizer(config: dict):
    o = config["assumed"]["optimizer"]["value"]
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


# -- the program ---------------------------------------------------------------

def model_config(config: dict):
    from horovod_tpu.models.sdar_moe import SdarMoeConfig
    z = sizes(config)
    return SdarMoeConfig(
        vocab_size=z["vocab"], hidden_size=z["d"], num_layers=z["layers"],
        num_heads=z["heads"], num_kv_heads=z["kv_heads"],
        head_dim=z["head_dim"], expert_width=z["width"],
        num_experts=z["routed"], experts_per_token=z["top_k"],
        experts_held=z["held"], first_expert=z["first"],
        rope_theta=z["theta"], rms_norm_eps=z["eps"],
        block_length=z["block"],
        dtype=jnp.dtype(config["compute_dtype"]),
        attention_tile=config["assumed"]["attention_tile"]["value"],
        loss_chunk=config["assumed"]["loss_chunk"]["value"])


class Program:
    """The system under test: ``step(*state, *batch) -> (*state, loss)``
    over the initialised ``hvd`` world, ``images_per_chip`` sequences a
    slot (the runner's name for a slot's share of the batch).  ``first``
    is the :class:`FirstStep` of the first step this program ran, which
    the runner makes from the seeded state: what that step returned beside
    the loss, a layer (pairs routed to the held experts, every position's
    choices), and the gradient its optimizer took."""

    def __init__(self, config: dict, images_per_chip: int, seed: int):
        import horovod_tpu as hvd
        from horovod_tpu.models import sdar_moe
        self.config, self.seed = config, seed
        self.global_batch = images_per_chip * hvd.num_slots()
        self.batch = jax.device_put(
            seeded_batch(config, seed, self.global_batch),
            hvd.parallel.data_parallel_sharding())
        cfg = model_config(config)
        self.optimizer = opt = hvd.DistributedOptimizer(
            make_optimizer(config))

        def local_step(params, opt_state, xt, x0, weight):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: sdar_moe.loss_fn(p, xt, x0, weight, cfg),
                has_aux=True)(params)
            loss = hvd.allreduce(loss, op=hvd.Average)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, \
                aux.routed_here[None], aux.chosen[None]

        # Off the TPU the kernels run in Pallas's interpreter, which cannot
        # type its loop indices under varying-axes tracking
        # (parallel/flash.py); the optimizer then reduces every gradient
        # itself, as it does for any untracked step.
        self.compiled = hvd.shard_step(
            local_step,
            in_specs=(P(), P(), P("hvd"), P("hvd"), P("hvd")),
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
        it (so the second call runs the program the first compiled)."""
        import horovod_tpu as hvd
        # Made in place: a ``device_put`` of the finished state would hold
        # it twice, 13 GB of the chip's 16.
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

def highest(fn):
    """``fn`` traced with every product at full float32 precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped


def dense_mask(length: int, block: int):
    """The block-diffusion mask over ``[xt ; x0]`` as a boolean ``[2L,
    2L]`` array, from the rule."""
    pos = np.arange(2 * length)
    noised, blk = pos < length, (pos % length) // block
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rotary(x, positions, theta):
    """``x [S, heads, head_dim]`` rotated by its positions."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.checkpoint
def attend(q, k, v, mask):
    """Dense masked softmax attention of a few query heads ``q [S, n,
    head_dim]`` on one key/value head ``k``, ``v`` ``[S, head_dim]``."""
    scores = jnp.einsum("qnd,kd->nqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(mask[None], scores, -jnp.inf)
    return jnp.einsum("nqk,kd->qnd", jax.nn.softmax(scores, axis=-1), v)


@jax.checkpoint
def expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def reference_layer(z: dict, mask, p: dict, x):
    """One layer on one sequence ``x [2L, hidden]``; returns ``(y,
    chosen [2L, top_k])``."""
    seq = x.shape[0]
    positions = jnp.arange(seq) % (seq // 2)
    heads, kv_heads, hd = z["heads"], z["kv_heads"], z["head_dim"]
    h = rms_norm(x, p["attn_norm"], z["eps"])
    q = rotary(rms_norm((h @ p["wq"]).reshape(seq, heads, hd),
                        p["q_norm"], z["eps"]), positions, z["theta"])
    k = rotary(rms_norm((h @ p["wk"]).reshape(seq, kv_heads, hd),
                        p["k_norm"], z["eps"]), positions, z["theta"])
    v = (h @ p["wv"]).reshape(seq, kv_heads, hd)
    # Two query heads at a time, one after the other (a loop, here and
    # over the experts below: unrolled, the pieces are scheduled side by
    # side, each with its own [2, 2L, 2L] scores, and compile for minutes).
    group = heads // kv_heads
    at_a_time = min(2, group)
    pieces = q.reshape(seq, heads // at_a_time, at_a_time, hd)
    kv_of_piece = jnp.arange(heads // at_a_time) * at_a_time // group
    attended = jax.lax.map(
        lambda piece: attend(piece[0], k[:, piece[1]], v[:, piece[1]], mask),
        (pieces.transpose(1, 0, 2, 3), kv_of_piece))
    x = x + attended.transpose(1, 0, 2, 3).reshape(seq, heads * hd) \
        @ p["wo"]

    h = rms_norm(x, p["moe_norm"], z["eps"])
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    top_p, chosen = jax.lax.top_k(probs, z["top_k"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    def add_expert(acc, held):
        e, w_gate, w_up, w_down = held
        gate = jnp.sum(jnp.where(chosen == z["first"] + e, top_p, 0.0),
                       axis=-1, keepdims=True)
        return acc + gate * expert(h, w_gate, w_up, w_down), None

    # Every held expert on every position, one expert after the other.
    x, _ = jax.lax.scan(add_expert, x, (
        jnp.arange(z["held"]), p["w_gate"], p["w_up"], p["w_down"]))
    return x, chosen


def reference_head(z: dict, final_norm, head, x, targets, weight):
    """``sum_i weight_i * -log softmax(W_head RMSNorm(x_i))[target_i]``."""
    logits = rms_norm(x, final_norm, z["eps"]) @ head
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weight * nll)


def layer_of(layers: dict, i: int) -> dict:
    return {name: a[i] for name, a in layers.items()}


@highest
def reference_loss(config: dict, params: dict, xt, x0, weight):
    """The loss of a batch, whole: for ``jax.grad`` at small sizes."""
    z = sizes(config)
    batch, length = x0.shape
    mask = jnp.asarray(dense_mask(length, z["block"]))
    total = 0.0
    for b in range(batch):
        x = params["embed"][jnp.concatenate([xt[b], x0[b]])]
        for i in range(z["layers"]):
            x, _ = reference_layer(z, mask, layer_of(params["layers"], i), x)
        total += reference_head(z, params["final_norm"], params["head"],
                                x[:length], x0[b], weight[b])
    return total / (batch * length)


class ReferenceSteps:
    """The reference's loss and gradients a sequence and a layer at a
    time: a layer is compiled once and run for every layer and sequence,
    and its gradient comes from ``jax.vjp`` of that layer at its saved
    input.  The tree is the shared one with ``layers`` as a list."""

    def __init__(self, config: dict, batch: int):
        z = self.z = sizes(config)
        self.scale = 1.0 / (batch * z["length"])
        mask = jnp.asarray(dense_mask(z["length"], z["block"]))
        layer = highest(functools.partial(reference_layer, z))
        self.forward = jax.jit(lambda p, x: layer(mask, p, x))

        def backward(p, x, dy, acc):
            _, vjp, _ = jax.vjp(lambda p, x: layer(mask, p, x), p, x,
                                has_aux=True)
            dp, dx = vjp(dy)
            return jax.tree_util.tree_map(jnp.add, acc, dp), dx

        self.backward = jax.jit(backward, donate_argnums=(3,))

        def head(final_norm, head, x, targets, weight, acc):
            loss, grads = jax.value_and_grad(
                lambda f, h, x: self.scale * highest(reference_head)(
                    z, f, h, x[:z["length"]], targets, weight),
                argnums=(0, 1, 2))(final_norm, head, x)
            return loss, jax.tree_util.tree_map(
                jnp.add, acc, grads[:2]), grads[2]

        self.head = jax.jit(head, donate_argnums=(5,))
        self.embed_grad = jax.jit(
            lambda acc, tokens, dx: acc.at[tokens].add(dx),
            donate_argnums=(0,))

    def loss_and_grads(self, params: dict, xt, x0, weight):
        """``(loss, grads, chosen [layers, batch * 2L, top_k])``."""
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        d_embed, d_layers = zeros["embed"], zeros["layers"]
        d_top = (zeros["final_norm"], zeros["head"])
        total, chosen = 0.0, []
        for b in range(x0.shape[0]):
            tokens = jnp.concatenate([xt[b], x0[b]])
            inputs, picks = [params["embed"][tokens]], []
            for p in params["layers"]:
                y, pick = self.forward(p, inputs[-1])
                inputs.append(y)
                picks.append(pick)
            loss, d_top, dx = self.head(
                params["final_norm"], params["head"], inputs.pop(), x0[b],
                weight[b], d_top)
            total += float(loss)
            for i in reversed(range(len(params["layers"]))):
                d_layers[i], dx = self.backward(
                    params["layers"][i], inputs.pop(), dx, d_layers[i])
            d_embed = self.embed_grad(d_embed, tokens, dx)
            chosen.append(np.stack([np.asarray(c) for c in picks]))
        return total, {"embed": d_embed, "layers": d_layers,
                       "final_norm": d_top[0], "head": d_top[1]}, \
            np.concatenate(chosen, axis=1)


def unstacked(params: dict) -> dict:
    n = next(iter(params["layers"].values())).shape[0]
    return dict(params, layers=[layer_of(params["layers"], i)
                                for i in range(n)])


def choices_that_differ(program_chosen, reference_chosen) -> float:
    """Share of the program's (position, choice) pairs whose expert is not
    among the reference's choices for that position."""
    same = (np.asarray(program_chosen)[..., :, None]
            == np.asarray(reference_chosen)[..., None, :]).any(-1)
    return float(1.0 - same.mean())


def first_gradients(config: dict, opt_state) -> dict:
    """The gradient the optimizer took in its FIRST step, by leaf, on the
    host: AdamW's first moment starts at zero, so after one step it is
    ``(1 - b1)`` times that gradient, exactly.  The step itself hands no
    gradient out (2.2 GB more on a chip that has none to spare)."""
    b1 = config["assumed"]["optimizer"]["value"]["b1"]
    return jax.tree_util.tree_map(
        lambda m: np.asarray(m) / np.float32(1.0 - b1),
        optax.tree_utils.tree_get(opt_state, "mu"))


def gradient_errors(got: dict, want: dict) -> dict:
    """``|got - want| / |want|`` in the 2-norm for every kind of leaf of
    the parameter tree, a layer's leaves over all layers at once; either
    tree may hold its ``layers`` stacked or as a list."""
    def flat(tree):
        layers = tree["layers"]
        if isinstance(layers, list):
            layers = {name: [layer[name] for layer in layers]
                      for name in layers[0]}
        return dict({k: v for k, v in tree.items() if k != "layers"},
                    **layers)

    @jax.jit
    def error(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    got, want = flat(got), flat(want)
    as_one = lambda leaf: jnp.stack(leaf) if isinstance(leaf, list) \
        else jnp.asarray(leaf)
    return {name: float(error(as_one(got[name]), as_one(want[name])))
            for name in sorted(want)}


def leaves_outside(config: dict, errors: dict) -> list:
    """The leaves whose gradient's error is not within
    ``correct.gradient_limits`` (a leaf with no limit is outside); logs
    every leaf beside its limit."""
    limits = config["correct"]["gradient_limits"]
    outside = [name for name, e in errors.items()
               if not e <= limits.get(name, -1.0)]
    log("gradients: first step, |program - reference| / |reference| by "
        "leaf (limit): " + ", ".join(
            f"{name} {e:.3e} ({limits.get(name)})"
            for name, e in errors.items())
        + ("; OUTSIDE: " + ", ".join(outside) if outside else "; all inside"))
    return outside


def reference_losses(config: dict, seed: int, global_batch: int,
                     steps: int):
    """Losses of ``steps`` plain AdamW steps from the seeded state on the
    seeded batch, on one device.

    Where a program has left its first step for this seed and batch
    (``_first_steps``), the reference judges it: the routing is logged
    beside its own, and every gradient leaf of the first step is held to
    ``correct.gradient_limits``.  ``runners/train.py`` knows one limit, the
    loss's, and a seeded model's loss hardly depends on the precision of
    its products; so a first step with a leaf outside its limit gets
    ``inf`` as the loss to agree with, which no loss does, and the
    runner's own comparison reports the run as not correct."""
    log_memory("before the reference")
    first = _first_steps.pop((seed, global_batch), None)
    opt = make_optimizer(config)
    batch = seeded_batch(config, seed, global_batch)
    params = unstacked(seeded_params(config, seed))
    opt_state = opt.init(params)
    reference = ReferenceSteps(config, global_batch)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    losses = []
    for step in range(steps):
        loss, grads, chosen = reference.loss_and_grads(params, *batch)
        losses.append(loss)
        if step == 0:
            log_routing(config, chosen, first)
            if first is not None and leaves_outside(
                    config, gradient_errors(first.gradients, grads)):
                losses[0] = math.inf
            log_memory("after the reference's first step")
        if step + 1 < steps:
            params, opt_state = update(params, opt_state, grads)
    # 8.8 GB of float32 state: gone before the program's is made again.
    del params, opt_state, grads, reference
    gc.collect()
    log_memory("after the reference")
    return losses


def log_routing(config: dict, reference_chosen, first=None) -> None:
    z = sizes(config)
    here = ((reference_chosen >= z["first"])
            & (reference_chosen < z["first"] + z["held"])).sum(axis=(1, 2))
    log(f"routing: reference, pairs routed to the {z['held']} held experts "
        f"by layer {here.tolist()} of {reference_chosen[0].size} each")
    if first is None:
        return
    log(f"routing: program, pairs routed to the held experts by layer "
        f"{first.routed.tolist()}; "
        f"{100 * choices_that_differ(first.chosen, reference_chosen):.3f} % "
        f"of its choices are not the reference's")
