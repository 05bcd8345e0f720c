"""The AFMoE (Trinity-Mini) next-token training job and its plain reference.

The program's side is the normal path: ``horovod_tpu/models/afmoe.py``
(flash kernels under the causal window and the causal mask, the dropless
expert layer with a sigmoid router, a shared expert beside it) under
``jax.value_and_grad``, AdamW through ``hvd.DistributedOptimizer`` inside
``hvd.shard_step``, state donated, one batch that lives on the device, as
``jobs/sdar_moe.py``, whose log lines, optimizer, judgement of gradient
leaves and dense attention (``attend``, ``expert``, ``rms_norm``,
``rotary``: plain ``jax.numpy``) this job takes as they are.

The reference (everything from ``reference_layer`` down) is the published
model written out in ``jax.numpy``, float32, every product at
``jax.default_matmul_precision("highest")``; it imports nothing from
``horovod_tpu.models`` or ``horovod_tpu.parallel`` and shares only the
layout of the parameter tree (``seeded_params``) and the batch.

The equations (``config``; † marks what the published ``config.json`` does
not carry and ``assumed`` takes from the public ``afmoe`` modelling code).
No bias anywhere, RMSNorm eps ``rms_norm_eps``.  ``x0 = sqrt(hidden_size) x
E[tokens]`` (``mup_enabled``†).  Every layer: ``a = RMSNorm_in(x)``; ``q =
RMSNorm_q(a Wq)``, ``k = RMSNorm_k(a Wk)`` over the ``head_dim`` of each
head, ``v = a Wv``, ``g = a Wg``† (``num_attention_heads x head_dim``
wide); a ``sliding_attention`` layer rotates ``q`` and ``k`` by their
positions (theta ``rope_theta``, the whole head, pairs ``(i, i + head_dim /
2)``) and keeps pair ``(i, j)`` iff ``0 <= i - j < sliding_window``, a
``full_attention`` layer has no positions at all† and keeps ``j <= i``;
query head ``h`` reads key/value head ``h // (heads / kv heads)``; ``o =
softmax(q k^T / sqrt(head_dim)) v`` over the kept pairs; ``h = x +
RMSNorm_post_attn((o * sigmoid(g)) Wo)``† (four norms a layer); ``m =
RMSNorm_pre_mlp(h)``.  The first ``num_dense_layers`` layers: ``f = (silu(m
W1) * (m W3)) W2`` of width ``intermediate_size``.  The others: ``s =
sigmoid(m Wr)`` over all published experts (``score_func``); the
``num_experts_per_tok`` largest of ``s + expert_bias``† (a buffer, zero
here, no gradient); ``w = route_scale x s[chosen] / (sum s[chosen] +
1e-20)`` (``route_norm``); ``f = Shared(m) + sum_e w_e Expert_e(m)``, both
gated SiLU of width ``moe_intermediate_size``.  ``x' = h +
RMSNorm_post_mlp(f)``.  Loss: the mean over the ``S - 1`` predictions of a
sequence and over the sequences of ``-log softmax(RMSNorm_final(x_L)
W_head)_i[token_{i+1}]``, cross-entropy alone (``load_balance_coeff`` is a
pre-training setting and unused).

Departures from the published description: the sum over the chosen experts
runs over those HELD HERE (``num_experts`` of them from
``deployment.first_expert``; what the absent ones would add is left out,
here and in the program alike, and the shared expert is whole), and the
vocabulary is the slice held here (ids drawn from it, logits and loss over
it).

Every held expert is applied to every position and weighted by the routing
(zero where not chosen).  So that it fits one chip at the published widths
the reference runs a sequence at a time and a layer at a time (each layer's
gradient by ``jax.vjp`` of that layer from its saved input:
``ReferenceSteps``), attention two query heads at a time and the experts
one after the other; ``reference_loss`` is the same functions composed
whole, and the tests hold the two to each other.
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

sdar = mf.load_module("jobs", "sdar_moe")
FirstStep, log, log_memory, highest = (
    sdar.FirstStep, sdar.log, sdar.log_memory, sdar.highest)
make_optimizer, choices_that_differ, first_gradients, leaves_outside = (
    sdar.make_optimizer, sdar.choices_that_differ, sdar.first_gradients,
    sdar.leaves_outside)
rms_norm, rotary, attend, expert = (
    sdar.rms_norm, sdar.rotary, sdar.attend, sdar.expert)

SLIDING = "sliding_attention"

#: First steps by ``(seed, global batch)``: where ``Program.step`` and
#: ``reference_losses`` meet, as in ``jobs/sdar_moe.py``.
_first_steps = {}


# -- what program and reference share: the tree's layout and the batch -------

def sizes(config: dict) -> dict:
    published = config["published"]
    return dict(
        vocab=config["vocab_size"], d=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        dense_layers=config["num_dense_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        dense_width=config["intermediate_size"],
        width=config["moe_intermediate_size"],
        shared_width=(config["moe_intermediate_size"]
                      * config["num_shared_experts"]),
        routed=published["num_experts"], held=config["num_experts"],
        first=config["deployment"]["first_expert"],
        top_k=config["num_experts_per_tok"], eps=config["rms_norm_eps"],
        theta=float(config["rope_theta"]), window=config["sliding_window"],
        route_scale=config["route_scale"],
        mup=config["mup_enabled"],
        length=config["assumed"]["sequence_length"]["value"])


def kinds(z: dict) -> list:
    """``(dense?, window?)`` of every layer, in order."""
    return [(i < z["dense_layers"], kind == SLIDING)
            for i, kind in enumerate(z["layer_types"])]


def runs(z: dict) -> list:
    """``[(dense?, window?, layers)]``: stretches of consecutive layers of
    one kind, which the tree stacks (``models/afmoe.py``, the tree)."""
    return [(*kind, len(list(run)))
            for kind, run in itertools.groupby(kinds(z))]


def seeded_params(config: dict, seed: int, sharding=None) -> dict:
    """The parameter tree from the seed, float32, a run's layers stacked on
    a leading axis (``assumed.init``): normal(0, 1 / sqrt(fan_in))
    matrices, unit norms, an embedding of normal(0, 1 / sqrt(hidden)) rows
    (so that ``sqrt(hidden) x E`` has unit entries), router columns of
    normal(0, ``router_init.scale`` / sqrt(hidden)); no ``expert_bias``
    (zero, frozen); made where ``sharding`` says.

    The router (``assumed.router_init``): every chip's block of columns
    (``num_experts`` of them) has its mean taken off, so the columns a chip
    holds sum to zero.  At seeded weights attention averages its window, the
    norm after it blows that small mean up to a unit state, and the
    positions of a layer come to share a direction (a third of their
    state's energy by the last layer); a column's projection on it moves its
    expert's logit for every position alike.  With independent columns the
    pairs routed here were a lottery by seed (0.93 to 1.15 x the even load
    over the four layers on the v5e, ``train_samples_per_s`` spread 0.53 %
    over six seeds, 0.5 allowed: PERF.md, PR 31).  With a block's columns
    summing to zero such a direction moves a chip's experts against each
    other and, to first order, not the chip's load, whatever the direction
    is: what a balancing loss would have taught the router.  Every position
    still routes by its own state, unevenly by expert, layer and seed."""
    z = sizes(config)
    d, hd = z["d"], z["head_dim"]
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
            q_width = z["heads"] * hd
            layer = {
                "attn_norm": ones(n, d), "post_attn_norm": ones(n, d),
                "pre_mlp_norm": ones(n, d), "post_mlp_norm": ones(n, d),
                "wq": matrix(n, d, q_width, fan_in=d),
                "wk": matrix(n, d, z["kv_heads"] * hd, fan_in=d),
                "wv": matrix(n, d, z["kv_heads"] * hd, fan_in=d),
                "wg": matrix(n, d, q_width, fan_in=d),
                "q_norm": ones(n, hd), "k_norm": ones(n, hd),
                "wo": matrix(n, q_width, d, fan_in=q_width),
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

        return {"embed": matrix(z["vocab"], d, fan_in=d),
                "runs": [run(dense, n) for dense, _, n in runs(z)],
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
    from horovod_tpu.models.afmoe import AfmoeConfig
    z, assumed = sizes(config), config["assumed"]
    return AfmoeConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        layer_types=z["layer_types"], num_dense_layers=z["dense_layers"],
        num_attention_heads=z["heads"], num_key_value_heads=z["kv_heads"],
        head_dim=z["head_dim"], intermediate_size=z["dense_width"],
        moe_intermediate_size=z["width"], num_experts=z["routed"],
        num_experts_per_tok=z["top_k"],
        num_shared_experts=config["num_shared_experts"],
        sliding_window=z["window"], rope_theta=z["theta"],
        rms_norm_eps=z["eps"], score_func=config["score_func"],
        route_norm=config["route_norm"], route_scale=z["route_scale"],
        mup_enabled=z["mup"], experts_held=z["held"],
        first_expert=z["first"],
        dtype=jnp.dtype(config["compute_dtype"]),
        attention_tile=assumed["attention_tile"]["value"],
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
        from horovod_tpu.models import afmoe
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
                lambda p: afmoe.loss_fn(p, tokens, cfg),
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

def dense_masks(length: int, window: int) -> dict:
    """``{window?: boolean [S, S]}`` from the two rules."""
    ahead = np.arange(length)[:, None] - np.arange(length)[None, :]
    return {True: (ahead >= 0) & (ahead < window), False: ahead >= 0}


def gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def reference_layer(z: dict, dense: bool, window, mask, p: dict, x,
                    imposed=None):
    """One layer on one sequence ``x [S, hidden]`` under the boolean ``mask
    [S, S]``; ``window`` (a ``sliding_attention`` layer: rotary positions;
    a Python or a traced boolean, so that one compiled program serves both
    types of layer); returns ``(y, chosen [S, top_k])``, the layer's own
    choices, empty for a dense layer.  ``imposed [S, top_k]``: the experts
    that are weighed and applied in the chosen ones' place (a judged first
    step's: ``reference_losses``); every score and weight is still this
    layer's own."""
    seq = x.shape[0]
    heads, kv_heads, hd = z["heads"], z["kv_heads"], z["head_dim"]
    positioned = lambda t: jnp.where(
        window, rotary(t, jnp.arange(seq), z["theta"]), t)
    a = rms_norm(x, p["attn_norm"], z["eps"])
    q = positioned(rms_norm((a @ p["wq"]).reshape(seq, heads, hd),
                            p["q_norm"], z["eps"]))
    k = positioned(rms_norm((a @ p["wk"]).reshape(seq, kv_heads, hd),
                            p["k_norm"], z["eps"]))
    v = (a @ p["wv"]).reshape(seq, kv_heads, hd)
    # Two query heads at a time, one after the other (jobs/sdar_moe.py).
    group = heads // kv_heads
    at_a_time = min(2, group)
    pieces = q.reshape(seq, heads // at_a_time, at_a_time, hd)
    kv_of_piece = jnp.arange(heads // at_a_time) * at_a_time // group
    attended = jax.lax.map(
        lambda piece: attend(piece[0], k[:, piece[1]], v[:, piece[1]], mask),
        (pieces.transpose(1, 0, 2, 3), kv_of_piece))
    attended = attended.transpose(1, 0, 2, 3).reshape(seq, heads * hd)
    h = x + rms_norm((attended * jax.nn.sigmoid(a @ p["wg"])) @ p["wo"],
                     p["post_attn_norm"], z["eps"])

    m = rms_norm(h, p["pre_mlp_norm"], z["eps"])
    if dense:
        f = gated(m, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
        return h + rms_norm(f, p["post_mlp_norm"], z["eps"]), \
            jnp.zeros((seq, 0), jnp.int32)
    scores = jax.nn.sigmoid(m @ p["router"])
    bias = jax.lax.stop_gradient(p["expert_bias"]) \
        if "expert_bias" in p else 0.0
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
    return h + rms_norm(f, p["post_mlp_norm"], z["eps"]), chosen


def embedded(z: dict, embed, tokens):
    return embed[tokens] * (z["d"] ** 0.5 if z["mup"] else 1.0)


def reference_head(z: dict, final_norm, head, x, tokens):
    """``sum_{i < S - 1} -log softmax(W_head RMSNorm(x_i))[token_{i+1}]``."""
    logits = rms_norm(x[:-1], final_norm, z["eps"]) @ head
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, tokens[1:, None], axis=-1)[:, 0])


def unstacked(params: dict) -> dict:
    """The tree with ``layers``, a flat list of the layers' own dicts in
    order, in place of ``runs``; a tree that has ``layers`` as it is."""
    if "layers" in params:
        return params
    layers = [{name: a[i] for name, a in run.items()}
              for run in params["runs"]
              for i in range(next(iter(run.values())).shape[0])]
    return dict({k: v for k, v in params.items() if k != "runs"},
                layers=layers)


@highest
def reference_loss(config: dict, params: dict, tokens):
    """The loss of a batch, whole: for ``jax.grad`` at small sizes."""
    z = sizes(config)
    batch, length = tokens.shape
    masks = dense_masks(length, z["window"])
    params = unstacked(params)
    total = 0.0
    for b in range(batch):
        x = embedded(z, params["embed"], tokens[b])
        for (dense, window), p in zip(kinds(z), params["layers"]):
            x, _ = reference_layer(z, dense, window,
                                   jnp.asarray(masks[window]), p, x)
        total += reference_head(z, params["final_norm"], params["head"], x,
                                tokens[b])
    return total / (batch * (length - 1))


class ReferenceSteps:
    """The reference's loss and gradients a sequence and a layer at a
    time: a dense and an expert layer are each compiled once and run for
    every such layer and every sequence, and a layer's gradient comes from
    ``jax.vjp`` of that layer at its saved input.  The tree is
    ``unstacked``'s."""

    def __init__(self, config: dict, batch: int):
        z = self.z = sizes(config)
        self.kinds = kinds(z)
        self.scale = 1.0 / (batch * (z["length"] - 1))
        self.masks = {w: jnp.asarray(m) for w, m in dense_masks(
            z["length"], z["window"]).items()}
        # A program a kind of MLP: the mask and whether the layer has
        # positions are arguments (closed over, the 64 MB mask would be
        # compiled into the executable), so a window and a full layer run
        # the same one.
        self.forward, self.backward = {}, {}
        for dense in {kind[0] for kind in self.kinds}:
            layer = functools.partial(highest(reference_layer), z, dense)
            self.forward[dense] = jax.jit(layer)

            def backward(window, mask, p, x, dy, acc, imposed, layer=layer):
                _, vjp, _ = jax.vjp(
                    lambda p, x: layer(window, mask, p, x, imposed),
                    p, x, has_aux=True)
                dp, dx = vjp(dy)
                return jax.tree_util.tree_map(jnp.add, acc, dp), dx

            self.backward[dense] = jax.jit(backward, donate_argnums=(5,))

        head_loss = lambda f, h, x, tokens: self.scale * highest(
            reference_head)(z, f, h, x, tokens)
        self.head_loss = jax.jit(head_loss)

        def head(final_norm, head, x, tokens, acc):
            loss, grads = jax.value_and_grad(head_loss, argnums=(0, 1, 2))(
                final_norm, head, x, tokens)
            return loss, jax.tree_util.tree_map(
                jnp.add, acc, grads[:2]), grads[2]

        self.head = jax.jit(head, donate_argnums=(4,))
        mup = z["d"] ** 0.5 if z["mup"] else 1.0
        self.embed = jax.jit(lambda embed, tokens: embedded(z, embed, tokens))
        self.embed_grad = jax.jit(
            lambda acc, tokens, dx: acc.at[tokens].add(mup * dx),
            donate_argnums=(0,))

    def through(self, params: dict, tokens, imposed=()):
        """``(inputs of every layer and of the head, choices of every
        expert layer)`` of one sequence; ``imposed``: a ``[S, top_k]`` an
        expert layer, in order, or none."""
        inputs, picks = [self.embed(params["embed"], tokens)], []
        imposed = iter(imposed)
        for (dense, window), p in zip(self.kinds, params["layers"]):
            y, pick = self.forward[dense](
                window, self.masks[window], p, inputs[-1],
                None if dense else next(imposed, None))
            inputs.append(y)
            if not dense:
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
            for i, (dense, window) in reversed(list(enumerate(self.kinds))):
                d_layers[i], dx = self.backward[dense](
                    window, self.masks[window], params["layers"][i],
                    inputs.pop(), dx, d_layers[i],
                    None if dense or not mine else mine.pop())
            d_embed = self.embed_grad(d_embed, sequence, dx)
            chosen.append(np.stack(picks))
        return total, {"embed": d_embed, "layers": d_layers,
                       "final_norm": d_top[0], "head": d_top[1]}, \
            np.concatenate(chosen, axis=1)


def gradient_errors(got: dict, want: dict) -> dict:
    """``|got - want| / |want|`` in the 2-norm for every kind of leaf of
    the parameter tree, a layer's leaf over all the layers that have it;
    either tree may hold its layers in ``runs`` or as ``layers``.  A layer
    at a time: a tree may live on the host."""
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
        if name != "layers":
            add(name, got[name], want[name])
    for mine, theirs in zip(got["layers"], want["layers"]):
        for name in theirs:
            add(name, mine[name], theirs[name])
    return {name: float(np.sqrt(off / size))
            for name, (off, size) in sorted(sums.items())}


def update_by_leaf(opt, params: dict, grads: dict) -> dict:
    """AdamW's FIRST step from zero moments, a leaf at a time with the leaf
    donated: the moments of a leaf live only while it is updated (5.6 GB of
    moments beside 2.8 GB of parameters and as much of gradients would
    leave the reference's own temporaries no room)."""
    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(p, g):
        updates, _ = opt.update(g, opt.init(p), p)
        return optax.apply_updates(p, updates)

    leaves, tree = jax.tree_util.tree_flatten(params)
    g_leaves = tree.flatten_up_to(grads)
    return tree.unflatten([update(p, g) for p, g in zip(leaves, g_leaves)])


def reference_losses(config: dict, seed: int, global_batch: int,
                     steps: int):
    """Losses of ``steps`` (at most 2) plain AdamW steps from the seeded
    state on the seeded batch, on one device: loss and gradients of the
    seeded state, AdamW's first update, the loss of the updated state (a
    forward pass alone: nobody reads a second gradient).

    Where a program has left its first step for this seed and batch
    (``_first_steps``), the reference judges it.  It runs every position
    through THAT step's choices of experts, weighed by its own scores, so
    the gradients differ by arithmetic alone and not by the choices that
    rounding flips (bf16 flips 1.2 % of them, and under near equal sigmoid
    weights a flip swaps a third of a token's routed output: PERF.md, PR
    31); the choices themselves are held to the reference's own by
    ``correct.choices_limit``, the share of the step's choices that are not
    among the reference's for their position.  Then, as
    ``jobs/sdar_moe.py``: every gradient leaf of the first step is held to
    ``correct.gradient_limits``, and a leaf or the choices outside turn the
    first loss into ``inf``, which the runner's one comparison fails."""
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
