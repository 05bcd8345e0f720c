"""AFMoE: window and full attention in one stack, a gated attention output,
a sigmoid router with a shared expert, leading dense layers.

The model of ``arcee-ai/Trinity-Mini`` (``model_type`` ``afmoe``; the
published ``config.json``, and for what it does not carry the public
``afmoe`` modelling code of Hugging Face ``transformers``).  No bias
anywhere, four RMSNorms a layer.  With ``x [S, hidden]`` a layer's input:

    x0 = sqrt(hidden) * E[tokens]                              (mup_enabled)
    a  = RMSNorm_in(x)
    q  = RMSNorm_q(a Wq)  k = RMSNorm_k(a Wk)  v = a Wv        (a head each)
    g  = a Wg                                                  [S, heads x head_dim]
    sliding layer: rotary(q, k) over the whole head; pair (i, j) kept iff
                   0 <= i - j < sliding_window
    full layer:    no positions at all;  pair (i, j) kept iff j <= i
    o  = softmax(q k^T / sqrt(head_dim) over the kept pairs) v
    h  = x + RMSNorm_post_attn((o * sigmoid(g)) Wo)
    m  = RMSNorm_pre_mlp(h)
    dense layer:  f = (silu(m W1) * (m W3)) W2                 (the first
                  ``num_dense_layers`` layers, width ``intermediate_size``)
    expert layer: s = sigmoid(m Wr)                            float32
                  chosen = top-k of (s + expert_bias)          (bias: no gradient)
                  w = route_scale * s[chosen] / (sum s[chosen] + 1e-20)
                  f = Shared(m) + sum_e w_e Expert_e(m)        (gated SiLU,
                  width ``moe_intermediate_size``; the sum over the chosen
                  experts that are HELD HERE, ``parallel/moe.py:
                  dropless_expert_ffn``)
    x' = h + RMSNorm_post_mlp(f)
    loss = mean over i < S - 1 of -log softmax(W_head RMSNorm(x_L))_i[token_{i+1}]

Plain functions over a dict of arrays, as ``sdar_moe.py``, whose norm,
rotary embedding, chunked head loss and loop over (layer, sequence) these
are.  A layer is of one of three kinds, (dense or experts) x (window or
full); a *run* is a stretch of consecutive layers of one kind
(:func:`layer_runs`), its parameters stacked on a leading axis and run
under ``lax.scan``, each (layer, sequence) under its own ``jax.checkpoint``,
one traced body a run.  Parameters are float32, products run in
``cfg.dtype`` (bf16) with float32 accumulation; norms, the sigmoid scores,
softmax and loss in float32.  The tree: ``embed [vocab, hidden]``; ``runs``,
a list with one dict a run, every leaf with the run's layers on its leading
axis: ``attn_norm``, ``post_attn_norm``, ``pre_mlp_norm``, ``post_mlp_norm``
``[hidden]``, ``wq``, ``wg`` ``[hidden, heads x head_dim]``, ``wk``, ``wv``
``[hidden, kv heads x head_dim]``, ``q_norm``, ``k_norm`` ``[head_dim]``,
``wo [heads x head_dim, hidden]``; a dense run ``mlp_gate``, ``mlp_up``
``[hidden, intermediate]``, ``mlp_down``; an expert run ``router [hidden,
experts]``, ``shared_gate``, ``shared_up`` ``[hidden, shared width]``,
``shared_down``, ``w_gate``, ``w_up`` ``[held, hidden, width]``, ``w_down
[held, width, hidden]`` and, where there is one, ``expert_bias [experts]``;
``final_norm [hidden]``; ``head [hidden, vocab]``
(``benchmarks/jobs/afmoe.py: seeded_params`` makes one).  The step names
itself for the device trace (``docs/timeline.md``): under ``decoder``
``hvd::window_attention`` or ``hvd::full_attention`` (``hvd::qk_rope``
inside them), then
``hvd::dense_mlp`` or ``hvd::moe`` (``::shared``, ``::route``,
``::experts``, ``::combine`` inside it), under ``head``
``hvd::lm_head_loss``.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import scopes as _scopes
from ..utils import get_logger
from .sdar_moe import (Aux, embed, head_loss, heads_first_qkv, rms_norm,
                       through_layers)

SLIDING, FULL = "sliding_attention", "full_attention"
#: Layer types whose flash output and logsumexp are kept across the
#: recomputation of a (layer, sequence), 65 MB each at the published sizes;
#: a type not named runs its forward kernel again in the backward pass.
#: The rule: keep the type whose rerun costs more per byte saved, and as
#: many types as the step has room for.  A full layer's output spares twice
#: the tiles a window layer's does (136 against 70 a head at 8,192
#: positions).  Both fit: with the five layers' kept at four sequences
#: (1.3 GB) the training step of one chip's share of an eight-way split
#: takes 14.77 GiB of a v5e's 15.75 by the compiler's report, since the
#: grouped products read the float32 expert matrices themselves and the
#: expert half keeps only its products' operands and results (PR 34;
#: before, 15.96 with both and 14.74 with the full layer's alone).
KEPT_ATTENTION = (SLIDING, FULL)

#: The model's parts as they appear in an ``op_name`` (``scopes.py``).
PARTS = ("hvd::loss", "hvd::embed", "hvd::layer_loop",
         "hvd::window_attention", "hvd::full_attention", "hvd::dense_mlp",
         "hvd::moe", "hvd::lm_head_loss")


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published configuration under its published names; what a
    deployment sets is below them."""
    vocab_size: int = 200192        # rows held of the embedding and head
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    num_dense_layers: int = 2       # the first layers; the rest hold experts
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144   # a dense layer's width
    moe_intermediate_size: int = 1024
    num_experts: int = 128          # the router's width: ALL experts
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    experts_held: int = 128         # experts whose weights live here ...
    first_expert: int = 0           # ... starting at this one
    expert_axis: Optional[str] = None   # mesh axis the experts are over
    dtype: Any = jnp.bfloat16
    attention_tile: int = 512       # flash tile (queries and keys)
    loss_chunk: int = 2048          # positions a chunk of the head's logits
    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)


def layer_runs(cfg: AfmoeConfig):
    """``[(dense?, window?, layers)]``: the stack as stretches of
    consecutive layers of one kind, in order."""
    kinds = [(i < cfg.num_dense_layers, kind == SLIDING)
             for i, kind in enumerate(cfg.layer_types)]
    if set(cfg.layer_types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {set(cfg.layer_types)}: "
                         f"{SLIDING} or {FULL}")
    if not cfg.route_norm:
        raise ValueError("route_norm false (chosen scores not divided by "
                         "their sum) is not built")
    return [(dense, window, len(list(run)))
            for (dense, window), run in itertools.groupby(kinds)]


def gated_mlp(x, w_gate, w_up, w_down, dtype):
    """``(silu(x W_gate) * (x W_up)) W_down``: three plain products in
    ``dtype``, the gated unit in float32."""
    gate = jnp.dot(x, w_gate.astype(dtype))
    up = jnp.dot(x, w_up.astype(dtype))
    hidden = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return jnp.dot(hidden.astype(dtype), w_down.astype(dtype))


def _attention_half(cfg: AfmoeConfig, window: bool, tables, x, p):
    """``h = x + RMSNorm((o * sigmoid(g)) Wo)`` of one sequence ``x [S,
    hidden]``; ``tables`` are the rotary positions'
    (``qk_rope.rope_tables``), which a window layer alone takes."""
    from ..parallel.flash import (MASK_CAUSAL, flash_attention_heads_first,
                                  window_mask)
    seq, _ = x.shape
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    tile = min(cfg.attention_tile, seq)
    with _scopes.scope("hvd::window_attention" if window
                       else "hvd::full_attention"):
        a = rms_norm(x, p["attn_norm"], eps)
        q, k, v = heads_first_qkv(
            a, p, tables if window else None, cfg.num_attention_heads,
            cfg.num_key_value_heads, eps, dtype)
        gate = jnp.dot(a, p["wg"].astype(dtype))
        attended = flash_attention_heads_first(
            q, k, v, block_q=tile, block_k=tile,
            mask_mode=window_mask(cfg.sliding_window) if window
            else MASK_CAUSAL).transpose(1, 0, 2)
        gated = attended.reshape(seq, -1).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        out = jnp.dot(gated.astype(dtype), p["wo"].astype(dtype))
        return x + rms_norm(out, p["post_attn_norm"], eps)


def _dense_half(cfg: AfmoeConfig, h, p):
    with _scopes.scope("hvd::dense_mlp"):
        m = rms_norm(h, p["pre_mlp_norm"], cfg.rms_norm_eps)
        f = gated_mlp(m, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                      cfg.dtype)
        return h + rms_norm(f, p["post_mlp_norm"], cfg.rms_norm_eps), ()


def _expert_half(cfg: AfmoeConfig, h, p):
    from ..parallel.moe import dropless_expert_ffn
    with _scopes.scope("hvd::moe"):
        m = rms_norm(h, p["pre_mlp_norm"], cfg.rms_norm_eps)
        with _scopes.scope("hvd::moe::shared"):
            shared = gated_mlp(m, p["shared_gate"], p["shared_up"],
                               p["shared_down"], cfg.dtype)
        moe = dropless_expert_ffn(
            m, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
            axis_name=cfg.expert_axis, score_func=cfg.score_func,
            selection_bias=p.get("expert_bias"),
            route_scale=cfg.route_scale)
        f = shared.astype(jnp.float32) + moe.out.astype(jnp.float32)
        out = rms_norm(f, p["post_mlp_norm"], cfg.rms_norm_eps)
        return h + out.astype(h.dtype), (moe.routed_here, moe.chosen)


def _layer(cfg: AfmoeConfig, dense: bool, window: bool, tables):
    """One layer of a kind over one sequence, ``(x [S, hidden], p) -> (x,
    aux)``, under its ``jax.checkpoint``."""
    from ..parallel.flash import SAVED
    mlp = _dense_half if dense else _expert_half
    kind = SLIDING if window else FULL
    keep = kind in KEPT_ATTENTION
    get_logger().info(
        "afmoe: a run of %s layers over %s %s its flash output across the "
        "recomputation", "dense" if dense else "expert", kind,
        "keeps" if keep else "computes again")
    return jax.checkpoint(
        lambda x, p: mlp(cfg, _attention_half(cfg, window, tables, x, p),
                         p),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED)
        if keep else None)


def hidden_states(params: dict, tokens, cfg: AfmoeConfig):
    """``(final hidden states [batch, S, hidden] before the last norm,
    Aux)`` for ``tokens [batch, S]``; ``Aux`` counts the expert layers
    only, in their order."""
    batch, seq = tokens.shape
    from ..parallel.qk_rope import rope_tables
    tables = rope_tables(jnp.arange(seq, dtype=jnp.int32), cfg.head_dim,
                         cfg.rope_theta)
    x = embed(params, tokens, cfg.dtype,
              cfg.hidden_size ** 0.5 if cfg.mup_enabled else None)
    routed_here, chosen = [], []
    with _scopes.scope("decoder"):
        for (dense, window, _), stacked in zip(layer_runs(cfg),
                                               params["runs"]):
            x, aux = through_layers(_layer(cfg, dense, window, tables),
                                    x, stacked)
            if not dense:
                routed_here.append(aux[0].sum(axis=1))
                chosen.append(aux[1].reshape(aux[1].shape[0], batch * seq,
                                             -1))
    if not chosen:      # a stack of dense layers alone routes nothing
        return x, Aux(jnp.zeros((0,), jnp.int32), jnp.zeros(
            (0, batch * seq, cfg.num_experts_per_tok), jnp.int32))
    return x, Aux(jnp.concatenate(routed_here), jnp.concatenate(chosen))


@_scopes.part_scope("hvd::loss")
def loss_fn(params: dict, tokens, cfg: AfmoeConfig):
    """The next-token loss of ``tokens [batch, S]`` and its :class:`Aux`:
    position ``i`` predicts token ``i + 1``, one document a sequence, the
    mean over the ``batch x (S - 1)`` predictions.  ``hvd::loss`` is the
    part of what this function does itself (targets, weights, the mean) and
    of what ``hidden_states`` does between its parts."""
    batch, seq = tokens.shape
    hidden, aux = hidden_states(params, tokens, cfg)
    # Every position goes through the head's chunks; the last of a
    # sequence, which predicts nothing, with weight 0.
    targets = jnp.roll(tokens, -1, axis=1)
    weight = jnp.broadcast_to(
        (jnp.arange(seq) < seq - 1).astype(jnp.float32), (batch, seq))
    total = head_loss(params, hidden.reshape(batch * seq, -1),
                      targets.reshape(-1), weight.reshape(-1), cfg)
    return total / (batch * (seq - 1)), aux
