"""LFM2-MoE: a gated short convolution as the token mixer of most layers,
grouped-query attention in the others, a sigmoid router with a selection
bias behind leading dense layers.

The model of ``LiquidAI/LFM2-8B-A1B`` (``model_type`` ``lfm2_moe``; the
published ``config.json`` and, for what a key does not say, the public
``lfm2_moe`` modelling code).  No bias anywhere, RMSNorm with eps
``norm_eps`` and weight ``w`` (not ``1 + w``).  With ``x [S, hidden]`` a
layer's input and ``D = hidden``:

    x0      = E[tokens]
    a       = RMSNorm_op(x)
    conv layer (``layer_types`` "conv"):
      [B | C | x~] = a W_in           W_in [D, 3D], split in that column order
      u       = B * x~
      z_t     = sum_{j < L} w[:, j] u_{t - (L - 1) + j}   depthwise, causal,
                zero history, w [D, L] (L = ``conv_L_cache``), no activation
      h       = x + (C * z) W_out     W_out [D, D]
    attention layer ("full_attention"):
      q, k    = RMSNorm_q(a W_q), RMSNorm_k(a W_k) a head of D / heads,
                rotated (half-split, theta ``rope_theta``); v = a W_v:
                ``num_attention_heads`` query heads over
                ``num_key_value_heads`` key/value heads
      o       = softmax_{j <= i}(q_i . k_j / sqrt(head_dim)) v
      h       = x + o W_o
    m       = RMSNorm_ffn(h)
    dense layer (the first ``num_dense_layers``):
              f = (silu(m W1) * (m W3)) W2          width ``intermediate_size``
    expert layer: s = sigmoid(m Wr)                 float32, all experts
              chosen = top-k of (s + expert_bias)   (no gradient)
              w = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-6)
              f = sum_e w_e Expert_e(m)   (gated SiLU, width
              ``moe_intermediate_size``; the sum over the chosen experts
              HELD HERE, ``parallel/moe.py: dropless_expert_ffn``; no
              shared expert)
    x'      = h + f
    loss    = mean over i < S - 1 of
              -log softmax(W_head RMSNorm_f(x_L))_i[t_{i+1}]    (untied)

Plain functions over a dict of arrays, from the parts of ``decoder.py``;
runs of layers differ in their parameter trees (two token mixers beside two
second halves), as in ``kimi_linear.py``.  Parameters are float32, products
run in ``cfg.dtype`` (bf16) with float32 accumulation; the convolution's
pass (float32 inside), norms, router, softmax and loss in float32.  The tree:
``embed [vocab, hidden]``; ``runs``, a list of dicts, one a stretch of
consecutive layers of one kind (``layer_runs``), every leaf with the run's
layers on its leading axis: ``attn_norm``, ``mlp_norm`` ``[hidden]``; a
convolution run ``w_in [hidden, 3 hidden]``, ``conv [hidden, L]``, ``w_out
[hidden, hidden]``; an attention run ``sdar_moe``'s ``wq [hidden, heads x
head_dim]``, ``wk``, ``wv`` ``[hidden, kv heads x head_dim]``,
``q_norm``, ``k_norm`` ``[head_dim]``, ``wo [heads x head_dim, hidden]``;
a dense run ``mlp_gate``, ``mlp_up`` ``[hidden, intermediate]``,
``mlp_down``; an expert run ``router [hidden, experts]``, ``w_gate``,
``w_up`` ``[held, hidden, width]``, ``w_down [held, width, hidden]`` and,
where there is one, ``expert_bias [experts]``; ``final_norm [hidden]``;
``head [hidden, vocab]`` (``benchmarks/jobs/lfm2_moe.py: seeded_params``
makes one).  The step names itself for the device trace
(``docs/timeline.md``): under ``decoder`` ``hvd::short_conv``
(``::project`` the product with ``W_in``; ``::conv`` the pass of
``parallel/gated_conv.py``, ``B * x~``, the convolution and ``C *``, and
the sum of its weight's partials; ``::out`` ``W_out`` and the residual sum)
or ``hvd::full_attention`` (inside it ``hvd::qk_rope``: the head norms and
the rotation, which ``parallel/qk_rope.py`` runs as plain passes at heads
of 64), then ``hvd::dense_mlp`` or ``hvd::moe`` (``::route``,
``::experts``, ``::combine``), and under ``head`` ``hvd::lm_head_loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import scopes as _scopes
from .decoder import (dense_half, embed, group_runs, heads_first_qkv,
                      next_token_loss, rms_norm, through_runs)

CONV, ATTENTION = "conv", "full_attention"

#: The model's parts as they appear in an ``op_name`` (``scopes.py``).
PARTS = ("hvd::loss", "hvd::embed", "hvd::layer_loop", "hvd::short_conv",
         "hvd::full_attention", "hvd::dense_mlp", "hvd::moe",
         "hvd::lm_head_loss")

_PUBLISHED_TYPES = tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published configuration under its published names; what a
    deployment sets is below them."""
    vocab_size: int = 65536         # rows held of the embedding and head
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    num_dense_layers: int = 2       # the first layers; the rest hold experts
    intermediate_size: int = 7168   # a dense layer's width
    moe_intermediate_size: int = 1792
    num_experts: int = 32           # the router's width: ALL experts
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3           # taps of the short convolution
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    route_norm_eps: float = 1e-6    # under the chosen scores' sum
    experts_held: int = 32          # experts whose weights live here ...
    first_expert: int = 0           # ... starting at this one
    expert_axis: Optional[str] = None   # mesh axis the experts are over
    dtype: Any = jnp.bfloat16
    attention_tile: int = 512       # flash tile (queries and keys)
    loss_chunk: int = 2048          # positions a chunk of the head's logits

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_norm_eps(self) -> float:    # the name ``decoder``'s parts read
        return self.norm_eps


def layer_runs(cfg: Lfm2MoeConfig):
    """``[(mixer, dense?, layers)]``: the stack as stretches of consecutive
    layers of one kind, in published order."""
    if len(cfg.layer_types) != cfg.num_hidden_layers \
            or set(cfg.layer_types) - {CONV, ATTENTION}:
        raise ValueError(
            f"layer_types {list(cfg.layer_types)} is not {CONV!r} or "
            f"{ATTENTION!r} for each of {cfg.num_hidden_layers} layers")
    kinds = [(mixer, i < cfg.num_dense_layers)
             for i, mixer in enumerate(cfg.layer_types)]
    return [(*kind, count) for kind, count in group_runs(kinds)]


def _conv_half(cfg: Lfm2MoeConfig, x, p):
    """``h = x + (C * conv(B * x~)) W_out`` of one sequence ``x [S,
    hidden]``."""
    from ..parallel.gated_conv import gated_conv
    dot = lambda t, w: jnp.dot(t, w.astype(cfg.dtype))
    with _scopes.scope("hvd::short_conv"):
        a = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        with _scopes.scope("hvd::short_conv::project"):
            bcx = dot(a, p["w_in"])
        with _scopes.scope("hvd::short_conv::conv"):
            y = gated_conv(bcx, p["conv"])
        with _scopes.scope("hvd::short_conv::out"):
            return x + dot(y, p["w_out"])


def _attention_half(cfg: Lfm2MoeConfig, tables, x, p):
    """``h = x + o W_o`` of one sequence ``x [S, hidden]`` through causal
    grouped-query attention with normed, rotated query and key heads;
    ``tables`` are the rotary positions' (``qk_rope.rope_tables``)."""
    from ..parallel.flash import MASK_CAUSAL, flash_attention_heads_first
    seq, _ = x.shape
    tile = min(cfg.attention_tile, seq)
    with _scopes.scope("hvd::full_attention"):
        a = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q, k, v = heads_first_qkv(a, p, tables, cfg.num_attention_heads,
                                  cfg.num_key_value_heads, cfg.norm_eps,
                                  cfg.dtype)
        attended = flash_attention_heads_first(
            q, k, v, mask_mode=MASK_CAUSAL, block_q=tile, block_k=tile)
        return x + jnp.dot(attended.transpose(1, 0, 2).reshape(seq, -1),
                           p["wo"].astype(cfg.dtype))


def _expert_half(cfg: Lfm2MoeConfig, h, p):
    from ..parallel.moe import dropless_expert_ffn
    with _scopes.scope("hvd::moe"):
        m = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
        moe = dropless_expert_ffn(
            m, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
            axis_name=cfg.expert_axis, score_func="sigmoid",
            selection_bias=p.get("expert_bias"),
            route_scale=cfg.routed_scaling_factor,
            renorm_eps=cfg.route_norm_eps)
        return h + moe.out, (moe.routed_here, moe.chosen)


def _layer(cfg: Lfm2MoeConfig, mixer: str, dense: bool, tables):
    """One layer of a kind over one sequence, ``(x [S, hidden], p) -> (x,
    aux)``, under its ``jax.checkpoint``.  An attention layer keeps its
    flash output and logsumexp across the recomputation; a convolution
    layer keeps nothing: its pass reads the projection's output, which the
    recomputation makes again."""
    from ..parallel.flash import SAVED
    mlp = dense_half if dense else _expert_half
    if mixer == CONV:
        return jax.checkpoint(lambda x, p: mlp(cfg, _conv_half(cfg, x, p),
                                               p))
    return jax.checkpoint(
        lambda x, p: mlp(cfg, _attention_half(cfg, tables, x, p), p),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED))


def hidden_states(params: dict, tokens, cfg: Lfm2MoeConfig):
    """``(final hidden states [batch, S, hidden] before the last norm,
    Aux)`` for ``tokens [batch, S]``; ``Aux`` counts the expert layers
    only, in their order."""
    from ..parallel.qk_rope import rope_tables
    seq = tokens.shape[1]
    tables = rope_tables(jnp.arange(seq, dtype=jnp.int32), cfg.head_dim,
                         cfg.rope_theta)
    x = embed(params, tokens, cfg.dtype)
    return through_runs(
        x, layer_runs(cfg), params["runs"],
        lambda mixer, dense, _: _layer(cfg, mixer, dense, tables),
        cfg.num_experts_per_tok)


@_scopes.part_scope("hvd::loss")
def loss_fn(params: dict, tokens, cfg: Lfm2MoeConfig):
    """The next-token loss of ``tokens [batch, S]`` and its ``Aux``:
    position ``i`` predicts token ``i + 1``, one document a sequence, the
    mean over the ``batch x (S - 1)`` predictions.  ``hvd::loss`` is the
    part of what ``decoder.next_token_loss`` does itself (targets, weights,
    the mean) and of what ``hidden_states`` does between its parts."""
    hidden, aux = hidden_states(params, tokens, cfg)
    return next_token_loss(params, hidden, tokens, cfg), aux
