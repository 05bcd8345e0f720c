"""JoyAI-LLM-Flash: latent attention (MLA), a sigmoid router with a shared
expert behind a leading dense layer, and a multi-token-prediction module on
the shared embedding and head.

The model of ``jdopensource/JoyAI-LLM-Flash`` (``model_type``
``joyai_llm_flash``; the published ``config.json``, whose keys are
DeepSeek-V3's, and for what a key does not say DeepSeek-V3's report,
arXiv:2412.19437).  No bias anywhere, two RMSNorms a layer and one on each
latent.  With ``x [S, hidden]`` a layer's input:

    x0      = E[tokens]
    a       = RMSNorm_in(x)
    c_q     = RMSNorm_qa(a W_qa)                              [S, q_lora_rank]
    q       = c_q W_qb -> [S, heads, nope + rope] = (q_nope, q_rope)
    (c_kv, k_rope) = split(a W_kva)     [S, kv_lora_rank], [S, rope]: ONE head
    (k_nope, v)    = split(RMSNorm_kva(c_kv) W_kvb -> [S, heads, nope + v])
    q_rope, k_rope = rotary over their ``rope`` dimensions alone, pairs
                     ``(2i, 2i + 1)`` (``rope_interleave``), no scaling
    s_ij    = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(nope + rope)
              for j <= i, a head;    o = softmax_j(s) v        [S, heads, v]
    h       = x + o W_o
    m       = RMSNorm_post(h)
    dense layer (the first ``first_k_dense_replace``):
              f = (silu(m W1) * (m W3)) W2          width ``intermediate_size``
    expert layer: s = sigmoid(m Wr)                 float32
              chosen = top-k of (s + e_score_correction_bias)   (no gradient)
              w = routed_scaling_factor * s[chosen] / (sum s[chosen] + 1e-20)
              f = Shared(m) + sum_e w_e Expert_e(m)   (gated SiLU, width
              ``moe_intermediate_size``; the sum over the chosen experts
              HELD HERE, ``parallel/moe.py: dropless_expert_ffn``)
    x'      = h + f
    L_main  = mean over i < S - 1 of
              -log softmax(W_head RMSNorm_f(x_L))_i[t_{i+1}]
    MTP, depth 1 (arXiv:2412.19437, section 2.2), for i < S - 1:
      u_i   = [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(x_L,i)] W_eh     (E shared)
      y     = Block_mtp(u)          one further expert layer, its own weights
      L_mtp = mean over i < S - 2 of
              -log softmax(W_head RMSNorm_mtp(y))_i[t_{i+2}]  (W_head shared)
    loss    = L_main + mtp_loss_weight * L_mtp

How the one rotary key reaches the heads: the caller broadcasts it and the
flash kernels see keys ``nope + rope`` wide beside values ``v`` wide
(``parallel/flash.py``: one product over the whole key); its gradient is the
sum over the heads of the kernel's dK.  The MTP block runs over all ``S``
rows, the last a dummy (fed ``E[t_0]``) that causal attention shows to no
other row and no loss weighs, so ``S`` stays a multiple of the tile.

Plain functions over a dict of arrays, from the parts of ``decoder.py``:
its norm, dense and shared-expert halves, chunked head loss and loop over
(layer, sequence).  Parameters are float32, products run in
``cfg.dtype`` (bf16) with float32 accumulation; norms, the sigmoid scores,
softmax and loss in float32.  The tree: ``embed [vocab, hidden]``; ``runs``,
a list of two dicts, the dense layers and then the expert layers, every leaf
with the run's layers on its leading axis: ``attn_norm``, ``mlp_norm``
``[hidden]``, ``w_qa [hidden, q_lora_rank]``, ``qa_norm [q_lora_rank]``,
``w_qb [q_lora_rank, heads x (nope + rope)]``, ``w_kva [hidden, kv_lora_rank
+ rope]``, ``kva_norm [kv_lora_rank]``, ``w_kvb [kv_lora_rank, heads x (nope
+ v)]``, ``wo [heads x v, hidden]``; the dense run ``mlp_gate``, ``mlp_up``
``[hidden, intermediate]``, ``mlp_down``; the expert run ``router [hidden,
experts]``, ``shared_gate``, ``shared_up`` ``[hidden, shared width]``,
``shared_down``, ``w_gate``, ``w_up`` ``[held, hidden, width]``, ``w_down
[held, width, hidden]`` and, where there is one, ``e_score_correction_bias
[experts]``; ``final_norm [hidden]``; ``head [hidden, vocab]``; ``mtp``:
``enorm``, ``hnorm``, ``mtp_norm`` ``[hidden]``, ``w_eh [2 x hidden,
hidden]`` and ``block``, an expert run of one layer
(``benchmarks/jobs/joyai_flash.py: seeded_params`` makes one).  The step
names itself for the device trace (``docs/timeline.md``): under ``decoder``
``hvd::mla_attention`` (``::compress``, ``::expand``, ``::out`` inside it),
then ``hvd::dense_mlp`` or ``hvd::moe`` (``::shared``, ``::route``,
``::experts``, ``::combine``), under ``head`` ``hvd::lm_head_loss``, and
under ``mtp`` ``hvd::mtp`` around the whole module, its block's scopes and
its head loss inside it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .. import scopes as _scopes
from ..utils import get_logger
from .decoder import (Aux, dense_half, embed, expert_half, next_token_loss,
                      rms_norm, routing, through_layers)

#: Whether a layer's flash output and logsumexp are kept across the
#: recomputation of its (layer, sequence): 68 MB each at the published
#: sizes, 1.6 GB over the six attention layers of one chip's share at four
#: sequences, beside 10.9 GB of state and gradient.  They fit: 14.54 GiB of
#: a v5e's 15.75 by the compiler's report, since the grouped products read
#: the float32 expert matrices themselves and the expert half keeps only
#: its products' operands and results (PR 34; before, 15.51 with them and
#: 14.25 without).  Off, the forward kernel runs again in the backward pass.
KEEP_ATTENTION = True

#: The model's parts as they appear in an ``op_name`` (``scopes.py``);
#: ``hvd::mtp`` is the part of what the module does outside its block's
#: halves, its embedding and its pass through the head.
PARTS = ("hvd::loss", "hvd::embed", "hvd::layer_loop", "hvd::mla_attention",
         "hvd::dense_mlp", "hvd::moe", "hvd::lm_head_loss", "hvd::mtp")


@dataclasses.dataclass(frozen=True)
class JoyaiFlashConfig:
    """The published configuration under its published names; what a
    deployment sets is below them."""
    vocab_size: int = 129280        # rows held of the embedding and head
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1  # the first layers; the rest hold experts
    intermediate_size: int = 7168   # a dense layer's width
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256     # the router's width: ALL experts
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.1    # lambda; the config has no key for it
    experts_held: int = 256         # experts whose weights live here ...
    first_expert: int = 0           # ... starting at this one
    expert_axis: Optional[str] = None   # mesh axis the experts are over
    dtype: Any = jnp.bfloat16
    attention_tile: int = 512       # flash tile (queries and keys)
    loss_chunk: int = 2048          # positions a chunk of the head's logits

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers {self.num_nextn_predict_layers}: "
                "a multi-token-prediction depth over 1 is not built")


def rotary_interleaved(x, positions, theta: float):
    """Rotary embedding over the whole last dimension of ``x [S, heads,
    rope]`` in the interleaved convention (``rope_interleave``): the pair
    ``(x[2i], x[2i + 1])`` is the complex number that position ``t`` turns
    by ``t * theta ** (-2i / rope)``; the result keeps the layout."""
    rope = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], rope // 2, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _attention_half(cfg: JoyaiFlashConfig, positions, x, p):
    """``h = x + o W_o`` of one sequence ``x [S, hidden]``."""
    from ..parallel.flash import MASK_CAUSAL, flash_attention
    seq, _ = x.shape
    dtype, eps, heads = cfg.dtype, cfg.rms_norm_eps, cfg.num_attention_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    tile = min(cfg.attention_tile, seq)
    dot = lambda t, w: jnp.dot(t, w.astype(dtype))
    with _scopes.scope("hvd::mla_attention"):
        a = rms_norm(x, p["attn_norm"], eps)
        with _scopes.scope("hvd::mla_attention::compress"):
            c_q = rms_norm(dot(a, p["w_qa"]), p["qa_norm"], eps)
            kva = dot(a, p["w_kva"])
            c_kv = rms_norm(kva[:, :cfg.kv_lora_rank], p["kva_norm"], eps)
            k_rope = kva[:, None, cfg.kv_lora_rank:]        # one head
        with _scopes.scope("hvd::mla_attention::expand"):
            q = dot(c_q, p["w_qb"]).reshape(seq, heads, nope + rope)
            kv = dot(c_kv, p["w_kvb"]).reshape(seq, heads,
                                               nope + cfg.v_head_dim)
            q = jnp.concatenate(
                [q[..., :nope],
                 rotary_interleaved(q[..., nope:], positions,
                                    cfg.rope_theta)], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(
                    rotary_interleaved(k_rope, positions, cfg.rope_theta),
                    (seq, heads, rope))], axis=-1)
            v = kv[..., nope:]
        attended = flash_attention(q[None], k[None], v[None],
                                   mask_mode=MASK_CAUSAL, block_q=tile,
                                   block_k=tile)
        with _scopes.scope("hvd::mla_attention::out"):
            return x + dot(attended.reshape(seq, -1), p["wo"])


def _layer(cfg: JoyaiFlashConfig, dense: bool, positions):
    """One layer of a kind over one sequence, ``(x [S, hidden], p) -> (x,
    aux)``, under its ``jax.checkpoint``."""
    from ..parallel.flash import SAVED
    mlp = dense_half if dense else functools.partial(
        expert_half, top_k=cfg.num_experts_per_tok)
    get_logger().info(
        "joyai_flash: a run of %s layers %s its flash output across the "
        "recomputation", "dense" if dense else "expert",
        "keeps" if KEEP_ATTENTION else "computes again")
    return jax.checkpoint(
        lambda x, p: mlp(cfg, _attention_half(cfg, positions, x, p), p),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED)
        if KEEP_ATTENTION else None)


def hidden_states(params: dict, tokens, cfg: JoyaiFlashConfig):
    """``(final hidden states [batch, S, hidden] before the last norm,
    (routed here, chosen))`` for ``tokens [batch, S]``, the expert layers
    alone and in their order."""
    batch, seq = tokens.shape
    positions = jnp.arange(seq, dtype=jnp.int32)
    x = embed(params, tokens, cfg.dtype)
    dense, experts = params["runs"]
    with _scopes.scope("decoder"):
        x, _ = through_layers(_layer(cfg, True, positions), x, dense)
        x, aux = through_layers(_layer(cfg, False, positions), x, experts)
    return x, routing(aux, batch, seq)


def mtp_hidden_states(params: dict, tokens, hidden, cfg: JoyaiFlashConfig):
    """``(y [batch, S, hidden] before the module's last norm, (routed here,
    chosen))``: row ``i`` joins the embedding of token ``i + 1`` to the
    stack's state of row ``i`` (before ``final_norm``); the last row of a
    sequence is the dummy."""
    batch, seq = tokens.shape
    mtp, eps = params["mtp"], cfg.rms_norm_eps
    ahead = embed(params, jnp.roll(tokens, -1, axis=1), cfg.dtype)
    joined = jnp.concatenate([rms_norm(ahead, mtp["enorm"], eps),
                              rms_norm(hidden, mtp["hnorm"], eps)], axis=-1)
    u = jnp.dot(joined, mtp["w_eh"].astype(cfg.dtype))
    y, aux = through_layers(
        _layer(cfg, False, jnp.arange(seq, dtype=jnp.int32)), u,
        mtp["block"])
    return y, routing(aux, batch, seq)


def losses(params: dict, tokens, cfg: JoyaiFlashConfig):
    """``(L_main, L_mtp, Aux)`` of ``tokens [batch, S]``, one document a
    sequence: row ``i`` of the stack is judged on token ``i + 1`` (the mean
    over ``batch x (S - 1)`` predictions), row ``i`` of the MTP module on
    token ``i + 2`` (``batch x (S - 2)``).  ``Aux`` counts the expert
    layers in their order and the module's block last."""
    hidden, (routed_here, chosen) = hidden_states(params, tokens, cfg)
    main = next_token_loss(params, hidden, tokens, cfg)
    if not cfg.num_nextn_predict_layers:
        return main, jnp.zeros_like(main), Aux(routed_here, chosen)
    # JAX writes the outermost scope of a differentiated function into its
    # ``jvp(...)`` marker; ``mtp`` takes that place, as ``decoder`` does.
    with _scopes.scope("mtp"), _scopes.scope("hvd::mtp"):
        y, (mtp_routed, mtp_chosen) = mtp_hidden_states(params, tokens,
                                                        hidden, cfg)
        ahead = next_token_loss({"head": params["head"],
                                 "final_norm": params["mtp"]["mtp_norm"]},
                                y, tokens, cfg, 2)
    return main, ahead, Aux(jnp.concatenate([routed_here, mtp_routed]),
                            jnp.concatenate([chosen, mtp_chosen]))


@_scopes.part_scope("hvd::loss")
def loss_fn(params: dict, tokens, cfg: JoyaiFlashConfig):
    """``L_main + mtp_loss_weight * L_mtp`` and ``(Aux, L_main, L_mtp)``.
    ``hvd::loss`` is the part of what ``losses`` does between its parts
    (targets, weights, the two means) and of the weighted sum."""
    main, ahead, aux = losses(params, tokens, cfg)
    return main + cfg.mtp_loss_weight * ahead, (aux, main, ahead)
