"""SDAR-MoE: a modern decoder block trained by block diffusion.

The model of ``JetLM/SDAR-30B-A3B-Chat`` (``model_type`` ``sdar_moe``): a
pre-norm decoder whose every layer is

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))

with no bias anywhere.  ``Attn``: grouped-query attention (``num_heads``
query heads over ``num_kv_heads`` key/value heads of ``head_dim``), RMSNorm
with a learned weight over each query and each key head, rotary positions
over the whole head.  ``MoE``: softmax router over ``num_experts``, the
``experts_per_token`` largest, renormalised, gated SiLU experts of width
``expert_width``; no shared expert (``parallel/moe.py:
dropless_expert_ffn``, which is told which experts are held here and drops
no token).  The embedding and the head are untied.

Block-diffusion training (BD3-LM, arXiv:2503.09573, as SDAR adopts it): a
sequence of ``L`` clean tokens ``x0`` in blocks of ``block_length``; block
``b`` draws ``t_b`` in (0, 1] and each of its tokens becomes ``[MASK]``
with probability ``t_b``.  The model runs once over the
``2L`` positions ``[xt ; x0]`` (position ``i`` of either copy has rotary
position ``i``) under the block-diffusion mask of ``parallel/flash.py``,
and the loss is the ``1 / t_b``-weighted cross-entropy of the clean token
at every masked position, over the noised copy only (no next-token shift),
divided by ``batch x L`` (:func:`loss_fn`).

Plain functions over a dict of arrays, not flax, from the parts of
``decoder.py``: the layers are stacked on a leading axis and run under
``lax.scan`` with one ``jax.checkpoint`` a layer; parameters are float32,
products in ``cfg.dtype`` (bf16) with float32 accumulation, norms, softmax
and loss in float32.  The tree:
``embed [vocab, hidden]``; ``layers``, every leaf with the layers on its
leading axis: ``attn_norm``, ``moe_norm`` ``[hidden]``, ``wq [hidden, heads
x head_dim]``, ``wk``, ``wv`` ``[hidden, kv heads x head_dim]``, ``q_norm``,
``k_norm`` ``[head_dim]``, ``wo [heads x head_dim, hidden]``, ``router
[hidden, experts]``, ``w_gate``, ``w_up`` ``[held, hidden, width]``,
``w_down [held, width, hidden]``; ``final_norm [hidden]``; ``head [hidden,
vocab]`` (``benchmarks/jobs/sdar_moe.py: seeded_params`` makes one, and
``seeded_batch`` a corrupted batch).  The step names
itself for the device trace (``docs/timeline.md``): under ``decoder``
``hvd::bd_attention`` (inside it ``hvd::qk_rope``: the head norms, the
rotary embedding and the head-major layout, one pass of
``parallel/qk_rope.py``) and ``hvd::moe`` (``::route``, ``::experts``,
``::combine`` inside it), under ``head`` ``hvd::lm_head_loss``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .. import scopes as _scopes
from .decoder import (Aux, embed, head_loss, heads_first_qkv, rms_norm,
                      routing, through_layers)


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int = 151936        # rows held of the embedding and head;
    # the last id is [MASK]
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_width: int = 768
    num_experts: int = 128          # the router's width: ALL experts
    experts_per_token: int = 8
    experts_held: int = 128         # experts whose weights live here ...
    first_expert: int = 0           # ... starting at this one
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    dtype: Any = jnp.bfloat16
    expert_axis: Optional[str] = None   # mesh axis the experts are over
    attention_tile: int = 512       # flash tile (queries and keys)
    loss_chunk: int = 4096          # positions a chunk of the head's logits


#: The model's parts as they appear in an ``op_name`` (``scopes.py``).
PARTS = ("hvd::loss", "hvd::embed", "hvd::layer_loop", "hvd::bd_attention",
         "hvd::moe", "hvd::lm_head_loss")


def _layer(cfg: SdarMoeConfig, tables, mask_mode, x, p):
    """One decoder layer over one sequence ``x [S, hidden]``; ``p`` is the
    layer's slice of the stacked parameters, ``tables`` the rotary
    positions' (``qk_rope.rope_tables``)."""
    from ..parallel.flash import flash_attention_heads_first
    from ..parallel.moe import dropless_expert_ffn
    seq, d = x.shape
    dtype = cfg.dtype
    tile = min(cfg.attention_tile, seq)
    with _scopes.scope("hvd::bd_attention"):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v = heads_first_qkv(h, p, tables, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.rms_norm_eps, dtype)
        attended = flash_attention_heads_first(
            q, k, v, mask_mode=mask_mode, block_q=tile, block_k=tile)
        x = x + jnp.dot(attended.transpose(1, 0, 2).reshape(seq, -1),
                        p["wo"].astype(dtype))

    with _scopes.scope("hvd::moe"):
        h = rms_norm(x, p["moe_norm"], cfg.rms_norm_eps)
        moe = dropless_expert_ffn(
            h, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            top_k=cfg.experts_per_token, first_expert=cfg.first_expert,
            axis_name=cfg.expert_axis)
        x = x + moe.out
    return x, (moe.routed_here, moe.chosen)


def hidden_states(params: dict, tokens, cfg: SdarMoeConfig, mask_mode):
    """``(final hidden states [batch, S, hidden] before the last norm,
    Aux)`` for ``tokens [batch, S]`` under ``mask_mode`` (a mode of
    ``parallel/flash.py``); ``S`` is ``2L`` under block diffusion, whose
    two copies share their rotary positions.

    The layers run under ``lax.scan`` and, inside a layer, the sequences
    one after the other, each (layer, sequence) under its own
    ``jax.checkpoint``: what the backward pass keeps is one ``[S, hidden]``
    input and the attention kernel's output a layer and sequence, and what
    it holds while it recomputes is one sequence's worth of one layer."""
    from ..parallel.flash import MASK_BLOCK_DIFFUSION, SAVED
    from ..parallel.qk_rope import rope_tables
    batch, seq = tokens.shape
    positions = jnp.arange(seq, dtype=jnp.int32)
    if isinstance(mask_mode, tuple) and mask_mode[0] == MASK_BLOCK_DIFFUSION:
        positions = positions % mask_mode[2]
    tables = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    x = embed(params, tokens, cfg.dtype)
    # Recomputed in the backward pass, but for the flash kernel's output
    # and logsumexp (65 MB a layer and sequence at the published sizes):
    # kept, they spare the forward kernel's second run.
    one = jax.checkpoint(
        lambda x, p: _layer(cfg, tables, mask_mode, x, p),
        policy=jax.checkpoint_policies.save_only_these_names(*SAVED))
    with _scopes.scope("decoder"):
        x, aux = through_layers(one, x, params["layers"])
    return x, Aux(*routing(aux, batch, seq))


@_scopes.part_scope("hvd::loss")
def loss_fn(params: dict, xt, x0, weight, cfg: SdarMoeConfig):
    """The block-diffusion loss of one batch and its :class:`Aux`: ``xt``,
    ``x0`` ``[batch, L]`` noised and clean tokens, ``weight [batch, L]``
    ``1 / t_b`` at the masked positions and 0 elsewhere.  ``hvd::loss`` is
    the part of what this function does itself: the two copies side by
    side, the noised half cut out for the head, the mean."""
    from ..parallel.flash import block_diffusion_mask
    batch, length = x0.shape
    hidden, aux = hidden_states(
        params, jnp.concatenate([xt, x0], axis=1), cfg,
        block_diffusion_mask(cfg.block_length, length))
    noised = hidden[:, :length].reshape(batch * length, -1)
    total = head_loss(params, noised, x0.reshape(-1), weight.reshape(-1),
                      cfg)
    return total / (batch * length), aux
