"""Kimi-Linear: Kimi Delta Attention (a gated delta rule with a decay for
every channel, a recurrent state a head) in three layers of four, latent
attention without positions in the fourth, a sigmoid router with a shared
expert behind a leading dense layer.

The model of ``moonshotai/Kimi-Linear-48B-A3B-Instruct`` (``model_type``
``kimi_linear``; the published ``config.json`` and, for what a key does not
say, the Kimi Linear report, arXiv:2510.26692, and the family's public
modelling code).  No bias anywhere, two RMSNorms a layer.  With ``x [S,
hidden]`` a layer's input, ``H`` heads of ``d`` (``linear_attn_config``:
32 of 128) and ``P = H d``:

    x0      = E[tokens]
    a       = RMSNorm_in(x)
    KDA layer (``linear_attn_config.kda_layers``, counted from 1):
      q~, k~, v~ = SiLU(conv(a W_q)), SiLU(conv(a W_k)), SiLU(conv(a W_v))
                 [S, H, d]; conv: causal, depthwise, width
                 ``short_conv_kernel_size``, zero history, a weight [P, 4]
                 each: y_t = sum_j w[:, j] x_{t - 3 + j}
      q, k    = q~ / sqrt(|q~|^2 + 1e-6), k~ / sqrt(|k~|^2 + 1e-6) a head;
                q <- q d^-1/2;  v = v~
      g       = -exp(A_log_h) * softplus((a W_fa) W_fb + dt_bias)
                [S, H, d] <= 0, float32: the log of the decay
      beta    = sigmoid(a W_b)                                   [S, H]
      a head, with S_0 = 0 in R^{d x d} (keys by values), t = 1..S:
        S'_t  = Diag(exp(g_t)) S_{t-1}
        S_t   = S'_t + beta_t k_t (v_t - k_t^T S'_t)^T
        o_t   = S_t^T q_t            (``parallel/kda.py``: the chunked form)
      y       = RMSNorm_o(o) (a head, weight [d]) * sigmoid((a W_ga) W_gb)
      h       = x + y W_o
    latent layer (``full_attn_layers``; ``q_lora_rank`` null,
    ``mla_use_nope``: no rotary embedding at all):
      q       = a W_q -> [S, heads, nope + rope]
      (c_kv, k_pe) = split(a W_kva)   [S, kv_lora_rank], [S, rope]: ONE head
      (k_nope, v)  = split(RMSNorm_kva(c_kv) W_kvb -> [S, heads, nope + v])
      k       = [k_nope ; k_pe broadcast over the heads]
      o       = softmax_{j <= i}(q_i . k_j / sqrt(nope + rope)) v
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
    loss    = mean over i < S - 1 of
              -log softmax(W_head RMSNorm_f(x_L))_i[t_{i+1}]

Plain functions over a dict of arrays, from the parts of ``decoder.py``;
the latent half is ``joyai_flash.py``'s without the query latent and the
rotation; the runs of layers differ in their parameter trees (two token
mixers, two second halves), not in a mask.
Parameters are float32, products run in ``cfg.dtype`` (bf16) with float32
accumulation; norms, the gates (``g``, ``beta``, both sigmoids), the scan's
state, softmax and loss in float32.  The tree: ``embed [vocab, hidden]``;
``runs``, a list of dicts, one a stretch of consecutive layers of one kind
(``layer_runs``), every leaf with the run's layers on its leading axis:
``attn_norm``, ``mlp_norm`` ``[hidden]``; a KDA run ``w_q``, ``w_k``,
``w_v`` ``[hidden, P]``, ``conv_q``, ``conv_k``, ``conv_v`` ``[P, 4]``,
``A_log [H]``, ``dt_bias [P]``, ``w_fa``, ``w_ga`` ``[hidden, d]``,
``w_fb``, ``w_gb`` ``[d, P]``, ``w_b [hidden, H]``, ``o_norm [d]``, ``w_o
[P, hidden]``; a latent run ``mla_wq [hidden, heads x (nope + rope)]``,
``w_kva [hidden, kv_lora_rank + rope]``, ``kva_norm [kv_lora_rank]``,
``w_kvb [kv_lora_rank, heads x (nope + v)]``, ``wo [heads x v, hidden]``; a
dense run ``mlp_gate``, ``mlp_up`` ``[hidden, intermediate]``,
``mlp_down``; an expert run ``router [hidden, experts]``, ``shared_gate``,
``shared_up`` ``[hidden, shared width]``, ``shared_down``, ``w_gate``,
``w_up`` ``[held, hidden, width]``, ``w_down [held, width, hidden]`` and,
where there is one, ``e_score_correction_bias [experts]``; ``final_norm
[hidden]``; ``head [hidden, vocab]`` (``benchmarks/jobs/kimi_linear.py:
seeded_params`` makes one).  The step names itself for the device trace
(``docs/timeline.md``): under ``decoder`` ``hvd::kda_attention``
(``::project`` the products; ``::conv`` the three passes of
``parallel/kda_surround.py: short_conv_silu``, each a convolution, SiLU and
for ``q`` and ``k`` the L2 norm, and the sums of the convolutions'
weight gradients; ``::gates`` the decay's pass and ``beta``; ``::scan``
around the scan's Pallas calls and nothing else; ``::out`` the gated
norm's pass and ``W_o``) or ``hvd::mla_attention``
(``::compress``, ``::expand``, ``::out``), then ``hvd::dense_mlp`` or
``hvd::moe`` (``::shared``, ``::route``, ``::experts``, ``::combine``), and
under ``head`` ``hvd::lm_head_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import scopes as _scopes
from .decoder import (dense_half, embed, expert_half, group_runs,
                      next_token_loss, rms_norm, through_runs)

KDA, MLA = "kda", "mla"
#: KDA layers, counted from 1 as ``kda_layers`` counts them, whose scan
#: keeps its output and the chunks' starting states (``kda.SAVED``; the
#: states are 268 MB a (layer, sequence) at 8,192 positions) across the
#: recomputation, so that its forward kernel runs once; a run of layers
#: (``layer_runs``) that holds one is kept whole, and a KDA layer not kept
#: runs its forward kernel again in the backward pass.  As many as the step
#: has room for.  The training step of one chip's share of a
#: thirty-two-way expert split (published layers 1-5), by the TPU
#: compiler's report for a v5e (``tools/step_memory.py``, of 15.75 GiB):
#: 12.20 GiB with none kept; 13.43 with layers 1 and 5 (two runs of one
#: layer), nothing re-laid or run again to fit; layers 2 and 3, one run and
#: one scan over both, do not fit (15.77 GiB: 24 MB over), nor do all four.
#: On the TPU a recomputation differs from its forward pass in the last
#: bits, so a kept layer's backward pass, which reads the forward pass's
#: own output, does not give the bits of one that keeps nothing: the
#: gradients come out nearer the float32 reference (``PERF.md``, section 6).
KEPT_KDA = (1, 5)

#: The model's parts as they appear in an ``op_name`` (``scopes.py``).
PARTS = ("hvd::loss", "hvd::embed", "hvd::layer_loop", "hvd::kda_attention",
         "hvd::mla_attention", "hvd::dense_mlp", "hvd::moe",
         "hvd::lm_head_loss")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published configuration under its published names
    (``linear_attn_config``'s ``num_heads`` and ``head_dim`` as ``kda_``);
    what a deployment sets is below them."""
    vocab_size: int = 163840        # rows held of the embedding and head
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    kda_layers: Tuple[int, ...] = tuple(
        i for i in range(1, 27) if i % 4)               # counted from 1
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    first_k_dense_replace: int = 1  # the first layers; the rest hold experts
    intermediate_size: int = 9216   # a dense layer's width
    moe_intermediate_size: int = 1024
    num_experts: int = 256          # the router's width: ALL experts
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64      # one head, shared, NOT rotated
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-5
    l2_norm_eps: float = 1e-6       # under the root of q's and k's norms
    experts_held: int = 256         # experts whose weights live here ...
    first_expert: int = 0           # ... starting at this one
    expert_axis: Optional[str] = None   # mesh axis the experts are over
    dtype: Any = jnp.bfloat16
    attention_tile: int = 512       # flash tile (queries and keys)
    kda_chunk: int = 64             # positions a chunk of the scan
    loss_chunk: int = 2048          # positions a chunk of the head's logits


def layer_runs(cfg: KimiLinearConfig):
    """``[(mixer, dense?, layers)]``: the stack as stretches of consecutive
    layers of one kind, in published order."""
    kda, full = set(cfg.kda_layers), set(cfg.full_attn_layers)
    every = set(range(1, cfg.num_hidden_layers + 1))
    if kda & full or kda | full != every:
        raise ValueError(
            f"kda_layers {sorted(kda)} and full_attn_layers {sorted(full)} "
            f"do not split layers 1 to {cfg.num_hidden_layers}")
    kinds = [(KDA if i + 1 in kda else MLA, i < cfg.first_k_dense_replace)
             for i in range(cfg.num_hidden_layers)]
    return [(*kind, count) for kind, count in group_runs(kinds)]


def short_conv(x, weight):
    """Causal depthwise convolution of ``x [S, P]`` with ``weight [P,
    width]`` from a zero history, ``y_t = sum_j weight[:, j] x_{t - (width
    - 1) + j}``, then SiLU; float32 inside, ``x``'s dtype out.  With
    :func:`_unit` the plain definition that the tests hold
    ``kda_surround.short_conv_silu`` to; the model runs the pass."""
    seq, width = x.shape[0], weight.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((width - 1, 0), (0, 0)))
    y = sum(padded[j:j + seq] * weight[:, j] for j in range(width))
    return jax.nn.silu(y).astype(x.dtype)


def _unit(x, eps: float):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                               + eps)


def kda_operands(cfg: KimiLinearConfig, a, p):
    """``(q, k, v [S, H, d], g [S, H, d] float32, beta [S, H] float32,
    the output gate's pre-activation [S, P])`` of one sequence's normed
    input ``a [S, hidden]``: what the scan takes, and what waits for its
    output.  Between the projections and the scan every operand is one
    pass of ``parallel/kda_surround.py`` over ``[S, P]``, where a head is a
    block of lanes: the views by head on the way out cost nothing, and
    ``kda_scan`` takes them back."""
    from ..parallel import kda_surround as surround
    seq, _ = a.shape
    dtype, heads, d = cfg.dtype, cfg.kda_num_heads, cfg.kda_head_dim
    dot = lambda t, w: jnp.dot(t, w.astype(dtype))
    by_head = lambda t: t.reshape(seq, heads, d)
    with _scopes.scope("hvd::kda_attention::project"):
        q, k, v = dot(a, p["w_q"]), dot(a, p["w_k"]), dot(a, p["w_v"])
        decay = dot(dot(a, p["w_fa"]), p["w_fb"])
        gate = dot(dot(a, p["w_ga"]), p["w_gb"])
        write = dot(a, p["w_b"])
    with _scopes.scope("hvd::kda_attention::conv"):
        q, k, v = (surround.short_conv_silu(t, p[w], heads, unit,
                                            cfg.l2_norm_eps)
                   for t, w, unit in ((q, "conv_q", d ** -0.5),
                                      (k, "conv_k", 1.0),
                                      (v, "conv_v", None)))
    with _scopes.scope("hvd::kda_attention::gates"):
        g = surround.decay(decay, p["dt_bias"], p["A_log"], heads)
        beta = jax.nn.sigmoid(write.astype(jnp.float32))
    return by_head(q), by_head(k), by_head(v), by_head(g), beta, gate


def _kda_half(cfg: KimiLinearConfig, x, p):
    """``h = x + y W_o`` of one sequence ``x [S, hidden]`` through Kimi
    Delta Attention."""
    from ..parallel import kda_surround as surround
    from ..parallel.kda import kda_scan
    seq, _ = x.shape
    with _scopes.scope("hvd::kda_attention"):
        a = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q, k, v, g, beta, gate = kda_operands(cfg, a, p)
        with _scopes.scope("hvd::kda_attention::scan"):
            o = kda_scan(q, k, v, g, beta, chunk=cfg.kda_chunk)
        with _scopes.scope("hvd::kda_attention::out"):
            y = surround.gated_norm(o.reshape(seq, -1), gate, p["o_norm"],
                                    cfg.kda_num_heads, cfg.rms_norm_eps)
            return x + jnp.dot(y, p["w_o"].astype(cfg.dtype))


def _mla_half(cfg: KimiLinearConfig, x, p):
    """``h = x + o W_o`` of one sequence ``x [S, hidden]`` through latent
    attention without positions."""
    from ..parallel.flash import MASK_CAUSAL, flash_attention_heads_first
    seq, _ = x.shape
    dtype, eps, heads = cfg.dtype, cfg.rms_norm_eps, cfg.num_attention_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    tile = min(cfg.attention_tile, seq)
    dot = lambda t, w: jnp.dot(t, w.astype(dtype))
    heads_first = lambda t: t.transpose(1, 0, 2)
    with _scopes.scope("hvd::mla_attention"):
        a = rms_norm(x, p["attn_norm"], eps)
        with _scopes.scope("hvd::mla_attention::compress"):
            kva = dot(a, p["w_kva"])
            c_kv = rms_norm(kva[:, :cfg.kv_lora_rank], p["kva_norm"], eps)
            k_pe = kva[:, None, cfg.kv_lora_rank:]          # one head
        with _scopes.scope("hvd::mla_attention::expand"):
            q = dot(a, p["mla_wq"]).reshape(seq, heads, nope + rope)
            kv = dot(c_kv, p["w_kvb"]).reshape(seq, heads,
                                               nope + cfg.v_head_dim)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, (seq, heads, rope))], axis=-1)
            q, k, v = (heads_first(t) for t in (q, k, kv[..., nope:]))
        attended = flash_attention_heads_first(
            q, k, v, mask_mode=MASK_CAUSAL, block_q=tile, block_k=tile)
        with _scopes.scope("hvd::mla_attention::out"):
            return x + dot(heads_first(attended).reshape(seq, -1), p["wo"])


def _layer(cfg: KimiLinearConfig, mixer: str, dense: bool, layers):
    """One layer of a kind over one sequence, ``(x [S, hidden], p) -> (x,
    aux)``, under its ``jax.checkpoint``; ``layers`` are the run's, counted
    from 1.  A latent layer keeps its flash output and logsumexp across the
    recomputation (68 MB a sequence).  A KDA layer of a run that holds one
    of ``KEPT_KDA`` keeps its scan's output and the chunks' states (268 MB
    a sequence at 8,192 positions), which the backward kernel and the
    gated norm's backward pass read; any other KDA layer keeps nothing: its
    forward kernel runs again in the backward pass and writes them there."""
    from ..parallel import flash, kda
    mix = _kda_half if mixer == KDA else _mla_half
    mlp = dense_half if dense else functools.partial(
        expert_half, top_k=cfg.num_experts_per_token)
    if mixer == MLA:
        kept = flash.SAVED
    else:
        kept = kda.SAVED if set(layers) & set(KEPT_KDA) else ()
    return jax.checkpoint(
        lambda x, p: mlp(cfg, mix(cfg, x, p), p),
        policy=jax.checkpoint_policies.save_only_these_names(*kept)
        if kept else None)


def hidden_states(params: dict, tokens, cfg: KimiLinearConfig):
    """``(final hidden states [batch, S, hidden] before the last norm,
    Aux)`` for ``tokens [batch, S]``; ``Aux`` counts the expert layers
    only, in their order."""
    x = embed(params, tokens, cfg.dtype)
    return through_runs(x, layer_runs(cfg), params["runs"],
                        functools.partial(_layer, cfg),
                        cfg.num_experts_per_token)


@_scopes.part_scope("hvd::loss")
def loss_fn(params: dict, tokens, cfg: KimiLinearConfig):
    """The next-token loss of ``tokens [batch, S]`` and its ``Aux``:
    position ``i`` predicts token ``i + 1``, one document a sequence, the
    mean over the ``batch x (S - 1)`` predictions.  ``hvd::loss`` is the
    part of what ``decoder.next_token_loss`` does itself (targets, weights,
    the mean) and of what ``hidden_states`` does between its parts."""
    hidden, aux = hidden_states(params, tokens, cfg)
    return next_token_loss(params, hidden, tokens, cfg), aux
