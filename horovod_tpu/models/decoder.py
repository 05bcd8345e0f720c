"""The parts that two or more of the five decoders (``sdar_moe``,
``afmoe``, ``joyai_flash``, ``kimi_linear``, ``lfm2_moe``) run unchanged up
to the values they pass, as plain functions over a dict of arrays; a
``cfg`` is the calling model's configuration.  The scopes written here are
exported by the decoders' ``PARTS``, none by this module."""

from __future__ import annotations

import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes as _scopes


class Aux(NamedTuple):
    """What leaves the step beside the loss, a layer: the (position,
    choice) pairs the held experts computed, and every position's chosen
    experts."""
    routed_here: jax.Array      # [layers] int32
    chosen: jax.Array           # [layers, positions, experts_per_token]


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def rotary(x, positions, theta: float):
    """Rotary embedding over the whole last dimension of ``x [batch, S,
    heads, head_dim]`` (the half-split convention of the published
    model: pairs ``(i, i + head_dim / 2)``)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def heads_first_qkv(h, p, tables, heads: int, kv_heads: int, eps: float,
                    dtype):
    """``(q [heads, S, head_dim], k, v [kv_heads, S, head_dim])`` of one
    sequence's normed input ``h [S, hidden]``, as
    ``flash_attention_heads_first`` takes them: the three projections,
    every query and key head under its RMSNorm (``p["q_norm"]``,
    ``p["k_norm"]``) and rotated by ``tables`` (``qk_rope.rope_tables``;
    ``None``: no positions).  Norm, rotation and the head-major layout of
    ``q`` and ``k`` are one pass over the projection's output
    (``parallel/qk_rope.py``), under ``hvd::qk_rope``."""
    from ..parallel.qk_rope import qk_norm_rope
    project = lambda w: jnp.dot(h, p[w].astype(dtype))
    q, k, v = project("wq"), project("wk"), project("wv")
    with _scopes.scope("hvd::qk_rope"):
        q = qk_norm_rope(q, p["q_norm"], tables, heads, eps)
        k = qk_norm_rope(k, p["k_norm"], tables, kv_heads, eps)
    return q, k, v.reshape(v.shape[0], kv_heads, -1).transpose(1, 0, 2)


def gated_mlp(x, w_gate, w_up, w_down, dtype):
    """``(silu(x W_gate) * (x W_up)) W_down``: three plain products in
    ``dtype``, the gated unit in float32."""
    gate = jnp.dot(x, w_gate.astype(dtype))
    up = jnp.dot(x, w_up.astype(dtype))
    hidden = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return jnp.dot(hidden.astype(dtype), w_down.astype(dtype))


def dense_half(cfg, h, p):
    """``h + GatedMLP(RMSNorm(h))`` of one sequence, and no aux."""
    with _scopes.scope("hvd::dense_mlp"):
        m = rms_norm(h, p["mlp_norm"], cfg.rms_norm_eps)
        return h + gated_mlp(m, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                             cfg.dtype), ()


def expert_half(cfg, h, p, top_k: int):
    """``h + Shared(m) + sum_e w_e Expert_e(m)``, ``m = RMSNorm(h)``, of one
    sequence under a sigmoid router, and ``(routed here, chosen)``."""
    from ..parallel.moe import dropless_expert_ffn
    with _scopes.scope("hvd::moe"):
        m = rms_norm(h, p["mlp_norm"], cfg.rms_norm_eps)
        with _scopes.scope("hvd::moe::shared"):
            shared = gated_mlp(m, p["shared_gate"], p["shared_up"],
                               p["shared_down"], cfg.dtype)
        moe = dropless_expert_ffn(
            m, p["router"], p["w_gate"], p["w_up"], p["w_down"],
            top_k=top_k, first_expert=cfg.first_expert,
            axis_name=cfg.expert_axis, score_func="sigmoid",
            selection_bias=p.get("e_score_correction_bias"),
            route_scale=cfg.routed_scaling_factor)
        f = shared.astype(jnp.float32) + moe.out.astype(jnp.float32)
        return h + f.astype(h.dtype), (moe.routed_here, moe.chosen)


def group_runs(kinds):
    """``[(kind, layers)]``: stretches of consecutive layers of one kind."""
    return [(kind, len(list(run))) for kind, run in itertools.groupby(kinds)]


def through_layers(layer, x, stacked):
    """``x [batch, S, hidden]`` through the layers whose parameters are
    ``stacked`` on a leading axis: the layers under ``lax.scan`` and, inside
    a layer, the sequences one after the other.  ``layer(x [S, hidden], p)
    -> (x, aux)`` comes with its own ``jax.checkpoint``; returns ``(x, aux
    [layers, batch, ...])``.

    ``hvd::layer_loop`` is around the whole scan, so that it is the part of
    everything a (layer, sequence) costs outside the layer's own parts, in
    both loops and both passes: the slices of the stacked parameters, the
    sums of their gradients over a layer's sequences, the auxiliary
    outputs."""
    with _scopes.scope("hvd::layer_loop"):
        return lax.scan(lambda x, p: lax.map(lambda xs: layer(xs, p), x),
                        x, stacked)


def routing(aux, batch: int, seq: int):
    """``(pairs routed here [layers], chosen [layers, batch x S, k])`` of
    a run of expert layers, from ``through_layers``' ``[layers, batch,
    ...]``."""
    return aux[0].sum(axis=1), aux[1].reshape(aux[1].shape[0], batch * seq,
                                              -1)


def through_runs(x, runs, stacked, make_layer, top_k: int):
    """``(x, Aux)`` of ``x [batch, S, hidden]`` through ``runs`` (``[(*kind,
    layers)]``, a model's ``layer_runs``) under ``decoder``: ``stacked`` holds
    a tree a run, ``make_layer(*kind, numbers)`` makes a run's layer from its
    layers' numbers counted from 1; ``Aux`` counts the runs that route."""
    batch, seq = x.shape[:2]
    routed, first = [], 1
    with _scopes.scope("decoder"):
        for (*kind, count), params in zip(runs, stacked):
            layer = make_layer(*kind, range(first, first + count))
            x, aux = through_layers(layer, x, params)
            first += count
            if aux:
                routed.append(routing(aux, batch, seq))
    if not routed:      # a stack of dense layers alone routes nothing
        return x, Aux(jnp.zeros((0,), jnp.int32), jnp.zeros(
            (0, batch * seq, top_k), jnp.int32))
    return x, Aux(*(jnp.concatenate(each) for each in zip(*routed)))


def embed(params: dict, tokens, dtype, scale: Optional[float] = None):
    """The rows of ``params["embed"]`` for ``tokens``, times ``scale``
    where one is given, in ``dtype``; the gather, and so the scatter-add of
    the backward pass, under ``hvd::embed``."""
    with _scopes.part_scope("hvd::embed"):
        x = params["embed"][tokens]
        if scale is not None:
            x = x * scale
        return x.astype(dtype)


def head_loss(params: dict, hidden, targets, weight, cfg):
    """``sum_i weight_i * -log softmax(W_head RMSNorm(hidden_i))[target_i]``
    over all positions of ``hidden [N, hidden]``, the logits over the rows
    of the vocabulary held here, a chunk of positions at a time so that no
    ``[N, vocab]`` array is ever whole."""
    n, d = hidden.shape
    chunk = min(cfg.loss_chunk, n)
    if n % chunk:
        raise ValueError(f"{n} positions are no multiple of the loss's "
                         f"chunk of {chunk}")
    head = params["head"].astype(cfg.dtype)

    @jax.checkpoint
    def chunk_loss(part):
        h, target, w = part
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.dot(h, head, preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, target[:, None], axis=-1)[:, 0]
        return jnp.sum(w * nll)

    # JAX writes the outermost scope of a differentiated function into its
    # ``jvp(...)`` marker; ``head`` takes that place, as ``decoder`` does.
    with _scopes.scope("head"), _scopes.scope("hvd::lm_head_loss"):
        total = jnp.sum(lax.map(
            chunk_loss,
            (hidden.reshape(n // chunk, chunk, d),
             targets.reshape(n // chunk, chunk),
             weight.reshape(n // chunk, chunk))))
    return total


def next_token_loss(params: dict, hidden, tokens, cfg, ahead: int = 1):
    """The mean over ``batch x (S - ahead)`` of :func:`head_loss`, row ``i``
    judged on token ``i + ahead``; the rows that predict nothing weigh 0."""
    batch, seq = tokens.shape
    targets = jnp.roll(tokens, -ahead, axis=1)
    weight = jnp.broadcast_to(
        (jnp.arange(seq) < seq - ahead).astype(jnp.float32), (batch, seq))
    total = head_loss(params, hidden.reshape(batch * seq, -1),
                      targets.reshape(-1), weight.reshape(-1), cfg)
    return total / (batch * (seq - ahead))
