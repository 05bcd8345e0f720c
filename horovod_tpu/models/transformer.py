"""Transformer family (GPT-2 / BERT) — TPU-first flax implementation.

Reference analog: the BASELINE.json north-star configs train BERT-large
(PyTorch DistributedOptimizer + gradient accumulation) and GPT-2 medium with
Adasum; the reference itself ships no model code beyond examples.  These
models are written for the MXU: bfloat16 matmuls with float32 layernorm/
softmax/loss islands, d_model/d_ff multiples of 128, optional
``jax.checkpoint`` rematerialization per block (HBM for FLOPs), and a
pluggable attention backend:

* ``seq_parallel=None``      — dense local attention (data-parallel only);
* ``seq_parallel='ring'``    — ring attention over the mesh axis
                               (parallel/ring.py), sequence sharded;
* ``seq_parallel='ulysses'`` — all_to_all head<->sequence exchange
                               (parallel/ulysses.py).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

import flax.linen as nn

from ..parallel.ring import (ring_attention, ring_attention_reference,
                             ring_flash_attention)
from ..parallel.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_len: int = 1024
    causal: bool = True              # GPT style; False = BERT style
    dtype: Any = jnp.bfloat16
    axis_name: str = "hvd"
    seq_parallel: Optional[str] = None   # None|'ring'|'ring_striped'|'ulysses'
    attention_impl: Optional[str] = None  # None (dense) | 'flash' (Pallas)
    remat: bool = False
    scan_layers: bool = False  # lax.scan over blocks: ~L x faster compile
    # Mixture-of-experts FFN (parallel/moe.py).  moe_experts > 0 replaces
    # the dense FFN with a top-k-routed MoE in every ``moe_every``-th block
    # (GShard alternation).  expert_axis names the mesh axis experts are
    # sharded over (params carry the GLOBAL [E, ...] expert dim; shard them
    # with in_specs on that axis) — None keeps experts replicated.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 2
    expert_axis: Optional[str] = None


# Benchmark-standard configurations.
GPT2_SMALL = TransformerConfig(num_layers=12, num_heads=12, d_model=768,
                               d_ff=3072)
GPT2_MEDIUM = TransformerConfig(num_layers=24, num_heads=16, d_model=1024,
                                d_ff=4096)
GPT2_LARGE = TransformerConfig(num_layers=36, num_heads=20, d_model=1280,
                               d_ff=5120)
BERT_BASE = TransformerConfig(vocab_size=30522, num_layers=12, num_heads=12,
                              d_model=768, d_ff=3072, max_len=512,
                              causal=False)
BERT_LARGE = TransformerConfig(vocab_size=30522, num_layers=24, num_heads=16,
                               d_model=1024, d_ff=4096, max_len=512,
                               causal=False)


class SelfAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, _ = x.shape
        head_dim = cfg.d_model // cfg.num_heads
        dense = partial(nn.DenseGeneral, dtype=cfg.dtype,
                        kernel_init=nn.initializers.normal(0.02))
        qkv = dense(features=(3, cfg.num_heads, head_dim), axis=-1,
                    name="qkv")(x)
        q, k, v = (qkv[:, :, i] for i in range(3))  # [B, S, H, Dh]
        if cfg.attention_impl not in (None, "flash"):
            raise ValueError(
                f"unknown attention_impl {cfg.attention_impl!r}; "
                f"expected None or 'flash'")
        use_flash = cfg.attention_impl == "flash"

        def local_flash(q, k, v, *, causal, scale=None):
            from ..parallel.flash import flash_attention
            return flash_attention(q, k, v, causal=causal, scale=scale)

        if cfg.seq_parallel in ("ring", "ring_striped"):
            # flash composes with the ring since round 5: the per-hop
            # block math runs in the Pallas kernel and the hops combine
            # by the (out, lse) logsumexp merge (ring_flash_attention).
            ring_fn = ring_flash_attention if use_flash else ring_attention
            out = ring_fn(q, k, v, axis_name=cfg.axis_name,
                          causal=cfg.causal,
                          striped=cfg.seq_parallel == "ring_striped")
        elif cfg.seq_parallel == "ulysses":
            out = ulysses_attention(
                q, k, v, axis_name=cfg.axis_name, causal=cfg.causal,
                attention_fn=local_flash if use_flash else None)
        elif use_flash:
            out = local_flash(q, k, v, causal=cfg.causal)
        else:
            out = ring_attention_reference(q, k, v, causal=cfg.causal)
        return dense(features=cfg.d_model, axis=(-2, -1), name="proj")(out)


class Block(nn.Module):
    cfg: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        ln = partial(nn.LayerNorm, dtype=jnp.float32, epsilon=1e-5)
        h = ln(name="ln1")(x)
        x = x + SelfAttention(cfg, name="attn")(h.astype(cfg.dtype))
        h = ln(name="ln2")(x)
        if self.use_moe:
            return x + self._moe_ffn(h.astype(cfg.dtype))
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, name="fc1",
                     kernel_init=nn.initializers.normal(0.02))(
                         h.astype(cfg.dtype))
        h = nn.gelu(h)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype, name="fc2",
                     kernel_init=nn.initializers.normal(0.02))(h)
        return x + h

    def _moe_ffn(self, h):
        """Top-k expert-parallel FFN (parallel/moe.py).  Params hold the
        expert dim at its LOCAL extent: the full E at init / replicated
        apply, E / n under shard_map with the expert dim sharded over
        cfg.expert_axis.  The aux load-balancing loss is sown into the
        "losses" collection — apply with ``mutable=["losses"]`` and add
        ``sum(jax.tree.leaves(mutated["losses"]))`` to the objective."""
        from jax import lax
        from ..parallel.moe import expert_parallel_ffn
        cfg = self.cfg
        if cfg.expert_axis:
            try:
                n = lax.axis_size(cfg.expert_axis)
            except NameError as e:
                raise ValueError(
                    f"expert_axis={cfg.expert_axis!r} is not bound — "
                    "initialize with expert_axis=None (params carry the "
                    "global [E, ...] expert dim) and shard them via "
                    "in_specs on the expert axis under shard_map; see "
                    "docs/moe.md") from e
        else:
            n = 1
        if cfg.moe_experts % max(n, 1):
            raise ValueError(f"moe_experts ({cfg.moe_experts}) must divide "
                             f"by the {cfg.expert_axis!r} axis size ({n})")
        e_local = cfg.moe_experts // n
        init = nn.initializers.normal(0.02)
        gate = self.param("moe_gate", init,
                          (cfg.d_model, cfg.moe_experts), jnp.float32)
        w_in = self.param("moe_w_in", init,
                          (e_local, cfg.d_model, cfg.d_ff), jnp.float32)
        w_out = self.param("moe_w_out", init,
                           (e_local, cfg.d_ff, cfg.d_model), jnp.float32)
        b, s, d = h.shape
        res = expert_parallel_ffn(
            h.reshape(b * s, d), gate,
            w_in.astype(cfg.dtype), w_out.astype(cfg.dtype),
            axis_name=cfg.expert_axis, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor)
        self.sow("losses", "moe_aux", res.aux_loss)
        return res.out.reshape(b, s, d)


class _ScanBlock(nn.Module):
    """Block adapted to the scan calling convention (carry, xs) ->
    (carry, ys); the real work stays in :class:`Block`."""
    cfg: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(self, x, _):
        return Block(self.cfg, use_moe=self.use_moe, name="block")(x), None


class Transformer(nn.Module):
    """Decoder-only (causal=True, GPT) or encoder (causal=False, BERT)
    producing token logits (LM head ties the embedding)."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, positions=None, predict_positions=None):
        """``predict_positions`` ([B, K] int32, BERT MLM only): apply the
        final layernorm + LM head ONLY at those K gathered positions and
        return [B, K, vocab] logits.  At 15 % masking the full-sequence
        head wastes ~6x its FLOPs and (at vocab 30k, f32) dominates logit
        HBM traffic — this is the standard max_predictions_per_seq
        formulation of BERT pretraining."""
        cfg = self.cfg
        B, S = tokens.shape
        emb = nn.Embed(cfg.vocab_size, cfg.d_model,
                       embedding_init=nn.initializers.normal(0.02),
                       dtype=cfg.dtype, name="wte")
        if positions is None:
            if cfg.seq_parallel == "ring_striped":
                # Striped layout: this shard holds global tokens
                # [idx, idx+n, idx+2n, ...].
                from ..parallel.ring import striped_positions
                positions = striped_positions(
                    S, axis_name=cfg.axis_name)[None, :]
            else:
                positions = jnp.arange(S)[None, :]
                if cfg.seq_parallel is not None:
                    # Block-sharded: this shard holds global tokens
                    # [idx*S, (idx+1)*S) — offset the position embedding or
                    # every shard but the first would silently embed 0..S-1.
                    from jax import lax as _lax
                    positions = positions + _lax.axis_index(
                        cfg.axis_name) * S
        pos_emb = nn.Embed(cfg.max_len, cfg.d_model,
                           embedding_init=nn.initializers.normal(0.01),
                           dtype=cfg.dtype, name="wpe")(positions)
        x = emb(tokens) + pos_emb
        if cfg.scan_layers:
            # One traced block, lax.scan'd over stacked [L, ...] params:
            # the HLO carries ONE block body instead of num_layers copies,
            # which divides XLA compile time by ~the depth.  Param tree
            # changes shape (blocks/block/... stacked) —
            # stack_block_params migrates unrolled checkpoints.
            if cfg.moe_experts > 0 and cfg.moe_every != 1:
                raise ValueError(
                    "scan_layers needs homogeneous blocks; interleaved "
                    "MoE (moe_every > 1) must use scan_layers=False")
            inner = _ScanBlock
            if cfg.remat:
                # prevent_cse is scan's job here (jax.checkpoint docs).
                inner = nn.remat(_ScanBlock, prevent_cse=False)
            blocks = nn.scan(
                inner,
                variable_axes={"params": 0, "losses": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
            )(cfg, use_moe=cfg.moe_experts > 0, name="blocks")
            x, _ = blocks(x, None)
        else:
            block = Block
            if cfg.remat:
                block = nn.remat(Block)  # jax.checkpoint: HBM for FLOPs
            for i in range(cfg.num_layers):
                use_moe = (cfg.moe_experts > 0
                           and i % cfg.moe_every == cfg.moe_every - 1)
                x = block(cfg, use_moe=use_moe, name=f"block_{i}")(x)
        if predict_positions is not None:
            x = jnp.take_along_axis(
                x, predict_positions[..., None].astype(jnp.int32), axis=1)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        # Tied LM head (GPT-2 convention); f32 logits for a stable loss.
        logits = emb.attend(x.astype(cfg.dtype)).astype(jnp.float32)
        return logits


def stack_block_params(params, num_layers: int):
    """Migrate an UNROLLED checkpoint (``block_0``..``block_{L-1}``) to the
    ``scan_layers`` layout (``blocks/block/...`` with leaves stacked on a
    leading layer axis).  Non-block entries (wte/wpe/ln_f) pass through.
    The inverse direction is ``unstack_block_params``."""
    import flax
    import numpy as np
    flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(params))
    out, grouped = {}, {}
    for k, v in flat.items():
        if k[0].startswith("block_"):
            grouped.setdefault(k[1:], {})[int(k[0][len("block_"):])] = v
        else:
            out[k] = v
    for rest, by_layer in grouped.items():
        if sorted(by_layer) != list(range(num_layers)):
            raise ValueError(
                f"checkpoint has layers {sorted(by_layer)} for "
                f"{'/'.join(rest)}, expected 0..{num_layers - 1}")
        out[("blocks", "block") + rest] = np.stack(
            [by_layer[i] for i in range(num_layers)])
    return flax.traverse_util.unflatten_dict(out)


def unstack_block_params(params):
    """scan_layers checkpoint -> unrolled layout (inverse of
    :func:`stack_block_params`)."""
    import flax
    flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(params))
    out = {}
    for k, v in flat.items():
        if k[:2] == ("blocks", "block"):
            for i in range(v.shape[0]):
                out[(f"block_{i}",) + k[2:]] = v[i]
        else:
            out[k] = v
    return flax.traverse_util.unflatten_dict(out)


def lm_loss(logits, targets, mask=None):
    """Token cross-entropy in f32 (BERT MLM or GPT next-token; caller shifts
    targets for causal LM)."""
    import optax
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is not None:
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(losses)


def create_gpt2(size: str = "medium", **overrides) -> Transformer:
    """Factories default ``scan_layers=True``: one traced block lax.scan'd
    over stacked params compiles ~num_layers x faster at identical step
    numerics (24-layer measurement: 59.7 -> 5.2 s CPU compile, StableHLO
    943 -> 137 kB).
    Pass ``scan_layers=False`` for the unrolled block_i param layout;
    ``stack_block_params``/``unstack_block_params`` convert checkpoints.
    Caveat: per-TENSOR gradient methods see stacked leaves as one tensor —
    Adasum in particular computes its projection coefficients per leaf.
    Keep the reference's per-layer granularity by passing
    ``per_layer_stacked`` to ``hvd.adasum_delta_step`` (it computes one
    coefficient pair per layer slice; examples/gpt2_adasum.py shows the
    pattern), or fall back to ``scan_layers=False``."""
    base = {"small": GPT2_SMALL, "medium": GPT2_MEDIUM,
            "large": GPT2_LARGE}[size]
    overrides.setdefault("scan_layers", True)
    return Transformer(dataclasses.replace(base, **overrides))


def create_bert(size: str = "large", **overrides) -> Transformer:
    """See :func:`create_gpt2` for the ``scan_layers`` default."""
    base = {"base": BERT_BASE, "large": BERT_LARGE}[size]
    overrides.setdefault("scan_layers", True)
    return Transformer(dataclasses.replace(base, **overrides))
