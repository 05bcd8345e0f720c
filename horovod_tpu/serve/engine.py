"""Continuous-batching inference engine over the repo's ``models/``.

No reference analog — the reference ends at the optimizer step.  The design
is Orca's iteration-level scheduling (OSDI '22) with vLLM's block-paged KV
storage (Kwon et al., SOSP '23) and Sarathi-Serve's chunked prefill
(Agrawal et al., OSDI '24):

* **paged KV cache** — the cache is a pool of fixed-size blocks
  (``HVD_SERVE_BLOCK_TOKENS`` positions each, serve/blocks.BlockManager);
  a sequence holds exactly the blocks its tokens occupy and addresses
  them through a per-sequence block table, so admission is bounded by
  *free blocks*, not by ``max_batch × max_len`` pre-reservation.  The
  attention over the tables runs either as a ``jnp.take`` gather +
  post-hoc mask (the exactness baseline) or as the fused Pallas
  paged-attention kernels (serve/paged_attention.py) that consume the
  pool and tables directly — ``HVD_SERVE_ATTN_IMPL`` picks, scheduling
  is identical either way.  Block storage is optionally int8/fp8
  quantized with append-time scale rows (``HVD_SERVE_KV_DTYPE``),
  roughly doubling the sequences a fixed HBM budget admits;
* **chunked prefill** — long prompts stream through the per-iteration
  token budget ``HVD_SERVE_PREFILL_CHUNK``, so every iteration still runs
  admit → prefill-chunk → decode and a ``max_len`` prompt never stalls
  in-flight decodes for a whole prefill (decode token-step p99 stays flat
  while prompts stream in);
* **prefix caching** — full prompt blocks are content-hashed; a request
  sharing a cached prefix maps the same physical blocks and skips their
  prefill (copy-on-write protects shared blocks from writes);
* **bucketed compilation** — chunk prefill jits once per (padded request
  count, padded chunk length) power-of-two bucket and paged decode jits
  exactly once, so steady-state serving never recompiles.

Exactness: decoding is greedy (argmax) and every per-sequence computation
is row-independent inside the batch — cache positions beyond a sequence's
length are masked to ``-1e30`` before the softmax (weight exactly 0),
block-table holes use an out-of-bounds sentinel (scatter drops the write,
gather clamps and the mask zeroes the read) — so the tokens a request
receives are bit-identical whether it ran alone, packed in a full batch,
prefilled in one shot or in chunks, or resumed on another replica.  Tests
pin batched==single, including block-boundary prompt lengths, and hold
the programs' logits to the flax model's (tests/test_serve_logits.py).

Model support: the ``models/`` Transformer (dense causal attention,
``TransformerAdapter`` — stacked ``scan_layers`` checkpoints are unstacked
once at load) and the MNIST-scale MLP as a trivially-cheap stand-in for
engine-mechanics tests (``MLPAdapter``: next token = argmax MLP(one-hot
(token)), no cache).  Everything runs under ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..faultline import runtime as _faultline
from ..faultline.plan import FaultInjected
from ..obs import tracing as _obs
from ..utils import get_logger
from . import sampling as _sampling
from .batcher import (DeadlineExceededError, DynamicBatcher, Request,
                      prompt_bucket)
from .blocks import BlockManager, NoFreeBlocksError, chain_hashes
from .metrics import ServeMetrics
from .tiering import (TierClient, TierConfig, TieredBlockManager,
                      TierWorker, make_block_io)


def _next_pow2(n: int, floor: int = 1) -> int:
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Model adapters
# ---------------------------------------------------------------------------

class ModelAdapter:
    """Engine-facing model interface.

    The engine owns slot/block bookkeeping; the adapter owns the math and
    the per-bucket compile caches.  ``prefill_chunk``/``decode_paged``
    take and return the cache pytree so the engine can thread it through
    jit with donation.  The engine refuses an adapter without the trio
    ``init_paged_cache`` / ``prefill_chunk`` / ``decode_paged``; an
    adapter whose sequences hold blocks (``kv_token_cost > 0``) also
    needs ``copy_block``.  Optional members, each checked where its
    feature is asked for: ``prefill_chunk_logits`` +
    ``decode_paged_sampled`` (sampling, n > 1), ``decode_paged_logits``
    (schemas, logprobs), ``verify_chunk`` + ``draft_decode`` +
    ``spec_capable`` (speculative decoding), ``prompt_logits`` /
    ``score_logits`` (``/score``).
    """

    vocab_size: int
    max_len: int

    def token_strings(self) -> Optional[List[str]]:
        """Token id → emitted text, the vocabulary hvdstream structured
        decoding builds its grammar masks over (serve/structured.py).
        The default maps byte-level vocabs (``vocab_size <= 256``) to
        their character identity; adapters over subword vocabularies
        must override with their detokenizer or return None — a None
        vocabulary makes ``schema`` requests fail with HTTP 400 rather
        than constrain against a fictional mapping."""
        if self.vocab_size <= 256:
            return [chr(i) for i in range(self.vocab_size)]
        return None

    def init_paged_cache(self, num_blocks: int, max_batch: int):
        """The block pool (any pytree) for ``num_blocks`` blocks."""
        raise NotImplementedError

    def prefill_chunk(self, cache, chunks: Sequence[Sequence[int]],
                      starts: Sequence[int],
                      tables: Sequence[Sequence[int]]):
        """Continue sequence i's prompt with ``chunks[i]`` at absolute
        position ``starts[i]`` into physical blocks ``tables[i]``;
        returns ``(cache, next_tokens)`` where ``next_tokens[i]`` is the
        greedy token after chunk i's last position."""
        raise NotImplementedError

    def decode_paged(self, cache, tokens: np.ndarray,
                     positions: np.ndarray, tables: np.ndarray):
        """One token step for the whole slot batch: feed ``tokens[b]`` at
        ``positions[b]`` through block table ``tables[b]``; returns
        ``(cache, next_tokens[max_batch])``.  Rows whose slot is inactive
        carry token 0 / position 0 / an all-hole table and their output
        is ignored."""
        raise NotImplementedError

    def copy_block(self, cache, src: int, dst: int):
        """Copy-on-write data move: duplicate physical block ``src`` into
        ``dst`` across all layers; returns the cache."""
        raise NotImplementedError


#: The members without which an adapter cannot serve at all.
_PAGED_TRIO = ("init_paged_cache", "prefill_chunk", "decode_paged")


def _missing_paged_members(adapter) -> List[str]:
    """Members of the paged trio ``adapter`` lacks (``ModelAdapter``'s
    own stubs count as lacking)."""
    return [m for m in _PAGED_TRIO
            if not callable(getattr(adapter, m, None))
            or getattr(type(adapter), m, None) is getattr(ModelAdapter, m)]


class TransformerAdapter(ModelAdapter):
    """KV-cache decoding for ``models.Transformer`` parameters.

    Runs the Block math (ln1 → qkv → causal attention → proj residual →
    ln2 → fc1/gelu/fc2 residual; f32 layernorm islands, tied LM head) as
    pure functions over the param pytree, with an explicit per-layer KV
    cache the flax module doesn't carry: a block pool addressed through
    block tables.
    Serving math is forced to f32 (``HVD_SERVE_DTYPE`` may widen
    training bf16 checkpoints) — greedy parity across batch compositions
    is the contract and f32 keeps the argmax far from dtype noise.

    Paged attention runs one of two implementations
    (``HVD_SERVE_ATTN_IMPL`` / ``attn_impl=``):

    * ``gather`` — ``jnp.take`` over the block tables + post-hoc mask +
      dense softmax (the exactness baseline; materializes gathered
      [B, S, H, Dh] K/V copies);
    * ``kernel`` — the fused Pallas paged-attention kernels
      (serve/paged_attention.py): block tables index the BlockSpecs
      directly, holes are masked inside the kernel, no gathered copy.
      Runs compiled on TPU, under the Pallas interpreter elsewhere;
    * ``auto`` (default) — ``kernel`` on TPU, ``gather`` off-TPU.

    Paged KV block storage dtype (``HVD_SERVE_KV_DTYPE`` / ``kv_dtype=``):
    ``native`` (the compute dtype, default), ``f32``/``bf16`` (explicit
    unquantized storage), or ``int8``/``fp8`` (quantized blocks with
    per-(position, head) scale rows written at append time and
    dequantized inside the attention — halves KV bytes again vs bf16, so
    a fixed HBM budget admits ~2x the concurrent sequences).

    Constraints (asserted): dense local attention only — a serving replica
    is data-parallel and holds the full model, so ``seq_parallel``/MoE
    configs are for the training mesh, not here.
    """

    kv_token_cost = 1  # cache positions consumed per token (MLP: 0)

    def __init__(self, cfg, params, max_len: Optional[int] = None,
                 block_tokens: Optional[int] = None,
                 attn_impl: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 draft_layers: Optional[int] = None):
        import jax.numpy as jnp
        if cfg.seq_parallel is not None or cfg.moe_experts:
            raise ValueError(
                "serving replicas are data-parallel: load the checkpoint "
                "with seq_parallel=None / moe_experts=0 (the params are "
                "layout-compatible)")
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.max_len = min(max_len or cfg.max_len, cfg.max_len)
        self.num_layers = cfg.num_layers
        self.head_dim = cfg.d_model // cfg.num_heads
        self.block_tokens = int(
            block_tokens if block_tokens is not None
            else os.environ.get("HVD_SERVE_BLOCK_TOKENS", "16"))
        dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[
            os.environ.get("HVD_SERVE_DTYPE", "f32")]
        params = _unstack_if_scanned(params, cfg.num_layers)
        import jax
        self.params = jax.tree.map(
            lambda a: jnp.asarray(a, dtype=dtype), params)
        self._dtype = dtype
        impl = (attn_impl if attn_impl is not None
                else os.environ.get("HVD_SERVE_ATTN_IMPL", "auto")).lower()
        if impl == "auto":
            # The fused kernel is the TPU fast path; the gather baseline
            # stays the off-TPU default (the kernel still RUNS anywhere
            # via the Pallas interpreter — slower, bit-stable — which is
            # how CPU tier-1 tests and the hermetic bench exercise it).
            impl = "kernel" if jax.default_backend() == "tpu" else "gather"
        if impl not in ("gather", "kernel"):
            raise ValueError(
                f"attn_impl must be gather|kernel|auto, got {impl!r}")
        self.attn_impl = impl
        self._interpret = jax.default_backend() != "tpu"
        kvd = (kv_dtype if kv_dtype is not None
               else os.environ.get("HVD_SERVE_KV_DTYPE", "native")).lower()
        from .paged_attention import KV_DTYPES, SCALE_DTYPE
        if kvd not in ("native", "f32", "bf16") and kvd not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be native|f32|bf16|int8|fp8, got {kvd!r}"
                + ("" if kvd != "fp8"
                   else " (this jax build has no float8_e4m3fn)"))
        self.kv_dtype = kvd
        self._kv_quantized = kvd in ("int8", "fp8")
        self._kv_store_dtype = (
            {"native": dtype, "f32": jnp.float32,
             "bf16": jnp.bfloat16}[kvd] if not self._kv_quantized
            else {"int8": jnp.int8,
                  "fp8": getattr(jnp, "float8_e4m3fn", None)}[kvd])
        self._scale_dtype = SCALE_DTYPE
        # Speculative-decoding draft: the first ``draft_layers`` blocks
        # + the final LN/LM-head run as a cheap proposer that SHARES the
        # target's params and KV pool — the draft's layer-l K/V at a
        # verified position is the same math the target writes there, so
        # the draft needs no cache of its own and a rejected draft
        # leaves nothing to reconcile (the verify step rewrites the same
        # positions for all layers).  0 disables (spec_capable False).
        dl = (draft_layers if draft_layers is not None
              else int(os.environ.get("HVD_SERVE_DRAFT_LAYERS", "0")))
        if not 0 <= dl < self.num_layers:
            raise ValueError(
                f"draft_layers must be in [0, num_layers), got {dl} "
                f"(num_layers {self.num_layers})")
        self.draft_layers = dl
        self._chunk_cache: Dict[Tuple[int, int, int], object] = {}
        self._chunk_logits_cache: Dict[Tuple[int, int, int], object] = {}
        self._verify_cache: Dict[Tuple[int, int, int], object] = {}
        self._paged_decode_fns: Dict[Tuple[int, int], object] = {}
        self._paged_logits_fns: Dict[Tuple[int, int], object] = {}
        self._sampled_decode_fns: Dict[Tuple[int, int], object] = {}
        self._draft_decode_fns: Dict[Tuple[int, int], object] = {}
        self._copy_block_fn = None

    @property
    def spec_capable(self) -> bool:
        """True when this adapter can serve speculative decoding (a
        draft stack is configured — HVD_SERVE_DRAFT_LAYERS >= 1)."""
        return self.draft_layers > 0

    # -- trace-time analysis (HVD_ANALYZE=1) ---------------------------------

    def _maybe_analyze(self, kind: str, key, fn, args) -> None:
        """HVD_ANALYZE ride-along for the serve-phase programs (the
        ROADMAP-5 lint gap): the first compile of every prefill/decode
        bucket gets the same collective-census + HVD101/102 walk — and
        the hvdmem liveness walk — that a training step gets.  Serve
        programs must census ZERO collectives (a replica is
        data-parallel and self-contained); that invariant is pinned by
        tests/test_memplan.py.  One env read when disabled; trace-only,
        so the donated cache argument is never consumed."""
        from ..analysis import hook as _hook
        if not _hook.enabled():
            return
        label = f"serve:{kind}[{','.join(str(k) for k in key)}]"
        _hook.analyze_traceable(fn, args, label=label)

    # -- cache --------------------------------------------------------------

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_len // self.block_tokens)

    def init_paged_cache(self, num_blocks: int, max_batch: int):
        """Block pool ``[L, num_blocks, block_tokens, H, Dh]``: one
        physical layout shared by every sequence; logical placement lives
        in the per-sequence block tables (serve/blocks.py).  Quantized
        storage (int8/fp8) adds per-(block, position, head) scale pools
        ``[L, num_blocks, block_tokens, H]`` written alongside every K/V
        append."""
        return self._pool_arrays(num_blocks)

    def _pool_arrays(self, num_blocks: int):
        """The pool pytree for ``num_blocks`` blocks (``prompt_logits``
        builds throwaway pools through this)."""
        import jax.numpy as jnp
        shape = (self.num_layers, num_blocks, self.block_tokens,
                 self.cfg.num_heads, self.head_dim)
        pool = {"k": jnp.zeros(shape, self._kv_store_dtype),
                "v": jnp.zeros(shape, self._kv_store_dtype)}
        if self._kv_quantized:
            pool["k_scale"] = jnp.zeros(shape[:-1], self._scale_dtype)
            pool["v_scale"] = jnp.zeros(shape[:-1], self._scale_dtype)
        return pool

    def paged_block_bytes(self) -> int:
        """HBM bytes one physical block costs across all layers (K + V
        payload plus scale rows when quantized) — the BlockManager's
        bytes-per-block accounting, which is what makes the fixed-budget
        admit_ratio win of quantized storage measurable."""
        from .paged_attention import kv_bytes_per_token
        per_tok_head = kv_bytes_per_token(
            self.kv_dtype if self._kv_quantized else "native",
            self.head_dim, self._kv_store_dtype)
        return (self.num_layers * 2 * self.block_tokens
                * self.cfg.num_heads * per_tok_head)

    def _quantized_scatter(self, pool, layer, wblk, woff, k, v):
        """Append-time quantization: one scale per (position, head) row,
        written once next to its int8/fp8 payload (module doc of
        serve/paged_attention.py has the why-not-per-block rationale).
        Out-of-bounds rows (the hole sentinel) drop from the scale pools
        by the same scatter rule as the payload."""
        from .paged_attention import quantize_kv
        kq, ks = quantize_kv(k, self.kv_dtype)
        vq, vs = quantize_kv(v, self.kv_dtype)
        pool["k"] = pool["k"].at[layer, wblk, woff].set(kq)
        pool["v"] = pool["v"].at[layer, wblk, woff].set(vq)
        pool["k_scale"] = pool["k_scale"].at[layer, wblk, woff].set(ks)
        pool["v_scale"] = pool["v_scale"].at[layer, wblk, woff].set(vs)
        return pool

    def _paged_attend(self, q, pool, layer, tables, q_positions):
        """One layer's paged attention over the pool, either impl.

        ``q`` is [n, H, Dh] (decode) or [n, c, H, Dh] (prefill chunk);
        ``q_positions`` [n] is the absolute position of each row's FIRST
        query (decode: the token's own position).  Returns the attention
        output in the compute dtype."""
        from . import paged_attention as _pa
        scale = 1.0 / math.sqrt(self.head_dim)
        ks = pool.get("k_scale")
        vs = pool.get("v_scale")
        if self.attn_impl == "kernel":
            fn = (_pa.paged_decode_attention if q.ndim == 3
                  else _pa.paged_prefill_attention)
            out = fn(q, pool["k"][layer], pool["v"][layer], tables,
                     q_positions,
                     k_scale=None if ks is None else ks[layer],
                     v_scale=None if vs is None else vs[layer],
                     scale=scale, interpret=self._interpret)
            return out.astype(self._dtype)
        # gather baseline: ONE implementation, shared with the parity
        # tests and the bench — paged_attention_reference does the take
        # over the tables (mode="clip": hole sentinels clamp onto the
        # last REAL block, so correctness depends on the validity mask
        # covering every clamped entry — pinned by the poisoned-pool
        # regression; the default "fill" mode would inject NaN), the
        # post-hoc positional mask, the dequantizing load, and the dense
        # softmax.  A mask/dequant fix there lands here by construction.
        out = _pa.paged_attention_reference(
            q, pool["k"][layer], pool["v"][layer], tables, q_positions,
            k_scale=None if ks is None else ks[layer],
            v_scale=None if vs is None else vs[layer], scale=scale)
        return out.astype(self._dtype)

    # -- functional forward pieces ------------------------------------------

    def _ln(self, x, p, eps):
        import jax.numpy as jnp
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * (1.0 / jnp.sqrt(var + eps))
        return (y * p["scale"] + p["bias"]).astype(jnp.float32)

    def _ffn(self, x, blk):
        import jax
        import jax.numpy as jnp
        h = self._ln(x, blk["ln2"], 1e-5).astype(self._dtype)
        h = jnp.einsum("...d,df->...f", h, blk["fc1"]["kernel"]) \
            + blk["fc1"]["bias"]
        h = jax.nn.gelu(h)  # flax nn.gelu default: approximate
        h = jnp.einsum("...f,fd->...d", h, blk["fc2"]["kernel"]) \
            + blk["fc2"]["bias"]
        return x + h

    def _qkv(self, x, blk):
        import jax.numpy as jnp
        h = self._ln(x, blk["ln1"], 1e-5).astype(self._dtype)
        qkv = jnp.einsum("...d,dthe->...the", h,
                         blk["attn"]["qkv"]["kernel"]) \
            + blk["attn"]["qkv"]["bias"]
        return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]

    def _proj(self, x, out, blk):
        import jax.numpy as jnp
        return x + (jnp.einsum("...he,hed->...d", out,
                               blk["attn"]["proj"]["kernel"])
                    + blk["attn"]["proj"]["bias"])

    def _logits(self, x, params):
        import jax.numpy as jnp
        x = self._ln(x, params["ln_f"], 1e-6)  # nn.LayerNorm default eps
        return jnp.einsum("...d,vd->...v", x.astype(self._dtype),
                          params["wte"]["embedding"]).astype(jnp.float32)

    # -- chunked prefill -----------------------------------------------------

    def _chunk_forward(self, params, cache, tokens, starts, lengths,
                       tables, NB: int, c: int):
        """The chunk-prefill forward (both attention impls, both KV
        storage dtypes): scatter each chunk's (possibly quantized) K/V
        into the pool, attend over the block tables, return ``(pool,
        final-position logits)``.  Shared by the jitted per-bucket
        programs (argmax on top), the logits/verify variants (sampling
        + speculative decoding need raw logits) and ``prompt_logits``
        (the bench/test logit-error probe — quantization error must be
        measured through the REAL storage path, not a simulation of
        it)."""
        import jax.numpy as jnp
        pool, x = self._chunk_body(params, cache, tokens, starts,
                                   lengths, tables, NB, c)
        last = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
        )[:, 0]
        return pool, self._logits(last, params)

    def _chunk_body(self, params, cache, tokens, starts, lengths,
                    tables, NB: int, c: int):
        """Scatter + attend for one chunk batch; returns ``(pool, x)``
        with ``x`` the final hidden states at EVERY chunk position —
        ``_chunk_forward`` reads only each row's last position, the
        speculative ``verify_chunk`` reads all of them."""
        import jax.numpy as jnp
        BT = self.block_tokens
        MB = self.max_blocks_per_seq
        # tokens [n, c] int32 (one prompt chunk per row); starts [n]
        # (absolute position of tokens[i, 0]); lengths [n] (real chunk
        # length <= c); tables [n, MB] (entry NB = hole: scatter drops
        # the write, the attention clamps and masks the read).
        pos = starts[:, None] + jnp.arange(c)[None, :]        # [n, c]
        in_chunk = jnp.arange(c)[None, :] < lengths[:, None]  # [n, c]
        x = params["wte"]["embedding"][tokens] \
            + params["wpe"]["embedding"][
                jnp.minimum(pos, self.max_len - 1)]
        pool = dict(cache)
        wblk = jnp.take_along_axis(
            tables, jnp.minimum(pos // BT, MB - 1), axis=1)
        wblk = jnp.where(in_chunk, wblk, NB)  # pad tail: drop writes
        woff = pos % BT
        for l in range(self.num_layers):
            blk = params[f"block_{l}"]
            q, k, v = self._qkv(x, blk)       # [n, c, H, Dh]
            if self._kv_quantized:
                pool = self._quantized_scatter(pool, l, wblk, woff, k, v)
            else:
                pool["k"] = pool["k"].at[l, wblk, woff].set(
                    k.astype(self._kv_store_dtype))
                pool["v"] = pool["v"].at[l, wblk, woff].set(
                    v.astype(self._kv_store_dtype))
            # Query at absolute position p attends to cache positions
            # <= p — the chunk's own K/V are scattered into the pool
            # BEFORE the attention, so intra-chunk causality falls out
            # of the same positional mask as attention over earlier
            # chunks / cached prefix blocks (both impls).
            out = self._paged_attend(q, pool, l, tables, starts)
            x = self._ffn(self._proj(x, out, blk), blk)
        return pool, x

    def _build_prefill_chunk(self, n: int, c: int, NB: int):
        import jax
        import jax.numpy as jnp

        def prefill_chunk(params, cache, tokens, starts, lengths, tables):
            pool, logits = self._chunk_forward(
                params, cache, tokens, starts, lengths, tables, NB, c)
            return pool, jnp.argmax(logits, axis=-1)

        return jax.jit(prefill_chunk, donate_argnums=(1,))

    def prompt_logits(self, prompt: Sequence[int]) -> np.ndarray:
        """Final-position LM logits for ``prompt`` through the full paged
        pipeline on a throwaway pool — including the configured KV
        storage quantization and attention impl.  The bench's
        ``kv_dtype`` arm and the quantized-error-bound tests read their
        "max logit error" through this, so the number reflects the real
        serving path."""
        import jax.numpy as jnp
        if not 0 < len(prompt) <= self.max_len:
            raise ValueError(f"prompt length {len(prompt)} outside "
                             f"(0, {self.max_len}]")
        MB = self.max_blocks_per_seq
        need = -(-len(prompt) // self.block_tokens)
        pool = self._pool_arrays(need)
        table = np.full((1, MB), need, np.int32)
        table[0, :need] = np.arange(need)
        _, logits = self._chunk_forward(
            self.params, pool,
            jnp.asarray(np.asarray(prompt, np.int32)[None]),
            jnp.zeros((1,), jnp.int32),
            jnp.asarray([len(prompt)], jnp.int32),
            jnp.asarray(table), need, len(prompt))
        return np.asarray(logits)[0]

    def score_logits(self, tokens: Sequence[int]) -> np.ndarray:
        """``prompt_logits`` generalized to ALL positions: the LM logits
        ``[T, V]`` at every position of ``tokens`` through the real
        paged pipeline on a throwaway pool (``logits[p]`` is the model's
        distribution over the token at position ``p + 1``) — the
        ``/score`` endpoint's forward (docs/serving.md).  Shares
        ``_chunk_body`` with the speculative ``verify_chunk`` program,
        so scoring sees exactly the serving math, storage quantization
        included."""
        import jax.numpy as jnp
        if not 0 < len(tokens) <= self.max_len:
            raise ValueError(f"token count {len(tokens)} outside "
                             f"(0, {self.max_len}]")
        MB = self.max_blocks_per_seq
        need = -(-len(tokens) // self.block_tokens)
        pool = self._pool_arrays(need)
        table = np.full((1, MB), need, np.int32)
        table[0, :need] = np.arange(need)
        _, x = self._chunk_body(
            self.params, pool,
            jnp.asarray(np.asarray(tokens, np.int32)[None]),
            jnp.zeros((1,), jnp.int32),
            jnp.asarray([len(tokens)], jnp.int32),
            jnp.asarray(table), need, len(tokens))
        return np.asarray(self._logits(x, self.params))[0]

    def prefill_chunk(self, cache, chunks, starts, tables):
        """One iteration's prompt chunks: ``chunks[i]`` continues sequence
        i's prompt at absolute position ``starts[i]`` with physical blocks
        ``tables[i]``.  Returns ``(cache, next_tokens)``; the engine uses
        ``next_tokens[i]`` only when the chunk completes its prompt (the
        argmax at each chunk's last position)."""
        key, call_args = self._pack_chunk_args(cache, chunks, starts,
                                               tables)
        if key not in self._chunk_cache:
            self._chunk_cache[key] = self._build_prefill_chunk(*key)
        self._maybe_analyze("prefill_chunk", key, self._chunk_cache[key],
                            call_args)
        cache, nxt = self._chunk_cache[key](*call_args)
        return cache, np.asarray(nxt)[:len(chunks)]

    def _pack_chunk_args(self, cache, chunks, starts, tables):
        """Shared bucketing + padding for the chunk-program family
        (prefill_chunk / prefill_chunk_logits / verify_chunk): returns
        ``(compile_key, call_args)`` — ONE home for the (count,
        chunk-len, pool-geometry) keying discipline, so the family can
        never compile under inconsistent keys.  Pool geometry comes from
        the CACHE ARGUMENT, never from a mutable adapter attribute, and
        is part of the compile key: the traced program bakes the OOB
        hole sentinel (= num_blocks) into its closure, and an adapter is
        shareable across engines with different pool sizes (even
        interleaved) — a stale sentinel would silently scatter pad-tail
        K/V into a REAL block."""
        import jax.numpy as jnp
        n_bucket = _next_pow2(len(chunks))
        max_c = max(len(ch) for ch in chunks)
        c_bucket = prompt_bucket(max_c, cap=self.max_len)
        NB = int(cache["k"].shape[1])
        key = (n_bucket, c_bucket, NB)
        MB = self.max_blocks_per_seq
        tok = np.zeros((n_bucket, c_bucket), np.int32)
        st = np.zeros((n_bucket,), np.int32)
        ln = np.zeros((n_bucket,), np.int32)
        tab = np.full((n_bucket, MB), NB, np.int32)
        for i, (ch, s0, t) in enumerate(zip(chunks, starts, tables)):
            tok[i, :len(ch)] = ch
            st[i] = s0
            ln[i] = len(ch)
            tab[i, :len(t)] = t
        return key, (self.params, cache, jnp.asarray(tok),
                     jnp.asarray(st), jnp.asarray(ln), jnp.asarray(tab))

    def _build_prefill_chunk_logits(self, n: int, c: int, NB: int):
        import jax

        def prefill_chunk_logits(params, cache, tokens, starts, lengths,
                                 tables):
            return self._chunk_forward(params, cache, tokens, starts,
                                       lengths, tables, NB, c)

        return jax.jit(prefill_chunk_logits, donate_argnums=(1,))

    def prefill_chunk_logits(self, cache, chunks, starts, tables):
        """``prefill_chunk`` returning each row's final-position LM
        logits instead of their argmax — the sampled / n>1 first-token
        path: the engine draws the first generated token(s) on the host
        (an n-way fork needs n draws from ONE logit row, each with its
        own sample key).  Greedy batches keep the token-only program —
        this variant only runs when a sampled or forked request is in
        the chunk batch."""
        key, call_args = self._pack_chunk_args(cache, chunks, starts,
                                               tables)
        if key not in self._chunk_logits_cache:
            self._chunk_logits_cache[key] = \
                self._build_prefill_chunk_logits(*key)
        self._maybe_analyze("prefill_chunk_logits", key,
                            self._chunk_logits_cache[key], call_args)
        cache, logits = self._chunk_logits_cache[key](*call_args)
        return cache, np.asarray(logits)[:len(chunks)]

    def _build_verify_chunk(self, n: int, c: int, NB: int):
        import jax

        def verify_chunk(params, cache, tokens, starts, lengths, tables):
            pool, x = self._chunk_body(params, cache, tokens, starts,
                                       lengths, tables, NB, c)
            return pool, self._logits(x, params)

        return jax.jit(verify_chunk, donate_argnums=(1,))

    def verify_chunk(self, cache, chunks, starts, tables):
        """Speculative verify: run ``chunks[i]`` (the row's last emitted
        token + its k drafted tokens) through the FULL model in one
        multi-token step — the chunked-prefill machinery with
        per-sequence positions — scattering their K/V and returning the
        LM logits at EVERY chunk position ``[n, c, V]``.  ``logits[i,
        j]`` is the target distribution for the token at absolute
        position ``starts[i] + j + 1``; the engine accepts a drafted
        prefix against it and resamples the first rejection
        (docs/serving.md speculative decoding)."""
        key, call_args = self._pack_chunk_args(cache, chunks, starts,
                                               tables)
        if key not in self._verify_cache:
            self._verify_cache[key] = self._build_verify_chunk(*key)
        self._maybe_analyze("verify_chunk", key, self._verify_cache[key],
                            call_args)
        cache, logits = self._verify_cache[key](*call_args)
        return cache, np.asarray(logits)[:len(chunks)]

    # -- decode --------------------------------------------------------------

    def _paged_step_body(self, params, cache, tokens, positions, tables,
                         num_layers: int):
        """ONE home for the single-token paged decode forward (embed →
        per-layer scatter/attend/ffn → LM logits), traceable.  The three
        decode builders (greedy / in-jit sampled / truncated-stack
        draft) wrap this with their own head, so the hole-clamp table
        lookup, the quantized-scatter branch, and the position clamp can
        never diverge between them.

        tokens [B]; positions [B] (cache index this token's K/V lands
        at); tables [B, MB] block tables (entry NB for holes and
        inactive rows — scatter drops, the attention clamps + masks; NB
        is baked per pool geometry via the compile key).  Returns
        ``(pool, logits[B, V])``."""
        import jax.numpy as jnp
        BT, MB = self.block_tokens, self.max_blocks_per_seq
        pos = jnp.minimum(positions, self.max_len - 1)
        x = params["wte"]["embedding"][tokens] \
            + params["wpe"]["embedding"][pos]  # [B, d]
        pool = dict(cache)
        wblk = jnp.take_along_axis(
            tables, jnp.minimum(pos // BT, MB - 1)[:, None],
            axis=1)[:, 0]                             # [B]
        woff = pos % BT
        for l in range(num_layers):
            blk = params[f"block_{l}"]
            q, k, v = self._qkv(x, blk)               # [B, H, Dh]
            if self._kv_quantized:
                pool = self._quantized_scatter(pool, l, wblk, woff,
                                               k, v)
            else:
                pool["k"] = pool["k"].at[l, wblk, woff].set(
                    k.astype(self._kv_store_dtype))
                pool["v"] = pool["v"].at[l, wblk, woff].set(
                    v.astype(self._kv_store_dtype))
            out = self._paged_attend(q, pool, l, tables, pos)
            x = self._ffn(self._proj(x, out, blk), blk)
        return pool, self._logits(x, params)

    def _build_paged_decode(self, B: int):
        import jax
        import jax.numpy as jnp

        def decode_paged(params, cache, tokens, positions, tables):
            pool, logits = self._paged_step_body(
                params, cache, tokens, positions, tables, self.num_layers)
            return pool, jnp.argmax(logits, axis=-1)

        return jax.jit(decode_paged, donate_argnums=(1,))

    def decode_paged(self, cache, tokens, positions, tables):
        import jax.numpy as jnp
        # Geometry from the call's own arguments + compile key, for the
        # same shared-adapter reason as prefill_chunk (the program
        # closes over the batch width; num_blocks shapes the cache).
        key = (int(cache["k"].shape[1]), len(tokens))
        if self._paged_decode_fns.get(key) is None:
            self._paged_decode_fns[key] = self._build_paged_decode(
                len(tokens))
        call_args = (self.params, cache, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(positions, jnp.int32),
                     jnp.asarray(tables, jnp.int32))
        self._maybe_analyze("decode_paged", key,
                            self._paged_decode_fns[key], call_args)
        cache, nxt = self._paged_decode_fns[key](*call_args)
        return cache, np.asarray(nxt)

    def _build_paged_decode_logits(self, B: int):
        import jax

        def decode_paged_logits(params, cache, tokens, positions, tables):
            return self._paged_step_body(
                params, cache, tokens, positions, tables, self.num_layers)

        return jax.jit(decode_paged_logits, donate_argnums=(1,))

    def decode_paged_logits(self, cache, tokens, positions, tables):
        """``decode_paged`` returning each row's raw LM logits ``[B, V]``
        instead of their argmax — the hvdstream host-mode decode step:
        grammar-masked token selection and top-k logprob extraction both
        need the full distribution on the host (serve/structured.py,
        docs/serving.md)."""
        import jax.numpy as jnp
        key = (int(cache["k"].shape[1]), len(tokens))
        if self._paged_logits_fns.get(key) is None:
            self._paged_logits_fns[key] = self._build_paged_decode_logits(
                len(tokens))
        call_args = (self.params, cache, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(positions, jnp.int32),
                     jnp.asarray(tables, jnp.int32))
        self._maybe_analyze("decode_paged_logits", key,
                            self._paged_logits_fns[key], call_args)
        cache, logits = self._paged_logits_fns[key](*call_args)
        return cache, np.asarray(logits)

    def _build_paged_decode_sampled(self, B: int):
        """The paged decode program with in-jit seeded sampling: same
        forward as ``_build_paged_decode``, but the LM logits feed
        ``sampling.sample_batched`` with per-row base keys + sampling
        params as traced operands — one program per (pool, batch)
        geometry regardless of the request mix, and rows with
        temperature 0 return the argmax bit-identically to the greedy
        program."""
        import jax
        from . import sampling as _sampling

        def decode_paged_sampled(params, cache, tokens, positions, tables,
                                 keys, temps, top_ks, top_ps):
            pool, logits = self._paged_step_body(
                params, cache, tokens, positions, tables, self.num_layers)
            # The token this step emits OCCUPIES position fed+1 — the
            # fold value of its key (sampling.py module doc).
            toks = _sampling.sample_batched(
                logits, keys, positions + 1, temps, top_ks, top_ps)
            return pool, toks

        return jax.jit(decode_paged_sampled, donate_argnums=(1,))

    def decode_paged_sampled(self, cache, tokens, positions, tables,
                             keys, temps, top_ks, top_ps):
        """One sampled token step for the whole batch (see
        ``_build_paged_decode_sampled``); greedy-only batches keep
        ``decode_paged``."""
        import jax.numpy as jnp
        key = (int(cache["k"].shape[1]), len(tokens))
        if self._sampled_decode_fns.get(key) is None:
            self._sampled_decode_fns[key] = \
                self._build_paged_decode_sampled(len(tokens))
        call_args = (self.params, cache, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(positions, jnp.int32),
                     jnp.asarray(tables, jnp.int32),
                     jnp.asarray(keys, jnp.uint32),
                     jnp.asarray(temps, jnp.float32),
                     jnp.asarray(top_ks, jnp.int32),
                     jnp.asarray(top_ps, jnp.float32))
        self._maybe_analyze("decode_sampled", key,
                            self._sampled_decode_fns[key], call_args)
        cache, nxt = self._sampled_decode_fns[key](*call_args)
        return cache, np.asarray(nxt)

    def _build_draft_decode(self, B: int):
        """The truncated-stack draft step: blocks ``0..draft_layers-1``
        + the final LN / tied LM head, writing draft K/V into the SAME
        pool (layers 0..draft_layers-1 only).  Proposals are the
        draft's argmax — a point-mass q, which keeps rejection
        sampling exact (sampling.residual_sample) without shipping
        draft distributions to the host."""
        import jax
        import jax.numpy as jnp

        def draft_decode(params, cache, tokens, positions, tables):
            pool, logits = self._paged_step_body(
                params, cache, tokens, positions, tables,
                self.draft_layers)
            return pool, jnp.argmax(logits, axis=-1)

        return jax.jit(draft_decode, donate_argnums=(1,))

    def draft_decode(self, cache, tokens, positions, tables):
        """One draft proposal step (see ``_build_draft_decode``)."""
        import jax.numpy as jnp
        if not self.spec_capable:
            raise ValueError(
                "no draft stack configured: set HVD_SERVE_DRAFT_LAYERS "
                ">= 1 (or pass draft_layers=) to enable speculative "
                "decoding")
        key = (int(cache["k"].shape[1]), len(tokens))
        if self._draft_decode_fns.get(key) is None:
            self._draft_decode_fns[key] = self._build_draft_decode(
                len(tokens))
        call_args = (self.params, cache, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(positions, jnp.int32),
                     jnp.asarray(tables, jnp.int32))
        self._maybe_analyze("draft_decode", key,
                            self._draft_decode_fns[key], call_args)
        cache, nxt = self._draft_decode_fns[key](*call_args)
        return cache, np.asarray(nxt)

    def copy_block(self, cache, src: int, dst: int):
        """Copy-on-write data move: duplicate one physical block across
        all layers (the BlockManager already moved the reference).
        Jitted with the cache DONATED so XLA updates the pool in place —
        an eager ``.at[].set`` would materialize a second full pool to
        move one block."""
        import jax
        import jax.numpy as jnp
        if self._copy_block_fn is None:
            def copy_block(c, s, d):
                return {k: a.at[:, d].set(a[:, s]) for k, a in c.items()}
            self._copy_block_fn = jax.jit(copy_block, donate_argnums=(0,))
        return self._copy_block_fn(cache, jnp.int32(src), jnp.int32(dst))


def _unstack_if_scanned(params, num_layers: int):
    """Accept either param layout: ``scan_layers`` checkpoints (stacked
    ``blocks/block``) are converted to the unrolled ``block_i`` layout the
    adapter's per-layer loop indexes (models.unstack_block_params)."""
    inner = params.get("params", params)
    if "blocks" in inner:
        from ..models.transformer import unstack_block_params
        inner = unstack_block_params(inner)
    return inner


class MLPAdapter(ModelAdapter):
    """Cache-free stand-in model for engine-mechanics tests: the next
    token is ``argmax(MLP(one_hot(token)))`` — a deterministic Markov
    chain over the vocab, so batching/requeue/parity logic is exercised
    without transformer compile cost.  It consumes zero blocks
    (``kv_token_cost = 0``), so it needs no ``copy_block``.  Sampling
    draws from ``softmax(MLP(one_hot(token)))`` through the same keyed
    sampler as the transformer, and the spec draft is the model ITSELF
    (``draft_decode`` == greedy decode): a perfect proposer, which is
    what lets the bench's spec arm measure pure amortization
    (target calls per token → 1/(k+1)) without draft-quality noise."""

    kv_token_cost = 0
    block_tokens = 1
    max_blocks_per_seq = 0
    spec_capable = True

    def __init__(self, mlp, params, vocab_size: int, max_len: int = 1024):
        import jax
        import jax.numpy as jnp
        from . import sampling as _sampling
        self.vocab_size = vocab_size
        self.max_len = max_len
        # Named functions: a program is ``jit_<function>`` in a device
        # trace, and a lambda would be ``jit__lambda_``.

        def mlp_logits(tokens):
            return mlp.apply(
                {"params": params},
                jax.nn.one_hot(tokens, vocab_size)).astype(jnp.float32)

        def mlp_greedy(tokens):
            return jax.numpy.argmax(
                mlp.apply({"params": params},
                          jax.nn.one_hot(tokens, vocab_size)), axis=-1)

        def mlp_sampled(tokens, keys, positions, temps, top_ks, top_ps):
            return _sampling.sample_batched(
                mlp_logits(tokens), keys, positions + 1, temps, top_ks,
                top_ps)

        self._logits_of = jax.jit(mlp_logits)
        self._apply = jax.jit(mlp_greedy)
        self._sampled_step = jax.jit(mlp_sampled)

    def init_paged_cache(self, num_blocks: int, max_batch: int):
        return ()

    def prefill_chunk(self, cache, chunks, starts, tables):
        # Next token depends only on the chunk's last token; non-final
        # chunks' outputs are ignored by the engine.
        last = np.asarray([ch[-1] for ch in chunks], np.int32)
        return cache, np.asarray(self._apply(last))

    def prefill_chunk_logits(self, cache, chunks, starts, tables):
        last = np.asarray([ch[-1] for ch in chunks], np.int32)
        return cache, np.asarray(self._logits_of(last))

    def verify_chunk(self, cache, chunks, starts, tables):
        # Markov chain: logits at chunk position j depend only on the
        # chunk token at j — one batched apply over the flattened
        # [n*c] token block (the MLP folds non-batch dims) gives every
        # position's target distribution.
        n, c = len(chunks), max(len(ch) for ch in chunks)
        tok = np.zeros((n, c), np.int32)
        for i, ch in enumerate(chunks):
            tok[i, :len(ch)] = ch
        flat = np.asarray(self._logits_of(tok.reshape(-1)))
        return cache, flat.reshape(n, c, self.vocab_size)

    def decode_paged(self, cache, tokens, positions, tables):
        return cache, np.asarray(self._apply(np.asarray(tokens, np.int32)))

    def decode_paged_logits(self, cache, tokens, positions, tables):
        # Host-mode decode (hvdstream): the raw distribution per row.
        return cache, np.asarray(
            self._logits_of(np.asarray(tokens, np.int32)))

    def prompt_logits(self, prompt) -> np.ndarray:
        # Markov chain: the final-position distribution depends only on
        # the last prompt token (the /score parity reference).
        return np.asarray(
            self._logits_of(np.asarray([prompt[-1]], np.int32)))[0]

    def score_logits(self, tokens) -> np.ndarray:
        if not 0 < len(tokens) <= self.max_len:
            raise ValueError(f"token count {len(tokens)} outside "
                             f"(0, {self.max_len}]")
        return np.asarray(
            self._logits_of(np.asarray(tokens, np.int32)))

    def decode_paged_sampled(self, cache, tokens, positions, tables,
                             keys, temps, top_ks, top_ps):
        import jax.numpy as jnp
        nxt = self._sampled_step(
            jnp.asarray(tokens, jnp.int32), jnp.asarray(keys, jnp.uint32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32))
        return cache, np.asarray(nxt)

    def draft_decode(self, cache, tokens, positions, tables):
        # The draft IS the target (perfect proposer): greedy spec then
        # accepts every draft and the engine's amortization machinery is
        # exercised at its theoretical ceiling.
        return self.decode_paged(cache, tokens, positions, tables)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class _Seq:
    """One sequence's state.

    ``generated`` is the authoritative token list for THIS sequence: for
    a plain n==1 request it IS ``request.generated`` (the same list
    object — every legacy surface keeps working), for an n>1 fork it is
    the fork's own stream, copied into ``request.samples[sample_index]``
    at retirement.  ``parked`` marks a fork slot reserved at admission
    but not yet activated (the prompt is still prefilling through the
    group's primary sequence)."""
    __slots__ = ("request", "length", "prompt_pos", "table", "hashes",
                 "admit_seq", "published", "generated", "group",
                 "sample_index", "base_key", "parked", "resident",
                 "pending_fetch", "host_kv", "swap_step", "tier_credit",
                 "gstate")

    def __init__(self, request: Request, cached_tokens: int,
                 table: List[int], hashes: List[int], admit_seq: int):
        self.request = request
        self.length = cached_tokens      # tokens with K/V in the pool
        self.prompt_pos = cached_tokens  # prompt tokens consumed so far
        self.table = table               # physical block ids, logical order
        self.hashes = hashes             # prompt full-block chain hashes
        self.admit_seq = admit_seq       # admission order (preempt youngest)
        self.published = 0               # prefix-registered block watermark
        self.generated = request.generated  # n>1 members get own lists
        self.group: Optional[_ForkGroup] = None
        self.sample_index = 0
        self.base_key = None             # uint32[2] seq key (sampled only)
        self.parked = False              # reserved fork slot, pre-activation
        # Tiered-KV state (serve/tiering.py; inert defaults untiered):
        # a non-resident sequence's K/V lives host-ward, pending_fetch
        # maps table index -> (chain hash | swap key, issue time) of
        # in-flight tier fetches, host_kv holds a swapped-out sequence's
        # payloads, swap_step ages swap decisions by engine iteration,
        # and tier_credit is the token watermark a migration admits at.
        self.resident = True
        self.pending_fetch: Optional[dict] = None
        self.host_kv: Optional[list] = None
        self.swap_step = 0
        self.tier_credit = 0
        # hvdstream structured decoding (serve/structured.py): the
        # grammar automaton state AFTER the tokens in ``generated``.  A
        # preemption/requeue builds a fresh _Seq, so replayed decoding
        # restarts from ``request.grammar.start`` in lockstep with the
        # emptied token list.
        self.gstate = (request.grammar.start
                       if request.grammar is not None else None)

    @property
    def decoding(self) -> bool:
        return not self.parked and self.prompt_pos >= len(self.request.prompt)


class _ForkGroup:
    """One n>1 request's fork family: the primary (sample 0) prefills
    the prompt once; at prompt completion the group forks — every member
    maps the shared full prompt blocks through its own CoW block table
    and decodes independently.  The request completes when the LAST
    member retires; preemption/expiry/drain treat the family as one unit
    (half a request can never be requeued).

    ``reserve`` is the family's not-yet-allocated worst-case decode
    footprint — the (n-1) fork tails admission COUNTED in its budget
    but did not allocate (the forks grow into them at decode time:
    the CoW copy of the shared partial prompt block plus each fork's
    decode blocks).  ``_admit`` subtracts the live groups'
    reserves from the pool budget so a later admission round can never
    hand those blocks to someone else — which would turn preemption
    from a defensive path into a steady-state tax on every n>1
    request; each fork-side allocation consumes one unit."""
    __slots__ = ("request", "seqs", "completed", "forked", "reserve",
                 "reserve_cap")

    def __init__(self, request: Request):
        self.request = request
        self.seqs: List[_Seq] = []
        self.completed = 0
        self.forked = False
        self.reserve = 0
        self.reserve_cap = 0  # admission-time value; refunds never exceed it


class InferenceEngine:
    """One continuous-batching decode loop (one per serving replica).

    Owns: the model adapter, the slot table, the KV storage (block pool +
    BlockManager), and a worker thread running admit → prefill → decode
    forever.  Completion is per-request (batcher.Request events); the loop
    never blocks while any sequence is active.
    """

    def __init__(self, adapter: ModelAdapter,
                 batcher: Optional[DynamicBatcher] = None,
                 metrics: Optional[ServeMetrics] = None,
                 max_batch: Optional[int] = None,
                 replica_id: str = "replica-0",
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 warmup: Optional[bool] = None,
                 tiering: Optional[TierConfig] = None,
                 tier_client=None):
        self.adapter = adapter
        # Multi-model residency (serve/registry.py): named variants
        # sharing this engine's slots and paged pool.  ``adapter`` stays
        # the default variant's adapter (every legacy single-model path
        # reads it); requests carrying ``model`` resolve through
        # _adapter_for.  Versions feed the per-(model, version) prefix-
        # hash salt so cached prefixes never cross a weight boundary.
        self.default_model = "default"
        self._adapters: Dict[str, ModelAdapter] = {
            self.default_model: adapter}
        self._model_versions: Dict[str, int] = {self.default_model: 0}
        self.max_batch = max_batch if max_batch is not None else int(
            os.environ.get("HVD_SERVE_MAX_BATCH", "8"))
        self.batcher = batcher or DynamicBatcher()
        self.metrics = metrics or ServeMetrics()
        if self.batcher._on_shed is None:
            # Deadline sheds happen inside the batcher (at admission);
            # surface them in this engine's metrics ("expired" outcome
            # — and "shed" for brownout purges, which pass that reason).
            self.batcher._on_shed = \
                lambda req, why: self.metrics.count_request(
                    why, tenant=req.tenant)
        self.replica_id = replica_id
        # Brownout rung (serve/controller.py), set by the
        # FleetController and read lock-free in the loop (plain int,
        # GIL-atomic): >=3 disables speculative decoding — the greedy
        # fallback is bit-identical (the spec exactness contract), it
        # just stops spending draft compute and draft-tail KV blocks
        # under pressure.  The admission-side rungs live on the batcher.
        self.brownout_level = 0
        missing = _missing_paged_members(adapter)
        if missing:
            raise TypeError(
                f"{type(adapter).__name__} cannot serve: it lacks "
                f"{', '.join(missing)} (the paged interface, ModelAdapter)")
        # Per-replica observability of HOW attention runs (gather vs the
        # Pallas kernel) and how KV is stored — surfaced through
        # kv_stats()/replica.to_dict()/metrics exposition.
        self.attn_impl = getattr(adapter, "attn_impl", "gather")
        self.kv_dtype = getattr(adapter, "kv_dtype", "native")
        self._mb = int(getattr(adapter, "max_blocks_per_seq", 0))
        bt = int(getattr(adapter, "block_tokens", 1))
        nb = (num_blocks if num_blocks is not None
              else int(os.environ.get("HVD_SERVE_NUM_BLOCKS", "0")))
        if nb <= 0:
            # Default pool = max_batch × max_len tokens, shared, so
            # mixed-length traffic admits far more than max_batch
            # sequences of full length would.
            nb = self.max_batch * max(self._mb, 1)
        pc = (prefix_cache if prefix_cache is not None
              else os.environ.get("HVD_SERVE_PREFIX_CACHE", "1")
              not in ("0", "false"))
        bpb_fn = getattr(adapter, "paged_block_bytes", None)
        bpb = int(bpb_fn()) if callable(bpb_fn) else None
        # Tiered-KV hierarchy (serve/tiering.py, docs/serving.md):
        # explicit config wins, else HVD_SERVE_TIER gates the env
        # path.  Untiered stays a plain BlockManager — zero behavior
        # change on every existing deployment.
        self.tiering = (tiering if tiering is not None
                        else TierConfig.from_env())
        if self.tiering is not None and not self.tiering.enabled:
            self.tiering = None
        self._tier_client: Optional[TierClient] = None
        if self.tiering is not None:
            client = tier_client
            if client is None and self.tiering.kv_addr:
                from ..runner.http_server import KVStoreClient
                host, _, port = self.tiering.kv_addr.rpartition(":")
                client = KVStoreClient(host or "127.0.0.1", int(port))
            if client is not None and not isinstance(client, TierClient):
                client = TierClient(client, replica_id=replica_id)
            self._tier_client = client
            self.blocks = TieredBlockManager(
                nb, bt, self.tiering, prefix_cache=pc,
                bytes_per_block=bpb, client=client)
        else:
            self.blocks = BlockManager(
                nb, bt, prefix_cache=pc, bytes_per_block=bpb)
        chunk = (prefill_chunk if prefill_chunk is not None
                 else int(os.environ.get("HVD_SERVE_PREFILL_CHUNK", "64")))
        # <= 0 disables chunking: whole prompts prefill in one
        # iteration (the unchunked bench/interference baseline).
        self._chunk_budget = chunk if chunk > 0 else None
        self._cache = adapter.init_paged_cache(nb, self.max_batch)
        self._verify_pool_budget(nb)
        if self.tiering is not None:
            # Device IO pair + tier worker + loop-side arrival
            # plumbing.  Arrivals are (worker → loop) messages; the
            # deque is appended under no lock (worker) and drained
            # at iteration top (loop) — deque.append/popleft are
            # atomic, and _tier_event lets a stalled loop wake the
            # moment a fetch lands instead of polling.
            self.blocks.set_device_io(*make_block_io(self))
            self._tier_arrivals: deque = deque()
            self._tier_event = threading.Event()
            self._tier_worker: Optional[TierWorker] = None
            if self._tier_client is not None:
                self._tier_worker = TierWorker(
                    self.blocks, self._tier_client,
                    self._tier_notify, replica_id=replica_id)
            self._tier_stall_anchor: Optional[float] = None
            self.tier_faults = 0
            self.inflight_peak = 0
            self._tier_peeked: set = set()
        # Decode-algorithm layer (docs/serving.md sampling/spec): seeded
        # sampling + n>1 forking need the logits/sampled adapter
        # programs; speculative decoding additionally needs the
        # draft + multi-token verify pair.  Capabilities are checked
        # here (spec: loudly at construction) and per request at
        # admission (_fail_doomed) so a legacy adapter keeps serving
        # greedy n==1 exactly as before.
        self._sample_capable = (
            hasattr(adapter, "decode_paged_sampled")
            and hasattr(adapter, "prefill_chunk_logits"))
        sk = (spec_k if spec_k is not None
              else int(os.environ.get("HVD_SERVE_SPEC_K", "0")))
        if sk < 0:
            raise ValueError(f"spec_k must be >= 0, got {sk}")
        if sk > 0 and not (hasattr(adapter, "verify_chunk")
                           and hasattr(adapter, "draft_decode")
                           and getattr(adapter, "spec_capable", False)):
            raise ValueError(
                f"{type(adapter).__name__} has no usable draft for "
                f"speculative decoding (verify_chunk/draft_decode + "
                f"spec_capable — transformer adapters need "
                f"HVD_SERVE_DRAFT_LAYERS >= 1)")
        self.spec_k = sk
        # n>1 fork observability (/metrics + kv_stats/healthz): total
        # forked sequences created (n-1 per forked group) and requests
        # that forked at all.
        self.seq_forks = 0
        self.forked_requests = 0
        # Compiled token grammars (serve/structured.py), keyed by
        # (model, vocab_size, canonical schema JSON, eos) — compiling a
        # DFA over the vocab is pure and deterministic, so identical
        # schemas against the same resident model share one automaton.
        self._grammar_cache: Dict[tuple, object] = {}
        self._slots: List[Optional[object]] = [None] * self.max_batch
        # Deferred trace emissions (loop-thread only): span/flow
        # emission does shard-file IO under the tracer's lock, and the
        # lifecycle boundaries where spans become known sit inside
        # ``self._lock`` critical sections — emitting there would let a
        # slow disk stall the decode loop and every thread contending
        # on the engine lock.  The loop collects closures under the
        # lock and flushes them after release (_flush_trace_emits);
        # timestamps are captured at the boundary, so deferral changes
        # nothing in the artifact.
        self._trace_emits: List = []
        # Zero cold-start, AOT half (warmup(), docs/serving.md): replay
        # the (pow2 count, pow2 len) prefill/decode bucket ladder at
        # EVERY start() — construction AND mark_alive revival — so the
        # first real request after a scale-up or a roll never pays a
        # compile.  Off by default (HVD_SERVE_WARMUP): tests and
        # single-shot tools should not pay the ladder.
        self._warmup_enabled = (
            warmup if warmup is not None
            else os.environ.get("HVD_SERVE_WARMUP", "0")
            not in ("0", "false"))
        self.warmup_runs = 0
        self.last_warmup_ms = 0.0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._admit_counter = 0
        self._step_anchor: Optional[float] = None
        self.steps = 0
        # Fault injection (faultline): env-configured plans bootstrap at
        # construction; the per-iteration guard is a None check.
        _faultline.maybe_install_from_env()
        # Request tracing (obs): same constructor-time env bootstrap and
        # the same None-check hot-path discipline.
        _obs.maybe_install_from_env()

    def _verify_pool_budget(self, num_blocks: int) -> None:
        """hvdmem HVD302 at construction (docs/serving.md kv_headroom):
        verify the BlockManager's sizing — ``paged_block_bytes() *
        num_blocks`` plus this replica's weight bytes — against
        ``HVD_MEM_BUDGET_BYTES`` / the probed device HBM, BEFORE the
        first request can OOM the chip.  The headroom is exposed as
        ``kv_headroom_bytes`` on ``kv_stats()`` → healthz + /metrics; an
        overshoot is logged and published to ``core.analysis_reports()``
        exactly like a trace-time finding."""
        from ..analysis import memplan as _memplan
        pool_bytes = (self.blocks.bytes_per_block or 0) * num_blocks
        if not pool_bytes:
            # Adapter reports no per-block cost (e.g. a cache-free MLP):
            # fall back to what the pool arrays actually hold.
            pool_bytes = _memplan.params_bytes(self._cache)
        self.pool_bytes = int(pool_bytes)
        # Weight bytes sum over the DISTINCT resident adapters (a
        # LoRA-style variant shares most leaves with the base by
        # reference, but params_bytes walks whole trees — the sum is a
        # conservative upper bound, which is the right direction for a
        # budget check).
        distinct = {id(ad): ad for ad in self._adapters.values()}
        self.weight_bytes = sum(
            _memplan.params_bytes(getattr(ad, "params", None))
            for ad in distinct.values())
        report = _memplan.check_pool_budget(
            f"serve:{self.replica_id}:kv-pool", self.pool_bytes,
            self.weight_bytes)
        self.kv_headroom_bytes = report.headroom_bytes
        if not report.ok():
            _memplan.publish_report(report)
        # hvdshard static go/no-go (docs/serving.md): the pool verdict
        # above combined with the per-step comm budget (HVD401).  A
        # data-parallel replica's serve programs census zero collectives
        # (the ROADMAP-5 invariant) so step_comm_bytes defaults to 0 and
        # the comm half passes trivially; a tensor/pipeline-sharded
        # adapter declares its measured per-decode-step wire bytes.
        from ..analysis import shardplan as _shardplan
        self.plan_verdict = _shardplan.check_replica_plan(
            f"serve:{self.replica_id}:plan",
            pool_bytes=self.pool_bytes,
            weight_bytes=self.weight_bytes,
            step_comm_bytes=int(getattr(self.adapter,
                                        "step_comm_bytes", 0) or 0),
            step_dcn_bytes=int(getattr(self.adapter,
                                       "step_dcn_bytes", 0) or 0))
        if not self.plan_verdict.go:
            _shardplan.publish_verdict(self.plan_verdict)

    # -- multi-model residency (serve/registry.py) ---------------------------

    def _check_geometry(self, adapter) -> None:
        """A co-resident variant shares this engine's slot table and
        paged pool, so every shape the shared state bakes in must match
        the default adapter's — checked loudly at add/swap time, not at
        the first mismatched gather."""
        base = self.adapter
        missing = _missing_paged_members(adapter)
        if missing:
            raise ValueError(
                f"{type(adapter).__name__} cannot be resident: it lacks "
                f"{', '.join(missing)}")
        for attr in ("max_len", "block_tokens", "max_blocks_per_seq",
                     "kv_token_cost"):
            a, b = getattr(adapter, attr, None), getattr(base, attr, None)
            if a is not None and b is not None and a != b:
                raise ValueError(
                    f"variant adapter {attr}={a} != resident {attr}={b}")
        a_bpb = getattr(adapter, "paged_block_bytes", None)
        b_bpb = getattr(base, "paged_block_bytes", None)
        if callable(a_bpb) and callable(b_bpb) and a_bpb() != b_bpb():
            raise ValueError(
                f"variant paged_block_bytes {a_bpb()} != resident "
                f"{b_bpb()} — the pool layout cannot serve both")
        a_cfg, b_cfg = getattr(adapter, "cfg", None), getattr(base, "cfg",
                                                             None)
        if a_cfg is not None and b_cfg is not None:
            for attr in ("num_layers", "num_heads", "d_model"):
                if getattr(a_cfg, attr) != getattr(b_cfg, attr):
                    raise ValueError(
                        f"variant cfg.{attr}={getattr(a_cfg, attr)} != "
                        f"resident {getattr(b_cfg, attr)}")
        sample_capable = (hasattr(adapter, "decode_paged_sampled")
                          and hasattr(adapter, "prefill_chunk_logits"))
        if self._sample_capable and not sample_capable:
            raise ValueError(
                f"{type(adapter).__name__} lacks the sampled programs "
                f"this engine advertises (decode_paged_sampled/"
                f"prefill_chunk_logits)")

    def add_model(self, name: str, adapter, version: int = 0) -> None:
        """Make variant ``name`` resident: it shares the slot table and
        the paged pool with the default model (requests partition by
        model per iteration, _prefill_step/_decode_once).  The programs
        address exclusively through block tables: an all-hole row (a
        row of another model's group) touches nothing."""
        if name == self.default_model or name in self._adapters:
            raise ValueError(f"model {name!r} already resident; use "
                             "swap_model to change its weights")
        self._check_geometry(adapter)
        with self._lock:
            self._adapters[name] = adapter
            self._model_versions[name] = int(version)
        # Re-run the budget check: a second resident variant's weights
        # count against the same HBM budget.
        self._verify_pool_budget(self.blocks.num_blocks)

    def swap_model(self, name: str, adapter, version: int) -> None:
        """Install new weights for resident variant ``name`` (the
        registry's roll path).  Only legal on a STOPPED engine — the
        roll machinery drains this replica first (mark_dead), so no
        iteration is mid-flight over the old adapter's programs; the
        subsequent start() re-runs warmup over the new adapter."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError(
                f"{self.replica_id}: swap_model requires a stopped "
                f"engine (drain it first — registry.roll does)")
        if name not in self._adapters:
            raise KeyError(f"model {name!r} not resident")
        self._check_geometry(adapter)
        if self.tiering is not None and name in self._model_versions:
            # Unpublish the OLD version's fleet directory entries while
            # _prefix_salt still yields the old salt — a peer
            # mid-migration of the rolled chain must miss and degrade
            # to recompute under the new weights (the version-salted
            # eviction audit, tiering.unpublish_salt).
            try:
                self.blocks.unpublish_salt(self._prefix_salt(name))
            except Exception as e:
                get_logger().warning(
                    "%s: tier unpublish on roll failed: %s",
                    self.replica_id, e)
        self._adapters[name] = adapter
        self._model_versions[name] = int(version)
        if name == self.default_model:
            self.adapter = adapter
        self._verify_pool_budget(self.blocks.num_blocks)

    def _adapter_for(self, model: Optional[str]):
        return self._adapters[model or self.default_model]

    def _grammar_for(self, ad, r: Request):
        """Compile (or fetch the cached) token-level grammar automaton
        for ``r.schema`` against adapter ``ad``'s vocabulary
        (serve/structured.py).  Raises ValueError on unsupported schema
        keywords or a byte-opaque vocabulary — surfaced as a 400."""
        from .structured import TokenGrammar
        if r.eos_id is None:
            raise ValueError(
                "structured decoding needs eos_id (the grammar allows "
                "EOS exactly at accepting states)")
        vocab = ad.token_strings()
        if vocab is None:
            raise ValueError(
                f"structured decoding needs a byte-transparent "
                f"vocabulary; {type(ad).__name__} (vocab_size="
                f"{ad.vocab_size}) does not expose token strings")
        key = (r.model or self.default_model, int(ad.vocab_size),
               json.dumps(r.schema, sort_keys=True), int(r.eos_id))
        g = self._grammar_cache.get(key)
        if g is None:
            g = TokenGrammar(r.schema, vocab, int(r.eos_id))
            self._grammar_cache[key] = g
        return g

    def score_tokens(self, tokens: Sequence[int],
                     model: Optional[str] = None,
                     top: int = 0) -> List[Optional[dict]]:
        """Per-token logprobs of ``tokens`` under the resident model —
        the /score endpoint (docs/serving.md).  Runs the adapter's
        ``score_logits`` program over a throwaway paged pool WITHOUT the
        engine lock (same discipline as ``prompt_logits``: pure forward,
        no shared slot/pool state touched).  Entry ``p`` is ``None`` at
        position 0 (nothing conditions it) and otherwise ``{"token",
        "logprob"[, "top"]}`` where ``logprob`` is
        ``log_softmax(logits[p-1])[token]``."""
        ad = self._adapter_for(model)
        if not hasattr(ad, "score_logits"):
            raise ValueError(
                f"{type(ad).__name__} has no score_logits program, "
                f"which /score needs")
        tokens = [int(t) for t in tokens]
        for t in tokens:
            if not 0 <= t < ad.vocab_size:
                raise ValueError(
                    f"token {t} out of range [0, {ad.vocab_size})")
        logits = np.asarray(ad.score_logits(tokens), np.float64)
        out: List[Optional[dict]] = []
        for p, t in enumerate(tokens):
            if p == 0:
                out.append(None)
                continue
            row = logits[p - 1]
            m = float(np.max(row))
            lse = m + math.log(float(np.sum(np.exp(row - m))))
            entry = {"token": t, "logprob": float(row[t] - lse)}
            if top > 0:
                idx = np.argsort(row)[::-1][:top]
                entry["top"] = [
                    {"token": int(i), "logprob": float(row[i] - lse)}
                    for i in idx]
            out.append(entry)
        return out

    def _prefix_salt(self, model: Optional[str]) -> int:
        from .registry import model_salt
        name = model or self.default_model
        return model_salt(name, self._model_versions.get(name, 0))

    # -- introspection -------------------------------------------------------

    @property
    def active_count(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots if s is not None)

    def load(self) -> int:
        """Routing load: in-flight sequences + queued requests."""
        return self.active_count + self.batcher.depth()

    def kv_stats(self) -> dict:
        """Block-pool utilization / prefix-cache statistics — sampled by
        metrics render and replica healthz.  Carries the engine's
        attention impl + KV storage dtype so both are visible per
        replica on every export surface."""
        stats = self.blocks.stats()
        stats["attn_impl"] = self.attn_impl
        stats["kv_dtype"] = self.kv_dtype
        # n>1 CoW fork + speculative config observability (ISSUE 11):
        # sequence forks ride the same kv_stats surface as the block-
        # level CoW copies, so /metrics + healthz show the n-best path
        # from the first forked request.
        stats["seq_forks"] = self.seq_forks
        stats["forked_requests"] = self.forked_requests
        stats["spec_k"] = self.spec_k
        # hvdmem pool-budget plan (docs/serving.md kv_headroom): the
        # pool + weight bytes this replica holds, and — when a budget is
        # known (HVD_MEM_BUDGET_BYTES / probed HBM) — the headroom left.
        stats["pool_bytes"] = self.pool_bytes
        stats["weight_bytes"] = self.weight_bytes
        if self.kv_headroom_bytes is not None:
            stats["kv_headroom_bytes"] = self.kv_headroom_bytes
        # hvdshard replica-plan go/no-go (docs/serving.md): the static
        # admission verdict from construction — pool-vs-HBM (HVD302)
        # combined with the per-step comm budget (HVD401) — rides
        # kv_stats so healthz + /metrics show whether this replica's
        # plan was admitted and with how much headroom.
        stats["plan_go"] = self.plan_verdict.go
        stats["plan_findings"] = len(self.plan_verdict.findings)
        if self.tiering is not None and "tier" in stats:
            # Loop-side tier counters next to the manager's: stall
            # episodes and the oversubscription high-water mark (the
            # tiered admit-ratio numerator in the bench).
            stats["tier"]["faults"] = self.tier_faults
            stats["tier"]["inflight_peak"] = self.inflight_peak
        return stats

    def tier_unpublish(self) -> int:
        """Withdraw this replica's fleet-tier directory entries (the
        mark_dead path): a peer must never resolve a chain hash to a
        dead holder.  Returns entries dropped (0 untiered)."""
        if self.tiering is None:
            return 0
        return self.blocks.unpublish_all()

    # -- warmup (zero cold-start) --------------------------------------------

    def _warmup_counts(self) -> List[int]:
        """Every reachable batch-count bucket: pow2 ladder up to
        ``max_batch``, plus ``max_batch`` itself when it is not a power
        of two (its bucket ``_next_pow2(max_batch)`` is only hit by a
        full admission)."""
        counts: List[int] = []
        n = 1
        while n <= self.max_batch:
            counts.append(n)
            n *= 2
        if counts[-1] != self.max_batch:
            counts.append(self.max_batch)
        return counts

    def warmup(self) -> float:
        """Replay every (count, len) prefill bucket plus one decode step
        per resident adapter so the XLA programs this engine serves from
        are compiled BEFORE mark_alive reports the replica healthy.
        Only legal against an empty slot table (a busy engine skips: the
        live cache must not see warmup writes); combined with the
        persistent compile cache ``hvd.init()`` enables, a freshly
        grown replica pays disk-cache lookups, not compiles.  Returns
        wall-clock milliseconds spent (0.0 when skipped or failed —
        warmup failure degrades to cold serving, never to a dead
        replica)."""
        with self._lock:
            if any(s is not None for s in self._slots):
                get_logger().warning(
                    "%s: warmup skipped — slots busy", self.replica_id)
                return 0.0
        t0 = time.monotonic()
        try:
            self._warmup_lattice()
        except Exception as exc:
            get_logger().warning(
                "%s: warmup failed (%s: %s); serving cold",
                self.replica_id, type(exc).__name__, exc)
            return 0.0
        ms = (time.monotonic() - t0) * 1e3
        self.warmup_runs += 1
        self.last_warmup_ms = ms
        self.metrics.observe_warmup(self.replica_id, ms)
        get_logger().info("%s: warmup #%d done in %.1f ms",
                          self.replica_id, self.warmup_runs, ms)
        return ms

    def _warmup_lattice(self) -> None:
        """Drive every resident adapter (id-deduped: variants sharing
        one adapter object compile once) through the bucket lattice.
        Chunks are all-hole — empty block tables map every
        K/V write onto the dropped sentinel row — so retained prefix
        blocks and pool accounting are untouched; only the compile
        caches change.  Decode warms at its single runtime shape:
        tokens ``(max_batch,)`` and tables exactly ``(max_batch,
        self._mb)`` (shapes are compile keys — a padded stand-in would
        warm a program the loop never runs)."""
        nb = self.blocks.capacity
        distinct = {id(ad): ad for ad in self._adapters.values()}
        for ad in distinct.values():
            cap = min(self._chunk_budget or ad.max_len, ad.max_len)
            lens: List[int] = []
            c = prompt_bucket(1, cap=ad.max_len)
            top = prompt_bucket(cap, cap=ad.max_len)
            while True:
                lens.append(c)
                if c >= top:
                    break
                c = min(c * 2, top)
            for n in self._warmup_counts():
                for c in lens:
                    self._cache, _ = ad.prefill_chunk(
                        self._cache, [[0] * c for _ in range(n)],
                        [0] * n, [[] for _ in range(n)])
            tokens = np.zeros((self.max_batch,), np.int32)
            positions = np.zeros((self.max_batch,), np.int32)
            tables = np.full((self.max_batch, self._mb), nb, np.int32)
            self._cache, _ = ad.decode_paged(
                self._cache, tokens, positions, tables)

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "InferenceEngine":
        if self._thread is not None:
            if self._thread.is_alive() and not self._stop.is_set():
                return self  # already running
            # A prior stop() timed out on a wedged iteration (stop()
            # keeps the handle in that case): the old loop must be OUT
            # before the restart — clearing _stop under a live loop
            # would leave two threads racing the batcher, the slot
            # table, and the donated cache arrays.
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                raise RuntimeError(
                    f"{self.replica_id}: previous engine loop has not "
                    f"exited; cannot restart")
            self._thread = None
        # A revived engine (drain()/stop() then mark_alive) restarts on
        # the same object: the stop flag must clear or the new thread
        # exits before its first iteration.
        self._stop.clear()
        # Warmup runs at EVERY start — construction and mark_alive
        # revival alike (the revived-replica cold-start bug: warmup only
        # at construction would make a controller-grown replica re-pay
        # every bucket compile on its first real requests).  It runs
        # BEFORE the loop thread spawns, so mark_alive's "healthy" means
        # "warm": routing only rebalances onto this replica once its
        # bucket programs are compiled.
        if self._warmup_enabled:
            self.warmup()
        if self.tiering is not None and self._tier_worker is not None:
            self._tier_worker.start()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"hvd-serve-engine-{self.replica_id}")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            # Keep the handle if the join timed out (an iteration wedged
            # past 30 s): start() must be able to see the still-running
            # loop and refuse to spawn a second one next to it.
            if not self._thread.is_alive():
                self._thread = None
        if self.tiering is not None and self._tier_worker is not None:
            self._tier_worker.stop()

    def drain(self) -> List[Request]:
        """Stop the loop and return all in-flight requests WITHOUT
        completing them (dead-replica path: the scheduler resubmits them
        elsewhere).  No cache state travels: generated-so-far tokens are
        discarded and paged block references are released here — greedy
        decoding reproduces the output exactly on the new replica, whose
        own prefix cache (if any) re-fills from the prompt."""
        self.stop()
        now = time.monotonic()
        with self._lock:
            inflight = []
            seen = set()
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                self.blocks.free_table(s.table)
                self._slots[i] = None
                r = s.request
                if id(r) in seen:
                    continue  # another member of the same fork family
                seen.add(id(r))
                r.generated = []
                if r.token_logprobs is not None:
                    # Replay regenerates logprobs from position 0; the
                    # stream sink's dedupe keeps delivery exactly-once.
                    r.token_logprobs = []
                if r.samples is not None:
                    r.samples = [None] * r.n
                if s.group is not None:
                    s.group.completed = 0
                    s.group.forked = False
                r.requeues += 1
                # Failover bookkeeping: the next admission (on the
                # survivor) emits the resubmission span from here.
                r.resubmitted_at = now
                inflight.append(r)
            return inflight

    # -- shared helpers ------------------------------------------------------

    def _free_slots(self) -> List[int]:
        with self._lock:
            return [i for i, s in enumerate(self._slots) if s is None]

    @staticmethod
    def _seq_finished(s: "_Seq", token: int) -> bool:
        """Per-sequence finish check: a fork finishes on
        its OWN stream, not the request's sample-0 mirror.  Finish
        decisions record ``finish_reason`` on n==1 requests (hvdstream:
        the terminal event / response field): ``stop`` (EOS), ``length``
        (max_new_tokens), or ``grammar`` — the structured-decoding
        automaton reached an accepting state with no continuation, so
        the document is complete and decoding further could only break
        it."""
        r = s.request
        solo = s.group is None
        if r.eos_id is not None and token == r.eos_id:
            if solo:
                r.finish_reason = "stop"
            return True
        if len(s.generated) >= r.max_new_tokens:
            if solo:
                r.finish_reason = "length"
            return True
        if (r.grammar is not None and s.gstate is not None
                and r.grammar.exhausted(s.gstate)):
            r.finish_reason = "grammar"
            return True
        return False

    @staticmethod
    def _publish_stream(r: Request, generated: List[int],
                        logprob=None) -> None:
        """Offer the just-appended last token of ``generated`` to the
        request's streaming sink (hvdstream, serve/streaming.py).  Holds
        whatever lock the caller holds — publish is non-blocking and
        never does IO, which is the never-hold-the-engine-lock-across-
        socket-writes contract; position-keyed dedupe in the sink makes
        failover/preemption replays invisible to the client."""
        if r.sink is not None:
            r.sink.publish(len(generated) - 1, generated[-1], logprob)

    @staticmethod
    def _logprob_entry(raw, tok: int, k: int) -> dict:
        """One ``token_logprobs`` record (hvdstream ``logprobs: k``):
        the chosen token's log-probability under the RAW logits — before
        any grammar mask or temperature/top-k/top-p filter, so the
        number is the model's own belief — plus the top-``k``
        alternatives from the same distribution."""
        row = np.asarray(raw, np.float64)
        m = float(np.max(row))
        lse = m + math.log(float(np.sum(np.exp(row - m))))
        entry = {"token": int(tok), "logprob": float(row[tok] - lse)}
        if k > 0:
            idx = np.argsort(row)[::-1][:k]
            entry["top"] = [{"token": int(i),
                             "logprob": float(row[i] - lse)}
                            for i in idx]
        return entry

    def _retire_seq(self, i: int, s: "_Seq") -> None:
        """Free one finished sequence's slot + block refs and complete
        its request — group-aware: an n>1 request completes when its
        LAST fork retires (each fork's stream lands in
        ``request.samples[sample_index]``; ``request.generated`` mirrors
        sample 0).  Caller holds ``self._lock``."""
        self.blocks.free_table(s.table)
        # The table is FREED now; clear it so group-level paths that
        # walk ``group.seqs`` later (a pool-exhaustion preempt of a
        # surviving member, expiry) can never free it a second time — a
        # double free either raises or, if the block was reallocated in
        # between, silently releases another sequence's live block.
        s.table = []
        self._slots[i] = None
        r = s.request
        if s.group is None:
            self._complete(r)
            return
        r.samples[s.sample_index] = list(s.generated)
        s.group.completed += 1
        if s.group.completed == r.n:
            r.generated = list(r.samples[0])
            self._complete(r)

    def _fork_group(self, s: "_Seq", logits, now: float) -> None:
        """The fork moment of an n>1 request: its prompt K/V is fully in
        the pool — draw every member's first token from the primary's
        final-position ``logits`` row (each with its OWN (seed, sample)
        key) and activate the parked forks on the shared prompt blocks.
        This is the first real consumer of ``BlockManager``'s
        copy-on-write path: every member maps the same physical prompt
        blocks (one reference each), and the first divergent append into
        the shared partial block forks a private copy
        (``ensure_writable`` in ``_ensure_write_blocks``).  Caller holds
        ``self._lock``."""
        r = s.request
        group = s.group
        P = len(r.prompt)
        shared = self._blocks_for_tokens(P)
        r.first_token_at = now
        r.stage_add("prefill", now)
        self.metrics.observe_ttft((now - r.submitted_at) * 1e3)
        # observe_ttft counted sample 0's first token; the other n-1
        # members emitted theirs in the same instant.
        self.metrics.count_tokens(r.n - 1)
        self.seq_forks += r.n - 1
        self.forked_requests += 1
        group.forked = True
        self._defer_flow(r)
        # Two passes: EVERY fork must take its block references before
        # ANY member can retire — a primary finishing on its first token
        # would otherwise free the shared prompt blocks (the unregistered
        # partial block lands on the free list) while later forks are
        # about to ref them, and a ref on a free-listed block aliases it
        # with the next allocation (two sequences sharing one physical
        # block, then a double free).
        finished: List["_Seq"] = []
        for f in group.seqs:
            if f is not s:
                f.table = list(s.table[:shared])
                for bid in f.table:
                    self.blocks.ref(bid)
                f.length = s.length
                f.prompt_pos = P
                f.parked = False
            tok = (_sampling.sample_host(
                logits, f.base_key, P, r.temperature, r.top_k, r.top_p)
                if r.sampled else int(np.argmax(logits)))
            f.generated.append(tok)
            if self._seq_finished(f, tok):
                finished.append(f)
        for f in finished:
            for slot, cur in enumerate(self._slots):
                if cur is f:
                    self._retire_seq(slot, f)
                    break

    def _flush_trace_emits(self) -> None:
        """Run deferred span/flow emissions OUTSIDE the engine lock
        (loop thread only — every deferring site is)."""
        if not self._trace_emits:
            return
        pending, self._trace_emits = self._trace_emits, []
        for fn in pending:
            try:
                fn()
            except Exception:
                pass  # tracing must never take down the decode loop

    def _defer_flow(self, r: Request) -> None:
        """Queue one token-stream flow step for a traced request —
        every token-append site defers through here (flushed outside
        the engine lock)."""
        if r.trace is None or _obs.TRACER is None:
            return

        def emit(t=_obs.TRACER, r=r):
            t.flow(r.trace, "token-stream", self.replica_id)
        self._trace_emits.append(emit)

    def _complete(self, r: Request) -> None:
        now = time.monotonic()
        if r.finish_reason is None:
            # The engine-cap retirement paths (s.length >= max_len)
            # complete without a _seq_finished verdict — the client-visible
            # reason is the same as exhausting max_new_tokens.
            r.finish_reason = "length"
        if r.first_token_at is not None:
            r.stage_add("decode", now)
        # Stage decomposition feeds /metrics unconditionally (the
        # autoscaler inputs, docs/observability.md); the SPANS only for
        # sampled requests.  Each stage is emitted twice: the all-tiers
        # aggregate and the per-QoS-tier series ("stage|tier" key) the
        # controller's per-class SLO accounting reads.
        for stage, ms in r.stage_ms.items():
            if ms > 0.0:
                self.metrics.observe_stage(stage, ms)
                self.metrics.observe_stage(f"{stage}|{r.qos}", ms)
                # Per-tenant stage series (serve/tenancy.py; its own
                # dict on the metrics side — a tenant label must never
                # parse as a tier).
                self.metrics.observe_tenant_stage(r.tenant, stage, ms)
        # End-to-end latency per tier (the stage partition's sum — the
        # windowed-p99 input of the controller's SLO check) + the
        # service-time EWMA behind the load-aware Retry-After hint.
        self.metrics.observe_request_ms(r.qos, sum(r.stage_ms.values()))
        if r.trace is not None and _obs.TRACER is not None:
            t = _obs.TRACER

            def emit(t=t, r=r, now=now, first=r.first_token_at,
                     ntok=len(r.generated)):
                if first is not None:
                    t.emit_span(r.trace, "decode", first, now,
                                self.replica_id,
                                args={"tokens": ntok,
                                      "requeues": r.requeues})
                t.flow(r.trace, "token-stream", self.replica_id,
                       end=True)
                if r._emit_root:
                    # Scheduler-sampled request (no HTTP front-end —
                    # bench / direct submit): the root span is the whole
                    # request, emitted here where completion is known.
                    t.emit_span(r.trace, "request", r.submitted_at, now,
                                self.replica_id,
                                args={"request_id": r.request_id},
                                root=True)
            self._trace_emits.append(emit)
        r.complete()
        self.metrics.count_request("ok", tenant=r.tenant)

    def _observe_admission(self, requests: Sequence[Request]) -> None:
        """Per-request admission boundary: credit the wait to queue (or
        retry after a failover/preemption requeue) and emit the
        queue-wait / resubmission span for sampled requests."""
        now = time.monotonic()
        tracer = _obs.TRACER
        for r in requests:
            stage = "retry" if r.requeues else "queue"
            prev = r.stage_add(stage, now)
            if r.trace is None or tracer is None:
                r.resubmitted_at = None
                continue
            try:
                if r.resubmitted_at is not None:
                    # The failover span the merged fleet trace shows
                    # crossing replicas: requeue time → this admission,
                    # attributed to the replica that picked the work up.
                    tracer.emit_span(
                        r.trace, "resubmission", r.resubmitted_at, now,
                        self.replica_id,
                        args={"to": self.replica_id,
                              "requeues": r.requeues})
                    r.resubmitted_at = None
                else:
                    tracer.emit_span(
                        r.trace, "queue-wait", prev, now,
                        self.replica_id,
                        args={"replica": self.replica_id})
                tracer.instant(r.trace, "admission", self.replica_id,
                               args={"replica": self.replica_id}, t=now)
            except Exception:
                pass

    def _fail_doomed(self, r: Request) -> bool:
        """Requests that can never run on this engine fail loudly at
        admission.  Returns True when the request was failed."""
        # Deadline propagation (docs/fault_injection.md): a request whose
        # budget is already gone is never prefilled — prefill is the
        # expensive phase, and its output could only ever be thrown away.
        # The batcher pops expired requests at admission too; this covers
        # the window between its queue walk and the prefill call (and
        # requeued work whose budget died in transit).
        if r.expired():
            r.fail(DeadlineExceededError(
                f"{r.request_id} expired before prefill "
                f"({time.monotonic() - r.submitted_at:.3f}s since submit)"))
            self.metrics.count_request("expired", tenant=r.tenant)
            return True
        # Client gone before prefill (hvdstream): the handler flagged a
        # write-time disconnect — never spend the prefill on a request
        # nobody is reading.
        if r.cancelled:
            r.fail(RuntimeError(
                f"{r.request_id} client disconnected before prefill"))
            self.metrics.count_request(r.cancel_reason or "client_gone",
                                       tenant=r.tenant)
            return True
        # Unknown model variant: routing filters candidates on residency
        # (replica.submit), so this fires only for direct engine submits
        # or a variant that left the fleet between routing and admission
        # — loudly either way, never silently served the default model.
        if r.model is not None and r.model not in self._adapters:
            r.fail(ValueError(
                f"{r.request_id}: unknown model {r.model!r} on "
                f"{self.replica_id} (resident: "
                f"{sorted(self._adapters)})"))
            self.metrics.count_request("error", tenant=r.tenant)
            return True
        ad = self._adapter_for(r.model)
        total = len(r.prompt) + r.max_new_tokens
        if total > ad.max_len:
            r.fail(ValueError(
                f"{r.request_id}: prompt+max_new_tokens {total} exceeds "
                f"max_len {ad.max_len}"))
            self.metrics.count_request("error", tenant=r.tenant)
            return True
        # Sampling / n>1 need the logits + sampled adapter programs —
        # fail loudly instead of silently serving a greedy single answer
        # to a sampled n-best request.
        if (r.sampled or r.n > 1) and not self._sample_capable:
            r.fail(ValueError(
                f"{r.request_id}: sampling/n>1 needs an adapter with "
                f"prefill_chunk_logits/decode_paged_sampled "
                f"(adapter {type(self.adapter).__name__})"))
            self.metrics.count_request("error", tenant=r.tenant)
            return True
        if r.n > self.max_batch:
            r.fail(ValueError(
                f"{r.request_id}: n={r.n} exceeds the engine's "
                f"max_batch {self.max_batch} decode slots"))
            self.metrics.count_request("error", tenant=r.tenant)
            return True
        # hvdstream structured decoding / per-token logprobs need the
        # host-mode decode step (raw logits on the host:
        # decode_paged_logits) — fail loudly rather than silently drop
        # the mask or the logprobs (serve/structured.py, docs/serving.md).
        if r.schema is not None or r.logprobs is not None:
            if (not self._sample_capable
                    or not hasattr(ad, "decode_paged_logits")):
                r.fail(ValueError(
                    f"{r.request_id}: schema/logprobs need an adapter "
                    f"with decode_paged_logits + prefill_chunk_logits "
                    f"(adapter {type(ad).__name__})"))
                self.metrics.count_request("error", tenant=r.tenant)
                return True
        if r.schema is not None and r.grammar is None:
            try:
                r.grammar = self._grammar_for(ad, r)
            except ValueError as e:
                r.fail(ValueError(f"{r.request_id}: {e}"))
                self.metrics.count_request("error", tenant=r.tenant)
                return True
        # Same cost formula as admission's cost/hard_cap (incl.
        # kv_token_cost and the n>1 shared-prompt + n-tails shape) — a
        # mismatch would let _take's hard_cap bypass pop a request this
        # check then declines to fail: an infinite requeue livelock.
        if self._mb and self._request_cost_blocks(r) > self.blocks.capacity:
            r.fail(ValueError(
                f"{r.request_id}: needs "
                f"{self._request_cost_blocks(r)} KV blocks but the "
                f"pool holds {self.blocks.capacity}"))
            self.metrics.count_request("error", tenant=r.tenant)
            return True
        return False

    def _expire_inflight(self) -> int:
        """Engine-side deadline check, once per iteration: an in-flight
        sequence whose client deadline passed is failed NOW (its handler
        is about to answer 504 anyway) and its slot + KV blocks return to
        the pool instead of decoding tokens nobody will read.  Returns
        the number of sequences expired."""
        expired = 0
        now = time.monotonic()
        with self._lock:
            failed = set()
            for i, s in enumerate(self._slots):
                if s is None or not (s.request.expired(now)
                                     or s.request.cancelled):
                    continue
                # A fork family expires as one unit: fail/count once,
                # free every member slot's blocks (this loop visits each
                # member in turn — only the first fails the request).
                if id(s.request) not in failed:
                    failed.add(id(s.request))
                    ntokens = len(s.generated)
                    if s.request.expired(now):
                        s.request.fail(DeadlineExceededError(
                            f"{s.request.request_id} deadline expired "
                            f"mid-flight ({ntokens} token(s) "
                            f"generated)"))
                        outcome, mark = "expired", "deadline-expired"
                    else:
                        # hvdstream: the handler observed the client
                        # hang up mid-stream and called cancel() — the
                        # engine reaps the sequence here, at the same
                        # boundary deadline expiry uses, so blocks are
                        # freed and the slot reopens within one
                        # iteration (docs/serving.md streaming).
                        s.request.fail(RuntimeError(
                            f"{s.request.request_id} client "
                            f"disconnected mid-flight ({ntokens} "
                            f"token(s) generated)"))
                        outcome = s.request.cancel_reason or "client_gone"
                        mark = "client-gone"
                    self.metrics.count_request(outcome,
                                               tenant=s.request.tenant)
                    if s.request.trace is not None \
                            and _obs.TRACER is not None:
                        def emit(t=_obs.TRACER, r=s.request, now=now,
                                 ntok=ntokens, mark=mark):
                            t.instant(r.trace, mark,
                                      self.replica_id,
                                      args={"tokens": ntok}, t=now)
                        self._trace_emits.append(emit)
                self.blocks.free_table(s.table)
                self._slots[i] = None
                expired += 1
        self._flush_trace_emits()
        return expired

    # -- fault injection (faultline) -----------------------------------------

    def _faultline_step(self) -> None:
        """``engine.step`` injection point, consulted at the top of every
        loop iteration (the step boundary).  ``poison-step`` raises into
        the loop's recovery path exactly like an organic XLA/runtime
        failure; ``slow-decode`` stalls the iteration; ``pool-corrupt-
        block`` drops retained prefix blocks (their contents are now
        suspect, so they must leave the registry rather than serve stale
        K/V to a later prefix hit)."""
        for f in _faultline.fire("engine.step", self.replica_id):
            if f.kind == "slow-decode":
                time.sleep(f.param or 0.02)
            elif f.kind == "pool-corrupt-block":
                n = self.blocks.invalidate_retained(max(int(f.param), 1))
                get_logger().warning(
                    "%s: faultline scrubbed %d retained KV block(s)",
                    self.replica_id, n)
            elif f.kind == "poison-step":
                raise FaultInjected(
                    f"faultline: poisoned step on {self.replica_id} "
                    f"(step {self.steps})")

    # -- admission, prefill and decode ---------------------------------------

    def _blocks_for_tokens(self, tokens: int) -> int:
        if not self._mb:
            return 0
        return self.blocks.blocks_for(
            tokens * getattr(self.adapter, "kv_token_cost", 1))

    def _request_cost_blocks(self, r: Request) -> int:
        """Lifetime KV-block footprint of one request — the admission
        cost.  n == 1: prompt + max_new positions.  n > 1: the FULL
        prompt blocks are shared by every fork (counted once), each of
        the n forks privately owns its tail — the partial last prompt
        block (CoW-forked on first divergent append) plus its decode
        region.  This is the worst case; refcounted sharing can only
        use less (e.g. the last fork writes the partial block in
        place)."""
        base = self._blocks_for_tokens(len(r.prompt) + r.max_new_tokens)
        if r.n <= 1 or not self._mb:
            return base
        cost = getattr(self.adapter, "kv_token_cost", 1)
        shared_full = (len(r.prompt) * cost) // self.blocks.block_tokens
        return base + (r.n - 1) * (base - shared_full)

    def _reserved_blocks(self) -> int:
        """Outstanding fork-tail reservations across the live fork
        groups (each counted once) — blocks the admission budget must
        treat as spoken-for even though they are not yet allocated."""
        seen, total = set(), 0
        with self._lock:
            for s in self._slots:
                g = s.group if s is not None else None
                if g is not None and id(g) not in seen:
                    seen.add(id(g))
                    total += g.reserve
        return total

    # -- tiered-KV hierarchy (serve/tiering.py, docs/serving.md) -------------

    def _tier_notify(self, msg: tuple) -> None:
        """Worker → loop arrival (any worker thread): enqueue the
        result and wake a stalled loop.  deque.append is atomic; the
        loop drains at the next iteration top (_tier_schedule)."""
        self._tier_arrivals.append(msg)
        self._tier_event.set()

    def _tier_committed_blocks(self) -> int:
        """Worst-case lifetime blocks the DISTINCT in-flight requests
        have committed against the oversubscribed admission budget."""
        with self._lock:
            seen = {id(s.request): s.request
                    for s in self._slots if s is not None}
        return sum(self._request_cost_blocks(r) for r in seen.values())

    def _tier_plan_migration(self, seq: "_Seq") -> None:
        """Extend ``seq``'s admission-time prefix hit fleet-wide: probe
        the block directory for a contiguous continuation past the
        local hit, claim device blocks for it, and stage the fetch plan
        on ``seq.pending_fetch`` (jobs are submitted once the slot is
        assigned).  ``tier_credit`` is the token watermark the sequence
        will resume prefill from when every fetch lands; any failure
        clears the plan and the blocks are simply prefilled locally —
        bit-identical by construction."""
        bt = self.blocks.block_tokens
        d = len(seq.table)  # = local cached blocks at this point
        usable = (len(seq.request.prompt) - 1) // bt
        if d >= usable:
            return
        k = self.blocks.remote_hits(seq.hashes[d:usable])
        if k <= 0:
            return
        try:
            mig = self.blocks.allocate(k)
        except NoFreeBlocksError:
            return  # pool contended; local prefill covers it
        seq.table.extend(mig)
        now = time.monotonic()
        seq.pending_fetch = {d + j: (seq.hashes[d + j], now)
                             for j in range(k)}
        seq.tier_credit = (d + k) * bt

    def _tier_grow(self, sel):
        """Lazy tiered allocation (the demand-paging half of the
        oversubscribed admission): grow each selected sequence's table
        to cover its prefill chunk, swapping younger residents host-
        ward under pressure (_tier_relieve) and shrinking the chunk —
        or sitting the sequence out this iteration — when the device
        pool is truly full.  Relief victims are strictly younger than
        their requester, so they always appear LATER in the admit-
        ordered selection and are dropped by the resident guard before
        their chunk is built."""
        bt = self.blocks.block_tokens
        out = []
        for i, s, take in sel:
            if not s.resident or s.pending_fetch is not None:
                continue  # swapped out by an earlier entry's relief
            need = ((s.prompt_pos + take - 1) // bt + 1 - len(s.table)
                    if take > 0 else 0)
            while need > 0:
                try:
                    s.table.extend(self.blocks.allocate(need))
                    need = 0
                except NoFreeBlocksError:
                    if not self._tier_relieve(s):
                        covered = len(s.table) * bt - s.prompt_pos
                        take = max(min(take, covered), 0)
                        need = 0
            if take > 0:
                out.append((i, s, take))
        return out

    def _tier_relieve(self, requester: "_Seq") -> bool:
        """Demote-over-preempt: on pool exhaustion, swap the youngest
        eligible RESIDENT sequence host-ward instead of preempting it
        back to the prompt — its tokens and K/V survive, it resumes
        after a later swap-in, and the preempted-requests counter stays
        flat.  Eligibility: strictly younger than the requester (so a
        relief victim can never already sit in the current pass's ok
        list), a plain n==1 sequence (fork families pin their shared
        blocks), not mid-fetch, and quantum-aged (no thrash)."""
        q = self.tiering.quantum
        with self._lock:
            cands = [(j, t) for j, t in enumerate(self._slots)
                     if t is not None and t is not requester
                     and t.resident and t.group is None
                     and t.pending_fetch is None and t.table
                     and t.admit_seq > requester.admit_seq
                     and (self.steps - t.swap_step) >= q]
        if not cands:
            return False
        slot, victim = max(cands, key=lambda c: c[1].admit_seq)
        self._tier_swap_out(slot, victim)
        return True

    def _tier_swap_out(self, slot: int, s: "_Seq") -> None:
        """Move one sequence's device blocks host-ward: extract the
        payloads (device IO, loop thread, no lock), then atomically
        mark it non-resident and release its blocks.  Registered prompt
        blocks become retained prefix blocks as usual — the host copy
        only has to cover this sequence's private tail exactly."""
        payloads = [self.blocks.extract_block(bid) for bid in s.table]
        with self._lock:
            if self._slots[slot] is not s:
                return
            s.host_kv = payloads
            s.resident = False
            s.swap_step = self.steps
            table, s.table = s.table, []
        self.blocks.free_table(table)
        self.blocks.count_swap(out_blocks=len(table))
        self.metrics.count_tier_bytes(
            spill=len(table) * (self.blocks.bytes_per_block or 0))

    def _tier_swap_in(self, slot: int, s: "_Seq") -> bool:
        """Resume a swapped-out sequence: claim device blocks, insert
        the host payloads, and issue async fetches (the ahead-of-decode
        prefetch) for any payload that demoted to the KV tier — the
        sequence turns resident when the last fetch lands
        (_tier_apply), stalling the loop only if nothing else is
        runnable meanwhile."""
        n = len(s.host_kv) if s.host_kv else 0
        if n == 0:
            with self._lock:
                if self._slots[slot] is s:
                    s.resident = True
                    s.swap_step = self.steps
            return True
        try:
            fresh = self.blocks.allocate(n)
        except NoFreeBlocksError:
            q = self.tiering.quantum
            with self._lock:
                cands = [(j, t) for j, t in enumerate(self._slots)
                         if t is not None and t is not s and t.resident
                         and t.group is None and t.pending_fetch is None
                         and t.table
                         and (self.steps - t.swap_step) >= q]
            if not cands:
                return False  # nobody evictable; retry next iteration
            vslot, victim = max(cands, key=lambda c: c[1].admit_seq)
            self._tier_swap_out(vslot, victim)
            try:
                fresh = self.blocks.allocate(n)
            except NoFreeBlocksError:
                return False
        now = time.monotonic()
        pend: Dict[int, tuple] = {}
        jobs = []
        for idx, payload in enumerate(s.host_kv):
            if isinstance(payload, tuple):  # ("kv", key): demoted
                pend[idx] = (payload[1], now)
                jobs.append(("fetch_swap", s, slot, idx, payload[1]))
            else:
                self.blocks.note_pending(fresh[idx], payload)
                self.blocks.apply_pending(fresh[idx])
        with self._lock:
            if self._slots[slot] is not s:
                self.blocks.free_table(fresh)
                return False
            s.table = fresh
            s.host_kv = None
            s.swap_step = self.steps
            if pend:
                s.pending_fetch = pend
            else:
                s.resident = True
        for job in jobs:
            self._tier_worker.submit(job)
        if jobs:
            # FIFO worker: the GC lands strictly after the fetches.
            self._tier_worker.submit(("drop_swap", [j[4] for j in jobs]))
        self.blocks.count_swap(in_blocks=n)
        self.metrics.count_tier_bytes(
            promote=n * (self.blocks.bytes_per_block or 0))
        return True

    def _tier_schedule(self) -> None:
        """Iteration-top tier pass: arrivals → timeouts → rotation →
        demotes → queue-peek prefetch (module doc in tiering.py)."""
        self.blocks.note_step(self.steps)
        self._tier_event.clear()
        while self._tier_arrivals:
            self._tier_apply(self._tier_arrivals.popleft())
        timeout = self.tiering.fetch_timeout_s
        now = time.monotonic()
        with self._lock:
            stale = [(i, s) for i, s in enumerate(self._slots)
                     if s is not None and s.pending_fetch
                     and any(now - t0 > timeout
                             for _, t0 in s.pending_fetch.values())]
        for i, s in stale:
            self._tier_cancel_pending(i, s)
        # Rotation: the oldest swapped-out sequence comes back when its
        # quantum expired, or immediately when nothing resident can run
        # (starvation-freedom: admit order bounds every wait).
        with self._lock:
            swapped = [(i, s) for i, s in enumerate(self._slots)
                       if s is not None and not s.resident
                       and s.pending_fetch is None]
            resident_work = any(
                s is not None and s.resident and not s.parked
                for s in self._slots)
        if swapped:
            swapped.sort(key=lambda t: t[1].admit_seq)
            i, s = swapped[0]
            if (not resident_work
                    or (self.steps - s.swap_step) >= self.tiering.quantum):
                self._tier_swap_in(i, s)
        if self._tier_worker is not None:
            for h, entry in self.blocks.demote_candidates():
                self._tier_worker.submit(("demote", h, entry))
            self._tier_demote_swapped()
            self._tier_peek()

    def _tier_demote_swapped(self) -> None:
        """Swapped-out sequences cold past HVD_SERVE_TIER_DEMOTE_ITERS
        export their host payloads to the KV-server tier (replica-
        private swap blobs): the payload entry becomes a ("kv", key)
        sentinel the next swap-in resolves with an async fetch_swap.
        The single worker queue is FIFO, so the put always lands before
        any later fetch of the same key."""
        di = self.tiering.demote_iters
        with self._lock:
            cold = [s for s in self._slots
                    if s is not None and not s.resident
                    and s.host_kv is not None
                    and s.pending_fetch is None
                    and (self.steps - s.swap_step) >= di]
        moved = 0
        for s in cold:
            for idx, payload in enumerate(s.host_kv):
                if isinstance(payload, tuple):
                    continue
                key = f"{self.replica_id}/{s.admit_seq}/{idx}"
                self._tier_worker.submit(("put_swap", key, payload))
                s.host_kv[idx] = ("kv", key)
                moved += 1
        if moved:
            bpb = self.blocks.bytes_per_block or 0
            self.blocks.count_demote(moved)
            self.metrics.count_tier_bytes(demote=moved * bpb)

    def _tier_peek(self) -> None:
        """Queue-peek prefetch: hash the next HVD_SERVE_TIER_PREFETCH
        queued prompts and fetch their unknown chain blocks from the
        fleet tier into the HOST tier ahead of admission — when the
        peek wins its race, admission's lookup_prefix promotes the
        staged blocks synchronously and the migration never even needs
        an in-band fetch."""
        depth = self.tiering.prefetch
        if depth <= 0:
            return
        try:
            peeked = self.batcher.peek(depth)
        except Exception:
            return
        if len(self._tier_peeked) > 4096:
            self._tier_peeked.clear()
        bt = self.blocks.block_tokens
        for prompt, model in peeked:
            usable = (len(prompt) - 1) // bt
            if usable <= 0:
                continue
            hs = chain_hashes(prompt, bt,
                              salt=self._prefix_salt(model))[:usable]
            for h in hs:
                if h in self._tier_peeked:
                    continue
                self._tier_peeked.add(h)
                if (self.blocks.registered_block(h) is not None
                        or self.blocks.host_contains(h)):
                    continue
                self._tier_worker.submit(("peek", h))

    def _tier_publish(self, jobs) -> None:
        """Ship newly completed prefix chains to the fleet tier.  The
        payload extract is synchronous (full prefix blocks are
        immutable, so the content is stable) but guarded: if the hash
        unregistered between the claim and the extract (eviction /
        spill), the publication is abandoned — the directory must
        never point at bytes that no longer match their hash."""
        for h, salt, bid in jobs:
            if not self.blocks.mark_publishing(h):
                continue
            if self.blocks.registered_block(h) != bid:
                self.blocks.note_published(h, salt, False)
                continue
            payload = self.blocks.extract_block(bid)
            if self.blocks.registered_block(h) != bid:
                self.blocks.note_published(h, salt, False)
                continue
            self._tier_worker.submit(("publish", h, salt, payload))

    def _tier_apply(self, msg: tuple) -> None:
        """Apply one worker arrival on the loop thread (the only thread
        doing device IO).  Stale arrivals — the slot moved on, the
        fetch was cancelled — are dropped; a None payload is a fetch
        that exhausted its retries and degrades via cancel."""
        kind = msg[0]
        if kind == "staged":
            _, h, payload, entry = msg
            self.blocks.stage_host(h, payload, entry)
            return
        _, seq, slot, idx, payload = msg
        with self._lock:
            if (self._slots[slot] is not seq or not seq.pending_fetch
                    or idx not in seq.pending_fetch):
                return
        if payload is None:
            self._tier_cancel_pending(slot, seq)
            return
        bid = seq.table[idx]
        self.blocks.note_pending(bid, payload)
        self.blocks.apply_pending(bid)
        done = False
        with self._lock:
            if self._slots[slot] is seq and seq.pending_fetch:
                seq.pending_fetch.pop(idx, None)
                if not seq.pending_fetch:
                    seq.pending_fetch = None
                    done = True
        if done:
            self._tier_finalize(slot, seq)

    def _tier_finalize(self, slot: int, seq: "_Seq") -> None:
        """The last in-flight fetch landed: a migration admits the
        sequence at its credit watermark (the migrated prefix is K/V it
        never prefills), a swap-in turns the sequence resident again.
        Either way an open stall episode ends here."""
        bt = self.blocks.block_tokens
        if seq.tier_credit > 0:
            salt = self._prefix_salt(seq.request.model)
            gained = 0
            with self._lock:
                if self._slots[slot] is seq:
                    for b in range(seq.prompt_pos // bt,
                                   seq.tier_credit // bt):
                        self.blocks.register(seq.hashes[b], seq.table[b],
                                             salt=salt)
                    gained = seq.tier_credit - seq.prompt_pos
                    seq.prompt_pos = seq.length = seq.tier_credit
                    seq.published = max(seq.published,
                                        seq.tier_credit // bt)
                    seq.tier_credit = 0
            if gained > 0:
                self.blocks.count_migrated(gained // bt, gained)
                self.metrics.count_tier_migration(gained)
        else:
            with self._lock:
                if self._slots[slot] is seq:
                    seq.resident = True
                    seq.swap_step = self.steps
        self._tier_stall_end(seq)

    def _tier_cancel_pending(self, slot: int, seq: "_Seq") -> None:
        """A tier fetch died (dropped past the retry budget, timed out,
        or its holder unpublished mid-flight).  A migration degrades to
        recompute: the plan clears WITHOUT credit and chunked prefill
        simply computes those blocks — bit-identical by construction
        (the soak test pins it).  A swap-in has no prompt-side recovery
        for mid-decode state, so the sequence takes the legacy preempt
        path — restart from the prompt, equally exact."""
        with self._lock:
            if self._slots[slot] is not seq or seq.pending_fetch is None:
                return
            migration = seq.tier_credit > 0
            seq.pending_fetch = None
            seq.tier_credit = 0
        if migration:
            self.blocks.count_migration_failure()
        else:
            self._preempt(slot, seq)
        self._tier_stall_end(seq)

    def _tier_stall_end(self, seq: Optional["_Seq"] = None) -> None:
        """Close an open tier-fault stall episode: count it, histogram
        it (part of the inter-decode-step p99 contract), and emit a
        ``tier-fault`` span on the request that resolved it."""
        anchor = self._tier_stall_anchor
        if anchor is None:
            return
        self._tier_stall_anchor = None
        now = time.monotonic()
        dt_ms = (now - anchor) * 1e3
        self.tier_faults += 1
        self.metrics.observe_tier_stall(dt_ms)
        r = seq.request if seq is not None else None
        if r is not None and r.trace is not None \
                and _obs.TRACER is not None:
            try:
                _obs.TRACER.emit_span(
                    r.trace, "tier-fault", anchor, now, self.replica_id,
                    args={"stall_ms": round(dt_ms, 3)})
            except Exception:
                pass

    def _tier_idle_wait(self, pre: int, dec: int) -> None:
        """Stall accounting at the iteration bottom: zero progress with
        tier fetches in flight means the loop is FAULTING on the tier —
        the prefetch lost its race.  Anchor the episode (one fault per
        episode, however many iterations it spans) and sleep on the
        arrival event instead of spinning."""
        if pre or dec:
            self._tier_stall_anchor = None
            return
        with self._lock:
            pending = any(s is not None and s.pending_fetch
                          for s in self._slots)
        if not pending:
            self._tier_stall_anchor = None
            return
        if self._tier_stall_anchor is None:
            self._tier_stall_anchor = time.monotonic()
        self._tier_event.wait(timeout=0.002)

    def _admit(self, block_s: float) -> int:
        free = self._free_slots()
        if not free:
            return 0
        use_blocks = self._mb > 0
        # A sequence's whole lifetime fits prompt + max_new_tokens cache
        # positions, so admission reserves exactly that, not max_len —
        # no decode-time
        # growth can exhaust the pool, so preemption stays a defensive
        # path instead of a steady-state tax.  n>1 fork tails are
        # reserved, not allocated (the forks grow into them at decode
        # time), so the live groups' outstanding reserves come off the
        # budget here.
        tiered = use_blocks and self.tiering is not None
        if tiered:
            # Demote-over-preempt admission (serve/tiering.py): in-
            # flight K/V beyond the device pool lives host-ward, so the
            # budget oversubscribes the pool by HVD_SERVE_TIER_OVERSUB
            # minus what the live requests have already committed —
            # cold sequences swap out instead of being preempted.  The
            # hard cap stays the DEVICE capacity: a decoding sequence
            # must still fit the pool while resident.
            budget = max(int(self.blocks.capacity * self.tiering.oversub)
                         - self._tier_committed_blocks(), 0)
        elif use_blocks:
            budget = max(self.blocks.available()
                         - self._reserved_blocks(), 0)
        admitted = self.batcher.get_admission(
            len(free), block_s=block_s,
            budget=budget if use_blocks else None,
            cost=self._request_cost_blocks if use_blocks else None,
            hard_cap=self.blocks.capacity if use_blocks else None)
        if not admitted:
            return 0
        self._observe_admission(admitted)
        cursor = 0
        for idx, r in enumerate(admitted):
            if self._fail_doomed(r):
                continue
            if r.n > len(free) - cursor:
                # An n>1 request reserves its WHOLE fork family's decode
                # slots at admission (the forks activate at prompt
                # completion — their slots must not be stolen by a later
                # admission in between).  Not enough left this round:
                # put it and everything after back in order.
                self.batcher.requeue_front(admitted[idx:])
                break
            cached_ids: List[int] = []
            cached_tokens = 0
            hashes: List[int] = []
            if use_blocks:
                if self.blocks.prefix_cache_enabled:
                    # Hash once; lookup reuses them (hashing is
                    # O(prompt) Python work on the decode-critical
                    # engine thread).
                    # Salted per (model, version) — equal tokens under
                    # different weights must never share K/V; salt 0 for
                    # (default, v0) keeps legacy hashes byte-exact.
                    hashes = chain_hashes(r.prompt,
                                          self.blocks.block_tokens,
                                          salt=self._prefix_salt(r.model))
                    cached_ids, cached_tokens = \
                        self.blocks.lookup_prefix(r.prompt, hashes=hashes)
                # Tiered n==1 admission is LAZY: the oversubscribed
                # budget admitted more lifetimes than the device pool
                # holds, so blocks are claimed chunk-by-chunk in
                # _tier_grow (prefill) / _ensure_write_blocks (decode)
                # — demand paging against the pool, with swap-out as
                # the pressure valve.  n>1 families keep the eager
                # reservation (their fork tails must never be paged
                # out from under a live group).
                if tiered and r.n == 1:
                    need = 0
                else:
                    need = self._blocks_for_tokens(
                        len(r.prompt) + r.max_new_tokens) - len(cached_ids)
                try:
                    fresh = self.blocks.allocate(need) if need > 0 else []
                except NoFreeBlocksError:
                    # The admission budget counted retained blocks an
                    # earlier request in THIS batch just claimed.  Put
                    # this and every later admitted request back in order
                    # and stop admitting this round.
                    self.blocks.free_table(cached_ids)
                    self.batcher.requeue_front(admitted[idx:])
                    break
            else:
                fresh = []
            seq = _Seq(r, cached_tokens, cached_ids + fresh, hashes,
                       self._admit_counter)
            if (tiered and r.n == 1 and hashes
                    and self._tier_worker is not None):
                # Cross-replica prefix migration: where the LOCAL
                # lookup stopped, probe the fleet block directory for
                # a contiguous continuation and fetch those blocks
                # over the KV transport instead of re-prefilling them.
                # Fetches are async (the ahead-of-decode prefetcher);
                # the sequence prefills only after they land or fail.
                self._tier_plan_migration(seq)
            self._admit_counter += 1
            if r.sampled:
                seq.base_key = _sampling.seq_key(r.seed, 0)
            group: Optional[_ForkGroup] = None
            if r.n > 1:
                # The fork family: the primary keeps its own token list
                # (request.generated stays the sample-0 mirror filled at
                # completion); n-1 parked members reserve their slots
                # now and activate at the fork moment (_fork_group).
                # The fork tails — everything this admission COUNTED
                # (_request_cost_blocks) beyond the primary's own
                # lifetime — become the group's block reservation.
                group = _ForkGroup(r)
                if use_blocks:
                    group.reserve = (
                        self._request_cost_blocks(r)
                        - self._blocks_for_tokens(
                            len(r.prompt) + r.max_new_tokens))
                    group.reserve_cap = group.reserve
                seq.group = group
                seq.generated = []
                group.seqs.append(seq)
            r.replica_id = self.replica_id
            with self._lock:
                slot = free[cursor]
                self._slots[slot] = seq
                cursor += 1
                for i in range(1, r.n):
                    f = _Seq(r, 0, [], [], seq.admit_seq)
                    f.group = group
                    f.sample_index = i
                    f.generated = []
                    f.parked = True
                    if r.sampled:
                        f.base_key = _sampling.seq_key(r.seed, i)
                    group.seqs.append(f)
                    self._slots[free[cursor]] = f
                    cursor += 1
            if seq.pending_fetch:
                # Slot is assigned — the arrivals can now verify
                # (seq, slot) identity; issue the migration fetches.
                for bidx, (h, _t0) in sorted(seq.pending_fetch.items()):
                    self._tier_worker.submit(
                        ("fetch", seq, slot, bidx, h))
        if tiered:
            with self._lock:
                inflight = len({id(s.request) for s in self._slots
                                if s is not None})
            if inflight > self.inflight_peak:
                # Oversubscription high-water mark — the tiered
                # admit-ratio numerator in the bench.
                self.inflight_peak = inflight
        return cursor

    def _prefill_step(self) -> int:
        """Advance prompt prefills by at most ``HVD_SERVE_PREFILL_CHUNK``
        tokens total (Sarathi-style per-iteration budget), oldest sequence
        first, in ONE batched chunk-prefill call.  Returns prompt tokens
        processed."""
        with self._lock:
            pending = [(i, s) for i, s in enumerate(self._slots)
                       if s is not None and not s.parked
                       and not s.decoding and s.resident
                       and s.pending_fetch is None]
        if not pending:
            return 0
        pending.sort(key=lambda t: t[1].admit_seq)
        budget = self._chunk_budget if self._chunk_budget is not None \
            else float("inf")
        sel: List[Tuple[int, _Seq, int]] = []
        for i, s in pending:
            if budget <= 0:
                break
            take = int(min(len(s.request.prompt) - s.prompt_pos, budget))
            sel.append((i, s, take))
            budget -= take
        if self.tiering is not None:
            sel = self._tier_grow(sel)
            if not sel:
                return 0
        chunks = [s.request.prompt[s.prompt_pos:s.prompt_pos + take]
                  for _, s, take in sel]
        starts = [s.prompt_pos for _, s, _ in sel]
        tables = [list(s.table) for _, s, _ in sel]
        # A batch containing any sampled or n>1 row runs the logits
        # variant: first tokens are drawn on the host (an n-way fork
        # draws n tokens from ONE logit row, each with its own sample
        # key).  Greedy-only batches keep the token-only program — the
        # pre-sampling fast path, bit-for-bit.
        use_logits = self._sample_capable and any(
            s.request.sampled or s.request.n > 1
            or s.request.grammar is not None
            or s.request.logprobs is not None for _, s, _ in sel)
        t0 = time.monotonic()
        # Multi-model partition: one chunk-prefill call per resident
        # variant in this selection, threading the SHARED pool cache
        # sequentially (donation-safe — each call consumes the previous
        # one's output).  Single-model batches take exactly the legacy
        # one-call path: one group holding every row.
        by_model: Dict[Optional[str], List[int]] = {}
        for j, (_, s, _) in enumerate(sel):
            by_model.setdefault(s.request.model, []).append(j)
        first: List = [None] * len(sel)
        for model, idxs in by_model.items():
            ad = self._adapter_for(model)
            g_chunks = [chunks[j] for j in idxs]
            g_starts = [starts[j] for j in idxs]
            g_tables = [tables[j] for j in idxs]
            if use_logits:
                self._cache, g_first = ad.prefill_chunk_logits(
                    self._cache, g_chunks, g_starts, g_tables)
            else:
                self._cache, g_first = ad.prefill_chunk(
                    self._cache, g_chunks, g_starts, g_tables)
            for j, tok in zip(idxs, g_first):
                first[j] = tok
        now = time.monotonic()
        if _obs.TRACER is not None:
            # One prefill-chunk span per TRACED sequence in this batched
            # call (same t0/now — they shared the compute), so a long
            # prompt's chunk-by-chunk streaming is visible per request.
            for (_, s, take), start in zip(sel, starts):
                r = s.request
                if r.trace is None or take <= 0:
                    continue
                try:
                    _obs.TRACER.emit_span(
                        r.trace, "prefill-chunk", t0, now,
                        self.replica_id,
                        args={"tokens": take, "start": start,
                              "batched": len(sel)})
                except Exception:
                    pass
        total = 0
        bt = self.blocks.block_tokens
        tiered = self.tiering is not None
        publishing = (tiered and self._tier_worker is not None
                      and self.tiering.publish)
        pub_jobs: List[Tuple[int, int, int]] = []
        with self._lock:
            for (i, s, take), tok in zip(sel, first):
                if self._slots[i] is not s:
                    continue  # drained concurrently
                s.prompt_pos += take
                s.length += take
                total += take
                if self._mb and s.hashes:
                    # Publish blocks COMPLETED BY THIS CHUNK for prefix
                    # reuse (watermarked — re-walking from 0 would be
                    # quadratic in prompt length; cached-hit blocks are
                    # already registered and skip via the no-op path).
                    # s.hashes is empty when prefix caching is off.
                    # Tiered: the salt rides along (per-version scrub on
                    # roll), and each newly completed chain becomes a
                    # fleet-directory publication candidate — migratable
                    # to a peer replica instead of re-prefilled there.
                    salt = (self._prefix_salt(s.request.model)
                            if tiered else 0)
                    for b in range(s.published, s.prompt_pos // bt):
                        self.blocks.register(s.hashes[b], s.table[b],
                                             salt=salt)
                        if publishing:
                            pub_jobs.append(
                                (s.hashes[b], salt, s.table[b]))
                    s.published = max(s.published, s.prompt_pos // bt)
                if not s.decoding:
                    continue
                r = s.request
                if r.n > 1:
                    # Fork moment: the prompt's K/V is complete — draw
                    # every member's first token from this row's logits
                    # and activate the parked forks on the shared
                    # prompt blocks.
                    self._fork_group(s, tok, now)
                    continue
                entry = None
                if use_logits:
                    # hvdstream host rows: the grammar mask rides
                    # sample_host's ``allowed`` hook (greedy = masked
                    # argmax, sampled = mask-then-filter), and logprob
                    # records read the RAW row before either.
                    mask = (r.grammar.allowed_mask(s.gstate)
                            if r.grammar is not None else None)
                    if r.sampled or mask is not None:
                        raw = tok
                        tok = _sampling.sample_host(
                            raw, s.base_key, len(r.prompt),
                            r.temperature, r.top_k, r.top_p,
                            allowed=mask)
                    else:
                        raw = tok
                        tok = int(np.argmax(tok))
                    if r.logprobs is not None:
                        entry = self._logprob_entry(raw, tok, r.logprobs)
                        r.token_logprobs.append(entry)
                else:
                    tok = int(tok)
                if r.grammar is not None and tok != r.eos_id:
                    s.gstate = r.grammar.advance_token(s.gstate, tok)
                r.first_token_at = now
                s.generated.append(tok)
                self._publish_stream(r, s.generated, entry)
                r.stage_add("prefill", now)
                self.metrics.observe_ttft((now - r.submitted_at) * 1e3)
                self._defer_flow(r)
                if self._seq_finished(s, tok):
                    self._retire_seq(i, s)
        self._flush_trace_emits()
        if pub_jobs:
            self._tier_publish(pub_jobs)
        return total

    def _preempt(self, slot: int, s: "_Seq") -> None:
        """Victim path for pool exhaustion: release the sequence's blocks
        and requeue its request at the FRONT of this engine's own queue —
        it restarts from the prompt later (position-keyed decoding —
        greedy argmax or seeded sampling — reproduces the answer
        exactly; its prompt blocks likely still sit in the prefix
        cache).  An n>1 fork family is preempted as ONE unit: every
        member's blocks are released, every member slot cleared, and the
        request requeued once — half a fork group can never restart."""
        members = s.group.seqs if s.group is not None else [s]
        with self._lock:
            if s.group is None:
                if self._slots[slot] is s:
                    self._slots[slot] = None
            else:
                for i, cur in enumerate(self._slots):
                    if cur in members:
                        self._slots[i] = None
        for m in members:
            self.blocks.free_table(m.table)
            m.table = []
        if s.group is not None:
            s.group.completed = 0
            s.group.forked = False
            s.request.samples = [None] * s.request.n
        s.request.generated = []
        if s.request.token_logprobs is not None:
            s.request.token_logprobs = []
        s.request.requeues += 1
        now = time.monotonic()
        s.request.resubmitted_at = now
        if s.request.trace is not None and _obs.TRACER is not None:
            try:
                _obs.TRACER.instant(
                    s.request.trace, "preempted", self.replica_id,
                    args={"reason": "kv-pool-exhausted"}, t=now)
            except Exception:
                pass
        self.metrics.count_request("preempted", tenant=s.request.tenant)
        self.batcher.requeue_front([s.request])
        get_logger().warning(
            "%s: preempted %s (KV pool exhausted); requeued",
            self.replica_id, s.request.request_id)

    def _ensure_write_blocks(self, active, extra=None):
        """Guarantee each decoding sequence owns writable blocks for
        cache positions ``length .. length + extra[i]`` (growing its
        table, CoW-forking shared blocks — ``extra`` is the speculative
        draft span; None/missing means just ``length``); preempts
        youngest-first on pool exhaustion.  Returns the sequences that
        still hold a slot."""
        ok = []
        for i, s in sorted(active, key=lambda t: t[1].admit_seq):
            with self._lock:
                if self._slots[i] is not s:
                    continue  # preempted as an earlier sequence's victim
            if not s.resident:
                # Swapped out host-ward as an earlier sequence's relief
                # victim THIS pass (tiered; victims are strictly younger
                # than their requester, so they always sort after it and
                # are caught here before entering the ok list).
                continue
            span = extra.get(i, 0) if extra else 0
            bt = self.blocks.block_tokens
            placed = False
            while not placed:
                with self._lock:
                    if self._slots[i] is not s:
                        break  # preempted (group victim) mid-retry
                # Both arms can exhaust the pool (a CoW fork allocates
                # too) — either way the youngest sequence is preempted
                # and the arm retried.
                try:
                    for bidx in range(s.length // bt,
                                      (s.length + span) // bt + 1):
                        allocated = False
                        if bidx < len(s.table):
                            old = s.table[bidx]
                            bid, copied = self.blocks.ensure_writable(old)
                            if copied:
                                # Release the old reference only AFTER
                                # the device copy succeeds
                                # (ensure_writable's contract): a failed
                                # copy must not leave the table pointing
                                # at a freed block.
                                try:
                                    self._cache = self.adapter.copy_block(
                                        self._cache, old, bid)
                                except BaseException:
                                    self.blocks.free(bid)  # never entered
                                    raise                  # a table
                                s.table[bidx] = bid
                                self.blocks.free(old)
                                allocated = True
                        else:
                            s.table.extend(self.blocks.allocate(1))
                            allocated = True
                        # A fork-family allocation consumes one unit of
                        # the tails admission reserved (CoW copy of the
                        # shared partial block, or a decode extend).
                        if allocated and s.group is not None \
                                and s.group.reserve > 0:
                            s.group.reserve -= 1
                    placed = True
                    ok.append((i, s))
                except NoFreeBlocksError:
                    if self.tiering is not None:
                        if self._tier_relieve(s):
                            continue  # room made host-ward; retry arm
                        if s.group is None and s.pending_fetch is None \
                                and s.table:
                            # No younger victim: the requester itself
                            # rides out the crunch host-ward — decoded
                            # state survives, it resumes after swap-in
                            # (demote-over-preempt, both directions).
                            self._tier_swap_out(i, s)
                            placed = True
                            continue
                    with self._lock:
                        live = [(j, t) for j, t in enumerate(self._slots)
                                if t is not None]
                    victim_slot, victim = max(
                        live, key=lambda t: t[1].admit_seq)
                    self._preempt(victim_slot, victim)
                    if victim is s or (s.group is not None
                                       and victim in s.group.seqs):
                        placed = True  # s itself evicted; skip this step
        return ok

    def _decode_once(self) -> int:
        with self._lock:
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None and s.decoding and s.resident]
        if not active:
            self._step_anchor = None
            return 0
        if self._mb:
            active = self._ensure_write_blocks(active)
            if not active:
                self._step_anchor = None
                return 0
        nb = self.blocks.capacity
        # Multi-model partition: one decode call per resident variant
        # with decoding rows, threading the shared pool sequentially
        # (the prefill partition's discipline).  Non-member rows in each
        # call are inactive — zero tokens and ALL-HOLE tables, so their
        # scatter writes drop and their masked reads are zero; a
        # single-model batch is one group with every row, the legacy
        # call bit-for-bit.
        groups: Dict[Optional[str], List[Tuple[int, "_Seq"]]] = {}
        for i, s in active:
            groups.setdefault(s.request.model, []).append((i, s))
        t0 = time.monotonic()
        nxt_by_slot: Dict[int, int] = {}
        entry_by_slot: Dict[int, dict] = {}
        for model, members in groups.items():
            ad = self._adapter_for(model)
            # hvdstream host-mode rows (structured decoding / per-token
            # logprobs) need the RAW logit row on the host each step:
            # they run their own decode_paged_logits call (same paged
            # programs underneath, logits instead of a fused argmax) and
            # draw on the host — sample_host with the grammar mask on
            # the ``allowed`` hook is bit-identical to the fused device
            # draw for unmasked rows (the batched==single contract), so
            # a request only pays the logit transfer when it asked for
            # one of the two features.
            host = [(i, s) for i, s in members
                    if s.request.grammar is not None
                    or s.request.logprobs is not None]
            if host:
                members = [(i, s) for i, s in members
                           if s.request.grammar is None
                           and s.request.logprobs is None]
                h_tokens = np.zeros((self.max_batch,), np.int32)
                h_positions = np.zeros((self.max_batch,), np.int32)
                h_tables = np.full((self.max_batch, self._mb), nb,
                                   np.int32)
                for i, s in host:
                    h_tokens[i] = s.generated[-1]
                    h_positions[i] = s.length
                    h_tables[i, :len(s.table)] = s.table
                self._cache, h_logits = ad.decode_paged_logits(
                    self._cache, h_tokens, h_positions, h_tables)
                for i, s in host:
                    r = s.request
                    raw = h_logits[i]
                    mask = (r.grammar.allowed_mask(s.gstate)
                            if r.grammar is not None else None)
                    tok = _sampling.sample_host_fused(
                        raw, s.base_key, s.length + 1, r.temperature,
                        r.top_k, r.top_p, allowed=mask)
                    nxt_by_slot[i] = tok
                    if r.logprobs is not None:
                        entry_by_slot[i] = self._logprob_entry(
                            raw, tok, r.logprobs)
                if not members:
                    continue
            tokens = np.zeros((self.max_batch,), np.int32)
            positions = np.zeros((self.max_batch,), np.int32)
            tables = np.full((self.max_batch, self._mb), nb, np.int32)
            sampled_rows = False
            for i, s in members:
                tokens[i] = s.generated[-1]
                positions[i] = s.length  # next cache index = length
                tables[i, :len(s.table)] = s.table
                sampled_rows = sampled_rows or s.request.sampled
            if sampled_rows:
                # Any sampled row switches the whole call to the sampled
                # program (greedy rows ride along with temperature 0 —
                # their argmax is computed identically); per-row keys
                # fold only that row's (seed, sample, position), so
                # batched == single given the same key holds by
                # construction.
                keys = _sampling.base_keys_array(
                    [None] * self.max_batch, self.max_batch)
                temps = np.zeros((self.max_batch,), np.float32)
                top_ks = np.zeros((self.max_batch,), np.int32)
                top_ps = np.ones((self.max_batch,), np.float32)
                for i, s in members:
                    r = s.request
                    if r.sampled:
                        keys[i] = s.base_key
                        temps[i] = r.temperature
                        top_ks[i] = r.top_k or 0
                        top_ps[i] = r.top_p
                self._cache, nxt = ad.decode_paged_sampled(
                    self._cache, tokens, positions, tables, keys, temps,
                    top_ks, top_ps)
            else:
                self._cache, nxt = ad.decode_paged(
                    self._cache, tokens, positions, tables)
            for i, _ in members:
                nxt_by_slot[i] = int(nxt[i])
        now = time.monotonic()
        # token_step is the INTER-decode-step latency while the engine
        # stays busy: everything between two decode completions (prefill,
        # admission) counts, so a prefill stalling decodes shows up in the
        # p99 — the statistic chunked prefill is built to hold flat.
        dt_ms = (now - (self._step_anchor if self._step_anchor is not None
                        else t0)) * 1e3
        self._step_anchor = now
        with self._lock:
            for i, s in active:
                if self._slots[i] is not s:
                    continue  # drained/preempted concurrently
                tok = nxt_by_slot[i]
                r = s.request
                s.generated.append(tok)
                entry = entry_by_slot.get(i)
                if entry is not None and r.token_logprobs is not None:
                    r.token_logprobs.append(entry)
                if r.grammar is not None and tok != r.eos_id:
                    s.gstate = r.grammar.advance_token(s.gstate, tok)
                if s.group is None:
                    self._publish_stream(r, s.generated, entry)
                s.length += 1
                self._defer_flow(s.request)
                if self._seq_finished(s, tok) \
                        or s.length >= self.adapter.max_len:
                    self._retire_seq(i, s)
        if self.tiering is not None:
            # Last-touch bookkeeping feeds the spill policy (coldest
            # retained block first) — loop-thread-only list writes.
            for i, s in active:
                self.blocks.touch(s.table, self.steps)
        self.steps += 1
        self._flush_trace_emits()
        self.metrics.observe_decode_step(dt_ms, len(active), len(active))
        self.metrics.maybe_emit_timeline(kv_stats=self.blocks.stats())
        return len(active)

    # -- speculative decoding (HVD_SERVE_SPEC_K > 0) -------------------------

    def _spec_once(self) -> int:
        """One speculative iteration (Leviathan et al. 2023 / Chen et
        al. 2023): the draft proposes up to k greedy tokens per decoding
        sequence (k cheap batched draft steps sharing the target's KV
        pool), then the target verifies all k+1 positions in ONE
        multi-token step through the chunked-prefill machinery
        (``verify_chunk``), amortizing the big model over every accepted
        token.  Acceptance: greedy requests accept while the draft
        matches the target argmax and emit the target's token at the
        first mismatch — bit-identical to non-speculative greedy;
        sampled requests accept draft d with probability ``p[d]`` (the
        draft is a point mass, so Leviathan rejection reduces to that)
        and resample the residual — the marginal is exactly the
        filtered target distribution.  K/V scattered past a rejected
        draft sits at positions >= the rolled-back length (masked, then
        overwritten); table entries extended for drafting are freed so
        a rejection leaks zero block refs."""
        with self._lock:
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None and s.decoding and s.resident]
        if not active:
            self._step_anchor = None
            return 0
        # Per-row draft budget: the step always emits >= 1 non-draft
        # token (correction or bonus), so drafting is capped at
        # max_new-1 remaining and at the last cache position.
        ks: Dict[int, int] = {}
        for i, s in active:
            r = s.request
            ks[i] = max(min(self.spec_k,
                            r.max_new_tokens - len(s.generated) - 1,
                            self.adapter.max_len - 1 - s.length), 0)
        pre_lens: Dict[int, int] = {}
        if self._mb:
            pre_lens = {i: len(s.table) for i, s in active}
            active = self._ensure_write_blocks(active, extra=ks)
            if not active:
                self._step_anchor = None
                return 0
        nb = self.blocks.capacity
        B = self.max_batch
        t0 = time.monotonic()
        drafts: Dict[int, List[int]] = {i: [] for i, _ in active}
        cur = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        for i, s in active:
            cur[i] = s.generated[-1]
            pos[i] = s.length
        max_k = max(ks[i] for i, _ in active)
        for j in range(max_k):
            rows = [(i, s) for i, s in active if ks[i] > j]
            if not rows:
                break
            tokens = np.zeros((B,), np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.full((B, self._mb), nb, np.int32)
            for i, s in rows:
                tokens[i] = cur[i]
                positions[i] = pos[i]
                tables[i, :len(s.table)] = s.table
            self._cache, proposed = self.adapter.draft_decode(
                self._cache, tokens, positions, tables)
            for i, s in rows:
                d = int(proposed[i])
                drafts[i].append(d)
                cur[i] = d
                pos[i] += 1
        chunks = [[s.generated[-1]] + drafts[i] for i, s in active]
        starts = [s.length for _, s in active]
        tables_l = [list(s.table) for _, s in active]
        self._cache, logits = self.adapter.verify_chunk(
            self._cache, chunks, starts, tables_l)
        now = time.monotonic()
        dt_ms = (now - (self._step_anchor if self._step_anchor is not None
                        else t0)) * 1e3
        self._step_anchor = now
        emitted_total = 0
        drafted = accepted = rejected = 0
        # Acceptance OUTSIDE the engine lock: the sampled arm runs
        # per-token host-side draws (jax fold_in/uniform) and full-vocab
        # filtered_probs sorts — the slow half of a sampled spec step.
        # Only this loop thread mutates sequence state, so the reads are
        # stable; application below re-checks slot ownership under the
        # lock as every decode path does.  (A row drained/preempted
        # during this pass still counts its drafted/accepted tokens —
        # the draft and verify compute really happened.)
        plan: List[Tuple[int, "_Seq", List[int], int]] = []
        for row, (i, s) in enumerate(active):
            r = s.request
            k = ks[i]
            lrow = logits[row]
            ell = s.length
            drafted += k
            emit: List[int] = []
            m = 0
            rejected_here = False
            for j in range(1, k + 1):
                pl = lrow[j - 1]
                d = drafts[i][j - 1]
                if not r.sampled:
                    tgt = int(np.argmax(pl))
                    if d == tgt:
                        emit.append(d)
                        m += 1
                        continue
                    emit.append(tgt)
                    rejected_here = True
                    break
                p = _sampling.filtered_probs(pl, r.temperature,
                                             r.top_k, r.top_p)
                if _sampling.accept_draw(s.base_key, ell + j) < p[d]:
                    emit.append(d)
                    m += 1
                    continue
                emit.append(_sampling.residual_sample(
                    p, d, s.base_key, ell + j))
                rejected_here = True
                break
            if not rejected_here:
                # Every draft accepted: the bonus token from the
                # target's last-position logits, keyed exactly as
                # the non-speculative path would key that position.
                pl = lrow[k]
                if not r.sampled:
                    emit.append(int(np.argmax(pl)))
                else:
                    emit.append(_sampling.sample_host(
                        pl, s.base_key, ell + k + 1, r.temperature,
                        r.top_k, r.top_p))
            accepted += m
            rejected += k - m
            plan.append((i, s, emit, m))
        with self._lock:
            staged = set()
            for i, s, emit, m in plan:
                if self._slots[i] is not s:
                    continue  # drained/preempted concurrently
                r = s.request
                ell = s.length
                if id(r) not in staged:
                    staged.add(id(r))
                    r.stage_add("spec", now)
                finished = False
                for tok in emit:
                    s.generated.append(tok)
                    if s.group is None:
                        self._publish_stream(r, s.generated)
                    emitted_total += 1
                    self._defer_flow(r)
                    if self._seq_finished(s, tok):
                        finished = True
                        break
                if finished:
                    self._retire_seq(i, s)
                    continue
                # K/V is valid through position ell+m (the fed token +
                # accepted drafts); the correction/bonus token is
                # pending exactly like a plain decode step's output.
                s.length = ell + m + 1
                if s.length >= self.adapter.max_len:
                    self._retire_seq(i, s)
                elif self._mb:
                    # Rejected-draft rollback: table entries extended
                    # for drafting beyond what the accepted prefix
                    # needs return to the pool NOW — never leak refs
                    # past a rejection.
                    keep = max(pre_lens.get(i, len(s.table)),
                               self._blocks_for_tokens(s.length))
                    if len(s.table) > keep:
                        freed = len(s.table) - keep
                        self.blocks.free_table(s.table[keep:])
                        del s.table[keep:]
                        # Refund the fork-tail reservation for rolled-
                        # back draft extensions (capped at the
                        # admission-time value): without this, repeated
                        # reject/rollback cycles drain the reserve and
                        # the admission budget stops protecting the
                        # family's remaining decode tail.
                        if s.group is not None:
                            s.group.reserve = min(
                                s.group.reserve + freed,
                                s.group.reserve_cap)
        self.steps += 1
        self._flush_trace_emits()
        self.metrics.observe_decode_step(dt_ms, len(active), emitted_total)
        self.metrics.observe_spec(drafted, accepted, rejected)
        self.metrics.maybe_emit_timeline(kv_stats=self.blocks.stats())
        return len(active)

    # -- the loop ------------------------------------------------------------

    def _cache_deleted(self) -> bool:
        """True when a failed jit call consumed its donated cache buffers
        (runtime failure AFTER donation): the pytree still holds arrays,
        but they are deleted and every later call would raise."""
        import jax
        for leaf in jax.tree_util.tree_leaves(self._cache):
            is_deleted = getattr(leaf, "is_deleted", None)
            if is_deleted is not None and is_deleted():
                return True
        return False

    def _recover(self, e: BaseException) -> None:
        """Poisoned-batch recovery: fail the in-flight requests NOW with
        the real error and keep serving.  It frees ONLY the
        failed iteration's block references — the pool arrays and the
        prefix registry survive (shared/registered blocks were written by
        previously-successful iterations; the failed sequences' private
        blocks return to the free list).  Exception: if the failed call
        had already consumed its DONATED cache buffers (XLA runtime
        failure mid-step), the pool is rebuilt and the prefix registry
        reset with it — retained hashes must never describe zeroed
        blocks."""
        get_logger().exception(
            "%s: engine step failed: %s", self.replica_id, e)
        with self._lock:
            failed = set()
            for i, s in enumerate(self._slots):
                if s is not None:
                    if id(s.request) not in failed:
                        # One fail/count per request even when an n>1
                        # fork family holds several slots.
                        failed.add(id(s.request))
                        s.request.fail(e)
                        self.metrics.count_request(
                            "error", tenant=s.request.tenant)
                    self.blocks.free_table(s.table)
                    self._slots[i] = None
        self._flush_trace_emits()  # leftovers from the crashed helper
        if self._cache_deleted():
            get_logger().warning(
                "%s: donated KV pool was consumed by the failed step; "
                "rebuilding pool and prefix registry", self.replica_id)
            if self.tiering is not None:
                self.blocks = TieredBlockManager(
                    self.blocks.capacity, self.blocks.block_tokens,
                    self.tiering,
                    prefix_cache=self.blocks.prefix_cache_enabled,
                    bytes_per_block=self.blocks.bytes_per_block,
                    client=self._tier_client)
                self._cache = self.adapter.init_paged_cache(
                    self.blocks.capacity, self.max_batch)
                # The insert program closes over engine._cache reads, so
                # it survives the rebuild — but the worker holds the OLD
                # manager; rebuild it too (same queue discipline).
                self.blocks.set_device_io(*make_block_io(self))
                if self._tier_worker is not None:
                    self._tier_worker.manager = self.blocks
            else:
                self.blocks = BlockManager(
                    self.blocks.capacity, self.blocks.block_tokens,
                    prefix_cache=self.blocks.prefix_cache_enabled,
                    bytes_per_block=self.blocks.bytes_per_block)
                self._cache = self.adapter.init_paged_cache(
                    self.blocks.capacity, self.max_batch)
        if self.tiering is not None:
            self._tier_stall_anchor = None
        self._step_anchor = None

    def _run(self) -> None:
        idle_block_s = float(os.environ.get("HVD_SERVE_IDLE_POLL_S", "0.05"))
        while not self._stop.is_set():
            try:
                if _faultline.PLAN is not None:
                    self._faultline_step()
                self._expire_inflight()
                if self.tiering is not None:
                    # Tier bookkeeping at the iteration top: apply
                    # worker arrivals, time out dead fetches, rotate
                    # swapped sequences back in, issue demotes and
                    # queue-peek prefetches — all ahead of this
                    # iteration's prefill/decode.
                    self._tier_schedule()
                busy = self.active_count > 0
                # Iteration-level scheduling: admission happens BETWEEN
                # decode steps — non-blocking while sequences are active,
                # blocking (bounded) when idle.
                self._admit(0.0 if busy else idle_block_s)
                pre = self._prefill_step()
                # Speculative decoding is single-model (the draft is
                # the DEFAULT adapter's): any non-default decoding
                # row falls back to the per-model greedy path —
                # bit-identical output, just no draft amortization
                # that iteration.
                spec_ok = self.spec_k > 0 and self.brownout_level < 3
                if spec_ok:
                    # Grammar/logprob rows decode on the host
                    # (decode_paged_logits) — the fused spec
                    # draft/verify pair has no logits or mask seam,
                    # so any such active row falls the whole
                    # iteration back to the plain per-model path
                    # (bit-identical output, hvdstream contract).
                    with self._lock:
                        spec_ok = all(
                            (s.request.model is None
                             or s.request.model == self.default_model
                             or len(self._adapters) == 1)
                            and s.request.grammar is None
                            and s.request.logprobs is None
                            for s in self._slots if s is not None)
                dec = (self._spec_once() if spec_ok
                       else self._decode_once())
                if pre or dec:
                    self.metrics.observe_iteration(pre, dec)
                if self.tiering is not None:
                    self._tier_idle_wait(pre, dec)
            except Exception as e:
                # A dying loop thread would hang every in-flight request
                # until its client timeout — recover instead: one
                # poisoned batch must not take the replica down.
                self._recover(e)

    # -- synchronous one-shot (bench / tests) --------------------------------

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 16,
                 eos_id: Optional[int] = None,
                 timeout_s: float = 300.0,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: float = 1.0,
                 n: int = 1,
                 seed: Optional[int] = None,
                 model: Optional[str] = None,
                 tenant: str = "default") -> List[int]:
        """Submit one request through the running loop and wait for it
        (n > 1: the returned list is sample 0; the full set is on the
        request's ``samples`` — use a hand-built Request for that)."""
        if self._thread is None:
            self.start()
        r = Request(prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    n=n, seed=seed, model=model, tenant=tenant)
        self.batcher.submit(r)
        return r.result(timeout=timeout_s)
