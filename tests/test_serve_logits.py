"""The serving programs' logits against ``Transformer.apply``.

The engine tests hold the serving stack to token equality and the
attention kernels to ``allclose``; this file holds every program that
returns logits to the flax model's, on the same tokens: the prompt is
written into a block pool through the engine's own programs (two
sequences a call, tables that are neither the identity nor contiguous, a
pool filled with noise beforehand, so a write to or a read from a wrong
block shows) and each program's logits are compared with the model's at
the same positions.  Native storage: ``allclose`` in float32 (measured
2e-7 on logits of at most 0.65).  The int8 pool: the cosine bound
tests/test_paged_attention.py holds quantized storage to against
unquantized (above 0.999), and no logit off by 0.01 (measured 7e-4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serve import TransformerAdapter

BT = 8   # block_tokens
NB = 12  # blocks in the pool
_TINY = TransformerConfig(vocab_size=61, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=64, causal=True,
                          dtype=jnp.float32, scan_layers=False)
# Two sequences, teacher-forced: the programs are fed these tokens
# whatever they predict, so every position has a reference.
_LENS = (27, 14)
_TABLES = ([7, 2, 9, 4], [10, 0, 5])


@pytest.fixture(scope="module")
def model_and_reference():
    model = Transformer(_TINY)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    seqs = [np.random.RandomState(7 + i).randint(0, 61, (n,)).tolist()
            for i, n in enumerate(_LENS)]
    refs = [np.asarray(model.apply({"params": params},
                                   jnp.asarray([s], jnp.int32))[0])
            for s in seqs]
    return params, seqs, refs


def _adapter(params, kv_dtype, attn_impl):
    return TransformerAdapter(_TINY, params, block_tokens=BT,
                              kv_dtype=kv_dtype, attn_impl=attn_impl)


def _noisy_pool(ad):
    """A pool whose every block holds noise: what a sequence has not
    written must never reach its logits."""
    rng = np.random.RandomState(3)

    def noise(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jnp.asarray(rng.randint(-127, 128, a.shape), a.dtype)
        return jnp.asarray(rng.standard_normal(a.shape) * 3.0, a.dtype)

    return jax.tree.map(noise, ad.init_paged_cache(NB, 4))


def _check(got, want, kv_dtype, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    if kv_dtype == "native":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-6,
                                   err_msg=what)
        return
    for g, w in zip(got.reshape(-1, got.shape[-1]),
                    want.reshape(-1, want.shape[-1])):
        cos = float(np.dot(g, w) / (np.linalg.norm(g) * np.linalg.norm(w)))
        assert cos > 0.999, (what, cos)
        assert float(np.max(np.abs(g - w))) < 0.01, what


def _prefill(ad, cache, seqs, upto, chunk):
    """Both prompts' first ``upto[i]`` tokens through ``prefill_chunk``,
    ``chunk`` tokens a call."""
    done = [0, 0]
    while any(d < u for d, u in zip(done, upto)):
        rows = [i for i in (0, 1) if done[i] < upto[i]]
        cache, _ = ad.prefill_chunk(
            cache,
            [seqs[i][done[i]:min(done[i] + chunk, upto[i])] for i in rows],
            [done[i] for i in rows], [_TABLES[i] for i in rows])
        for i in rows:
            done[i] = min(done[i] + chunk, upto[i])
    return cache


def _run_prefill_chunk_logits(ad, seqs, refs, kv_dtype):
    cache = _noisy_pool(ad)
    cuts = (11, 6)  # neither a block's nor a bucket's edge
    cache, first = ad.prefill_chunk_logits(
        cache, [s[:c] for s, c in zip(seqs, cuts)], [0, 0], _TABLES)
    cache, last = ad.prefill_chunk_logits(
        cache, [s[c:] for s, c in zip(seqs, cuts)], list(cuts), _TABLES)
    for i in (0, 1):
        _check(first[i], refs[i][cuts[i] - 1], kv_dtype, f"chunk 1 row {i}")
        _check(last[i], refs[i][-1], kv_dtype, f"chunk 2 row {i}")


def _run_decode_paged_logits(ad, seqs, refs, kv_dtype):
    steps = 3
    upto = [n - steps for n in _LENS]
    cache = _prefill(ad, _noisy_pool(ad), seqs, upto, chunk=5)
    rows = (1, 3)  # of a batch of 4; rows 0 and 2 are inactive
    for t in range(steps):
        tokens = np.zeros((4,), np.int32)
        positions = np.zeros((4,), np.int32)
        tables = np.full((4, ad.max_blocks_per_seq), NB, np.int32)
        for i, row in enumerate(rows):
            tokens[row] = seqs[i][upto[i] + t]
            positions[row] = upto[i] + t
            tables[row, :len(_TABLES[i])] = _TABLES[i]
        cache, logits = ad.decode_paged_logits(cache, tokens, positions,
                                               tables)
        for i, row in enumerate(rows):
            _check(logits[row], refs[i][upto[i] + t], kv_dtype,
                   f"step {t} row {row}")


def _run_verify_chunk(ad, seqs, refs, kv_dtype):
    k = 4
    upto = [n - k for n in _LENS]
    cache = _prefill(ad, _noisy_pool(ad), seqs, upto, chunk=16)
    _, logits = ad.verify_chunk(
        cache, [s[u:] for s, u in zip(seqs, upto)], upto, _TABLES)
    for i in (0, 1):
        _check(logits[i, :k], refs[i][upto[i]:], kv_dtype, f"row {i}")


def _run_score_logits(ad, seqs, refs, kv_dtype):
    for i in (0, 1):
        _check(ad.score_logits(seqs[i]), refs[i], kv_dtype, f"score {i}")
        _check(ad.prompt_logits(seqs[i]), refs[i][-1], kv_dtype,
               f"prompt {i}")


_PROGRAMS = {"prefill_chunk_logits": _run_prefill_chunk_logits,
             "decode_paged_logits": _run_decode_paged_logits,
             "verify_chunk": _run_verify_chunk,
             "score_logits": _run_score_logits}


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("program", sorted(_PROGRAMS))
def test_program_logits_match_flax_apply(model_and_reference, program,
                                         kv_dtype, attn_impl):
    params, seqs, refs = model_and_reference
    _PROGRAMS[program](_adapter(params, kv_dtype, attn_impl), seqs, refs,
                       kv_dtype)


@pytest.mark.parametrize("length", [2 * BT - 1, 2 * BT, 2 * BT + 1])
def test_chunked_prefill_logits_at_block_boundaries(model_and_reference,
                                                    length):
    """A prompt that ends one short of, on and one past a block's edge,
    prefilled in chunks of 5 that straddle the edges."""
    params, seqs, refs = model_and_reference
    ad = _adapter(params, "native", "gather")
    cache = _prefill(ad, _noisy_pool(ad), seqs, [length - 3, 0], chunk=5)
    _, logits = ad.prefill_chunk_logits(
        cache, [seqs[0][length - 3:length]], [length - 3], [_TABLES[0]])
    _check(logits[0], refs[0][length - 1], "native", f"length {length}")
