"""ISSUE 19: hvdstream structured decoding + logprob scoring.

Pins the grammar-constrained decoding and scoring contracts:

* parse_schema — the supported JSON-Schema subset, with every
  unsupported keyword/shape named in a ValueError (the HTTP 400);
* TokenGrammar — per-feature mask walks (object / array / string /
  number / integer / boolean / enum / const): any token sequence that
  honors ``allowed_mask`` spells a complete conforming document, EOS
  joins the mask exactly at accepting states, ``exhausted`` fires when
  the document admits no continuation, ``matches`` validates offline;
* engine — schema'd requests produce valid documents at temperature 0
  AND under seeded sampling (every seed), finish reason ``grammar``
  when the document completes itself, the paged-capability gate for
  schema/logprobs requests;
* HTTP — ``logprobs: k`` on /generate (buffered body and streamed
  token events), /score per-token logprob parity against the adapter's
  own log-softmax, and the 400 surfaces (unsupported keyword, missing
  eos_id, out-of-range tokens, oversized top_logprobs).
"""

import http.client
import json
import math
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import create_mlp
from horovod_tpu.serve import (InferenceEngine, MLPAdapter, Replica,
                               ReplicaScheduler, Request, ServeMetrics,
                               ServeServer)
from horovod_tpu.serve.streaming import parse_sse
from horovod_tpu.serve.structured import TokenGrammar, parse_schema

EOS = 0
BYTE_VOCAB = [chr(i) for i in range(128)]


# -- harness -----------------------------------------------------------------

def _mlp256(seed=3, max_len=512):
    """Byte-vocabulary MLP: token ids ARE character codes, so grammar
    emissions decode with bytes().decode() (the bench's idiom)."""
    vocab = 256
    mlp = create_mlp(features=(16, vocab))
    params = mlp.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, vocab)))["params"]
    return MLPAdapter(mlp, params, vocab_size=vocab, max_len=max_len)


def _paged_engine(adapter=None, **kw):
    kw.setdefault("max_batch", 4)
    return InferenceEngine(adapter or _mlp256(),
                           metrics=ServeMetrics(),
                           replica_id="structured-t", **kw)


def _run(eng, prompt, **req_kw):
    r = Request(prompt, **req_kw)
    eng.batcher.submit(r)
    toks = r.result(timeout=60)
    return r, toks


def _doc(tokens):
    """Decode a byte-vocab completion, dropping a trailing EOS."""
    toks = list(tokens)
    while toks and toks[-1] == EOS:
        toks.pop()
    return bytes(toks).decode()


def _server(adapter_fn=_mlp256, n=1):
    replicas = [Replica(f"replica-{i}", None,
                        _paged_engine(adapter_fn()))
                for i in range(n)]
    sched = ReplicaScheduler(replicas, metrics=replicas[0].engine.metrics)
    server = ServeServer(sched, request_timeout_s=60)
    port = server.start(port=0, host="127.0.0.1")
    return server, sched, port


def _post(port, payload, path="/generate", timeout=30):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _stream_events(port, payload, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate",
                     body=json.dumps(payload).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        raw = b""
        while True:
            chunk = resp.read1(8192)
            if not chunk:
                break
            raw += chunk
            cut = raw.rfind(b"\n\n")
            events = parse_sse(raw[:cut + 2]) if cut >= 0 else []
            if events and events[-1][0] in ("done", "error"):
                return events
        return parse_sse(raw)
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# parse_schema: the supported subset, loudly bounded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schema,needle", [
    ({"anyOf": [{"type": "string"}]}, "anyOf"),
    ({"type": "object", "patternProperties": {}}, "patternProperties"),
    ({"type": "string", "minLength": 3}, "minLength"),
    ({"type": "tuple"}, "unsupported type"),
    ({"type": "object", "additionalProperties": True},
     "additionalProperties"),
    ({"type": "array"}, "items"),
    ({"type": "array", "items": {"type": "integer"}, "minItems": 5,
      "maxItems": 2}, "maxItems"),
    ({"type": "object", "properties": {}, "required": ["ghost"]},
     "ghost"),
    ({"const": True, "type": "boolean"}, "const"),
    ({"enum": []}, "enum"),
    (True, "boolean"),
    ([1, 2], "JSON object"),
])
def test_parse_schema_names_the_unsupported_piece(schema, needle):
    with pytest.raises(ValueError, match=needle):
        parse_schema(schema)


def test_parse_schema_accepts_the_documented_subset():
    parse_schema({"type": "object",
                  "properties": {"a": {"type": "integer"}},
                  "required": ["a"], "additionalProperties": False})
    parse_schema({"type": "array", "items": {"type": "number"},
                  "minItems": 1, "maxItems": 4})
    for t in ("string", "number", "integer", "boolean", "null"):
        parse_schema({"type": t})
    parse_schema({"enum": ["red", 3, None]})
    parse_schema({"const": {"x": 1}})


# ---------------------------------------------------------------------------
# TokenGrammar: masked walks spell conforming documents
# ---------------------------------------------------------------------------

def _constrained_walk(g, rng, max_steps=2000):
    """Random walk honoring ``allowed_mask``; ends on ``exhausted`` or
    by drawing EOS where the mask admits it.  Returns the token list
    (EOS excluded)."""
    state, toks = g.start, []
    for _ in range(max_steps):
        if g.exhausted(state):
            return toks
        mask = g.allowed_mask(state)
        if mask[g.eos_id] and rng.rand() < 0.6:
            return toks  # EOS is only maskable at accepting states
        allowed = np.flatnonzero(mask)
        allowed = allowed[allowed != g.eos_id]
        assert allowed.size, "live non-exhausted state with no moves"
        tok = int(allowed[rng.randint(0, allowed.size)])
        toks.append(tok)
        state = g.advance_token(state, tok)
        assert state, "mask admitted a killing token"
    raise AssertionError("walk did not terminate")


def _validate(doc, schema):
    t = schema.get("type")
    if "const" in schema:
        assert doc == schema["const"]
    elif "enum" in schema:
        assert doc in schema["enum"]
    elif t == "object":
        assert isinstance(doc, dict)
        assert set(doc) <= set(schema.get("properties", {}))
        for name in schema.get("required", []):
            assert name in doc
        for name, sub in schema.get("properties", {}).items():
            if name in doc:
                _validate(doc[name], sub)
    elif t == "array":
        assert isinstance(doc, list)
        assert len(doc) >= schema.get("minItems", 0)
        if "maxItems" in schema:
            assert len(doc) <= schema["maxItems"]
        for item in doc:
            _validate(item, schema["items"])
    elif t == "string":
        assert isinstance(doc, str)
    elif t == "integer":
        assert isinstance(doc, int) and not isinstance(doc, bool)
    elif t == "number":
        assert isinstance(doc, (int, float)) \
            and not isinstance(doc, bool)
    elif t == "boolean":
        assert isinstance(doc, bool)
    elif t == "null":
        assert doc is None


@pytest.mark.parametrize("schema", [
    {"type": "object",
     "properties": {"a": {"type": "integer"},
                    "b": {"type": "boolean"},
                    "c": {"type": "string"}},
     "required": ["a"], "additionalProperties": False},
    {"type": "array", "items": {"type": "integer"},
     "minItems": 1, "maxItems": 3},
    {"type": "array", "items": {"type": "boolean"}, "minItems": 0,
     "maxItems": 2},
    {"type": "string"},
    {"type": "number"},
    {"type": "integer"},
    {"type": "boolean"},
    {"type": "null"},
    {"enum": ["red", "green", 3]},
    {"const": {"x": 1, "y": [True]}},
], ids=["object", "array", "array-empty-ok", "string", "number",
        "integer", "boolean", "null", "enum", "const"])
def test_grammar_masked_walks_spell_conforming_documents(schema):
    g = TokenGrammar(schema, BYTE_VOCAB, eos_id=EOS)
    rng = np.random.RandomState(7)
    for trial in range(20):
        toks = _constrained_walk(g, rng)
        assert g.matches(toks), toks
        assert g.matches(toks + [EOS])  # trailing EOS accepted
        doc = json.loads("".join(BYTE_VOCAB[t] for t in toks))
        _validate(doc, schema)


def test_grammar_eos_masked_in_only_at_accepting_states():
    g = TokenGrammar({"const": True}, BYTE_VOCAB, eos_id=EOS)
    state = g.start
    for i, ch in enumerate("true"):
        mask = g.allowed_mask(state)
        assert not mask[EOS], f"EOS allowed mid-emission at {i}"
        assert not g.accepting(state)
        # The const admits exactly one continuation per step.
        assert int(mask.sum()) == 1 and mask[ord(ch)]
        state = g.advance_token(state, ord(ch))
    assert g.accepting(state)
    assert g.allowed_mask(state)[EOS]
    assert g.exhausted(state)  # nothing but EOS left -> reason grammar


def test_grammar_const_and_enum_emit_canonical_json():
    g = TokenGrammar({"const": {"x": 1, "y": [True]}}, BYTE_VOCAB,
                     eos_id=EOS)
    toks = _constrained_walk(g, np.random.RandomState(0))
    # Canonical: compact separators, key order as given.
    assert "".join(BYTE_VOCAB[t] for t in toks) == '{"x":1,"y":[true]}'
    g = TokenGrammar({"enum": ["red", 3]}, BYTE_VOCAB, eos_id=EOS)
    seen = set()
    rng = np.random.RandomState(1)
    for _ in range(30):
        seen.add("".join(BYTE_VOCAB[t]
                         for t in _constrained_walk(g, rng)))
    assert seen == {'"red"', "3"}


def test_grammar_matches_rejects_tampered_and_truncated():
    g = TokenGrammar({"const": True}, BYTE_VOCAB, eos_id=EOS)
    good = [ord(c) for c in "true"]
    assert g.matches(good)
    assert not g.matches(good[:-1])          # incomplete document
    assert not g.matches(good + [ord("x")])  # trailing garbage
    bad = list(good)
    bad[1] = ord("x")
    assert not g.matches(bad)                # tampered interior
    assert not g.matches([EOS])              # EOS before acceptance
    assert not g.matches(good[:2] + [EOS] + good[2:])  # EOS mid-doc


def test_grammar_requires_byte_transparent_vocab_and_valid_eos():
    # eos out of vocabulary range: disabled, masks never include it.
    g = TokenGrammar({"type": "boolean"}, BYTE_VOCAB, eos_id=9999)
    assert g.eos_id is None


# ---------------------------------------------------------------------------
# engine: constrained decoding through the real paged pipeline
# ---------------------------------------------------------------------------

BOOL_SCHEMA = {"type": "boolean"}
OBJ_SCHEMA = {"type": "object",
              "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"], "additionalProperties": False}


def test_engine_schema_greedy_and_sampled_always_valid():
    eng = _paged_engine().start()
    g = TokenGrammar(OBJ_SCHEMA, [chr(i) for i in range(256)],
                     eos_id=EOS)
    try:
        r, toks = _run(eng, [65, 66, 67], max_new_tokens=64,
                       eos_id=EOS, schema=OBJ_SCHEMA)
        doc = json.loads(_doc(toks))
        assert isinstance(doc.get("ok"), bool) and set(doc) <= {"ok"}
        assert g.matches([t for t in toks if t != EOS])
        # Sampled: every seed stays inside the grammar.
        for seed in range(8):
            r, toks = _run(eng, [70 + seed], max_new_tokens=64,
                           eos_id=EOS, temperature=1.0,
                           seed=1000 + seed, schema=OBJ_SCHEMA)
            doc = json.loads(_doc(toks))
            assert isinstance(doc.get("ok"), bool), (seed, toks)
            assert set(doc) <= {"ok"}
            assert r.finish_reason in ("grammar", "stop")
    finally:
        eng.stop()


def test_engine_exhausted_grammar_finishes_with_reason_grammar():
    eng = _paged_engine().start()
    try:
        r, toks = _run(eng, [65], max_new_tokens=64, eos_id=EOS,
                       temperature=0.9, seed=5, schema=BOOL_SCHEMA)
        assert _doc(toks) in ("true", "false")
        # "true"/"false" admits no continuation: the engine finished
        # the sequence itself instead of waiting for the model's EOS.
        assert r.finish_reason == "grammar"
        assert len(toks) <= 6
    finally:
        eng.stop()


def test_engine_schema_needs_sampling_capable_adapter():
    class _GreedyOnly:
        """The paged trio and nothing else: no logits, no sampler."""

        def __init__(self, inner):
            self.vocab_size, self.max_len = inner.vocab_size, inner.max_len
            self.init_paged_cache = inner.init_paged_cache
            self.prefill_chunk = inner.prefill_chunk
            self.decode_paged = inner.decode_paged

    eng = InferenceEngine(_GreedyOnly(_mlp256()), max_batch=2,
                          metrics=ServeMetrics(),
                          replica_id="greedy-t").start()
    try:
        assert eng.generate([65], max_new_tokens=2)  # greedy still serves
        r = Request([65], max_new_tokens=8, eos_id=EOS,
                    schema=BOOL_SCHEMA)
        eng.batcher.submit(r)
        with pytest.raises(ValueError, match="decode_paged_logits"):
            r.result(timeout=30)
    finally:
        eng.stop()


def test_engine_logprobs_report_model_belief_with_topk():
    ad = _mlp256()
    eng = _paged_engine(ad).start()
    try:
        r, toks = _run(eng, [5, 7], max_new_tokens=6, logprobs=3)
        entries = r.token_logprobs
        assert len(entries) == len(toks)
        # Markov chain: each row's distribution depends only on the
        # previous token (the last prompt token for position 0).
        context = [7] + toks[:-1]
        for ctx_tok, tok, entry in zip(context, toks, entries):
            assert entry["token"] == tok
            row = np.asarray(
                ad._logits_of(np.asarray([ctx_tok], np.int32)),
                np.float64)[0]
            lse = float(row.max()) + math.log(
                float(np.sum(np.exp(row - row.max()))))
            assert entry["logprob"] == pytest.approx(
                float(row[tok] - lse), rel=1e-5)
            top = entry["top"]
            assert len(top) == 3
            lps = [t["logprob"] for t in top]
            assert lps == sorted(lps, reverse=True)
            # Greedy decode: the chosen token IS the top-1.
            assert top[0]["token"] == tok
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# HTTP: schema + logprobs + /score
# ---------------------------------------------------------------------------

def test_http_schema_stream_matches_buffered_and_validates():
    server, _, port = _server()
    try:
        payload = {"tokens": [65, 66], "max_new_tokens": 64,
                   "eos_id": EOS, "temperature": 0.8, "seed": 42,
                   "schema": OBJ_SCHEMA}
        status, buffered = _post(port, payload)
        assert status == 200
        doc = json.loads(_doc(buffered["tokens"]))
        assert isinstance(doc.get("ok"), bool)
        events = _stream_events(port, dict(payload, stream=True))
        assert events[-1][0] == "done"
        streamed = [t for e in events if e[0] == "token"
                    for t in e[1]["tokens"]]
        assert streamed == buffered["tokens"]
        assert events[-1][1]["finish_reason"] == \
            buffered["finish_reason"]
    finally:
        server.stop()


def test_http_generate_rejects_unsupported_schema_keyword():
    server, _, port = _server()
    try:
        status, body = _post(port, {
            "tokens": [65], "eos_id": EOS,
            "schema": {"anyOf": [{"type": "boolean"}]}})
        assert status == 400
        assert "anyOf" in body["error"]
        status, body = _post(port, {
            "tokens": [65], "schema": BOOL_SCHEMA})  # no eos_id
        assert status == 400
        assert "eos_id" in body["error"]
    finally:
        server.stop()


def test_http_generate_logprobs_ride_body_and_stream_events():
    server, _, port = _server()
    try:
        payload = {"tokens": [5, 7], "max_new_tokens": 5, "logprobs": 2}
        status, buffered = _post(port, payload)
        assert status == 200
        entries = buffered["logprobs"]
        assert len(entries) == len(buffered["tokens"])
        for tok, entry in zip(buffered["tokens"], entries):
            assert entry["token"] == tok
            assert entry["logprob"] <= 0.0
            assert len(entry["top"]) == 2
        # Streamed: per-token logprobs arrive ON the token events.
        events = _stream_events(port, dict(payload, stream=True))
        streamed = [lp for e in events if e[0] == "token"
                    for lp in e[1]["logprobs"]]
        assert streamed == entries
        assert events[-1][1]["logprobs"] == entries
    finally:
        server.stop()


def test_http_score_parity_with_adapter_log_softmax():
    ad = _mlp256()
    server, _, port = _server(lambda: ad)
    try:
        tokens = [5, 7, 11, 2]
        status, body = _post(port, {"tokens": tokens,
                                    "top_logprobs": 3}, path="/score")
        assert status == 200
        assert body["tokens"] == tokens
        entries = body["logprobs"]
        assert len(entries) == len(tokens)
        assert entries[0] is None  # nothing conditions position 0
        logits = np.asarray(ad.score_logits(tokens), np.float64)
        for p in range(1, len(tokens)):
            row = logits[p - 1]
            lse = float(row.max()) + math.log(
                float(np.sum(np.exp(row - row.max()))))
            want = float(row[tokens[p]] - lse)
            assert entries[p]["token"] == tokens[p]
            assert entries[p]["logprob"] == pytest.approx(want,
                                                          rel=1e-5)
            top = entries[p]["top"]
            assert len(top) == 3
            assert top[0]["logprob"] >= entries[p]["logprob"]
        # Scoring is pure observation: no decode slots were consumed.
        status, again = _post(port, {"tokens": tokens}, path="/score")
        assert status == 200 and "top" not in (again["logprobs"][1]
                                               or {})
    finally:
        server.stop()


def test_http_score_validation_400s():
    server, _, port = _server()
    try:
        for payload, needle in [
            ({"tokens": [5, 999]}, "out of range"),
            ({"tokens": [5], "top_logprobs": 17}, "top_logprobs"),
            ({"tokens": []}, "non-empty"),
            ({"tokens": "nope"}, "non-empty"),
        ]:
            status, body = _post(port, payload, path="/score")
            assert status == 400, payload
            assert needle in body["error"], (payload, body)
    finally:
        server.stop()
