"""The decoders' shared parts (``models/decoder.py``) on small arrays:
the grouping of a stack into runs, the loop over the runs with its
``Aux``, the next-token mean loss; and the rule that the five decoders
build from ``decoder`` and not from each other."""

import ast
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import decoder

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "horovod_tpu", "models")
DECODERS = ("sdar_moe", "afmoe", "joyai_flash", "kimi_linear", "lfm2_moe")


@pytest.mark.parametrize("kinds, runs", [
    (["a", "a", "b", "a", "a", "a"], [("a", 2), ("b", 1), ("a", 3)]),
    ([(True, 1), (False, 1), (False, 1), (False, 0)],
     [((True, 1), 1), ((False, 1), 2), ((False, 0), 1)]),
    (["only"] * 5, [("only", 5)]),
])
def test_group_runs(kinds, runs):
    assert decoder.group_runs(kinds) == runs
    assert sum(count for _, count in runs) == len(kinds)


BATCH, SEQ, WIDTH, TOP_K = 2, 4, 3, 2


def _toy_stack(kinds):
    """``(runs, stacked, calls, layer maker)`` of a stack whose layer adds
    its parameter and, when it routes, reports ``(its layer number, the
    layer number at every position's choices)``; ``calls`` records what the
    maker was given."""
    runs = [(kind, count) for kind, count in decoder.group_runs(kinds)]
    stacked, first = [], 1
    for _, count in runs:
        numbers = jnp.arange(first, first + count, dtype=jnp.int32)
        stacked.append({"n": numbers})
        first += count
    calls = []

    def make_layer(kind, numbers):
        calls.append((kind, list(numbers)))

        def layer(x, p):
            x = x + p["n"].astype(x.dtype)
            if kind == "dense":
                return x, ()
            return x, (p["n"], jnp.full((x.shape[0], TOP_K), p["n"]))
        return layer

    return runs, stacked, calls, make_layer


def test_through_runs_concatenates_the_routing_in_run_order():
    kinds = ["dense", "expert", "expert", "dense", "expert"]
    runs, stacked, calls, make_layer = _toy_stack(kinds)
    x = jnp.zeros((BATCH, SEQ, WIDTH), jnp.float32)
    y, aux = decoder.through_runs(x, runs, stacked, make_layer, TOP_K)
    assert calls == [("dense", [1]), ("expert", [2, 3]), ("dense", [4]),
                     ("expert", [5])]
    np.testing.assert_array_equal(y, np.full(y.shape, 1 + 2 + 3 + 4 + 5))
    # An expert layer's count, summed over the batch's sequences.
    np.testing.assert_array_equal(aux.routed_here,
                                  [2 * BATCH, 3 * BATCH, 5 * BATCH])
    assert aux.chosen.shape == (3, BATCH * SEQ, TOP_K)
    np.testing.assert_array_equal(aux.chosen[:, 0, 0], [2, 3, 5])
    assert (aux.chosen == np.array([2, 3, 5])[:, None, None]).all()


def test_through_runs_of_dense_layers_alone_routes_nothing():
    runs, stacked, _, make_layer = _toy_stack(["dense"] * 3)
    x = jnp.ones((BATCH, SEQ, WIDTH), jnp.float32)
    y, aux = decoder.through_runs(x, runs, stacked, make_layer, TOP_K)
    np.testing.assert_array_equal(y, np.full(y.shape, 1 + 1 + 2 + 3))
    assert aux.routed_here.shape == (0,)
    assert aux.chosen.shape == (0, BATCH * SEQ, TOP_K)
    assert aux.routed_here.dtype == aux.chosen.dtype == jnp.int32


@pytest.mark.parametrize("ahead", [1, 2])
def test_next_token_loss_is_the_mean_over_the_rows_that_predict(ahead):
    batch, seq, d, vocab = 2, 8, 4, 11
    keys = jax.random.split(jax.random.PRNGKey(ahead), 4)
    params = {"head": jax.random.normal(keys[0], (d, vocab)),
              "final_norm": 1 + 0.1 * jax.random.normal(keys[1], (d,))}
    hidden = jax.random.normal(keys[2], (batch, seq, d))
    tokens = jax.random.randint(keys[3], (batch, seq), 0, vocab)
    cfg = types.SimpleNamespace(rms_norm_eps=1e-6, dtype=jnp.float32,
                                loss_chunk=4)
    got = decoder.next_token_loss(params, hidden, tokens, cfg, ahead)

    h = np.asarray(hidden, np.float64)
    h = h / np.sqrt((h * h).mean(-1, keepdims=True) + 1e-6)
    logits = (h * np.asarray(params["final_norm"])) @ np.asarray(
        params["head"], np.float64)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    nll = [-logp[b, i, int(tokens[b, i + ahead])]
           for b in range(batch) for i in range(seq - ahead)]
    assert len(nll) == batch * (seq - ahead)
    np.testing.assert_allclose(float(got), np.mean(nll), rtol=1e-5)


def _imported_models(name):
    """The sibling modules of ``models/`` that ``<name>.py`` imports."""
    with open(os.path.join(MODELS, name + ".py")) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level == 1:
            found |= {node.module} if node.module else {
                a.name for a in node.names}
    return found


@pytest.mark.parametrize("name", DECODERS)
def test_a_decoder_builds_from_decoder_alone(name):
    assert _imported_models(name) == {"decoder"}


def test_decoder_exports_no_parts():
    """Its scopes are exported by the decoders that call it
    (``tests/test_step_scopes.py``), so a cell's partition is its own
    model's ``PARTS``."""
    assert not hasattr(decoder, "PARTS")
