"""GPT-2 served by ``hvdserve``, and its plain reference.

The program's side is what ``hvdserve --model gpt2-<size> --max-len N``
builds (``serve/server.py: _build_adapter_factory``): ``create_gpt2`` with
``scan_layers=False`` and float32 parameters, wrapped in a
``TransformerAdapter`` whose attention, block size and prefill chunk are the
program's defaults.  One change: the seeded ``model.init`` runs under
``jax.jit`` (eager it is hundreds of small programs, PERF.md).

The reference is the published GPT-2 forward pass in plain ``jax.numpy``:
float32, ``jax.default_matmul_precision("highest")``, no cache, no kernel,
no batching, reading the same parameter tree.  Departures from the
published model are the program's and are listed in the configuration.
"""

import math

import jax
import jax.numpy as jnp


def build_model(config: dict):
    from horovod_tpu.models import create_gpt2
    d = config["n_embd"]
    return create_gpt2(
        "small", scan_layers=False, dtype=jnp.float32,
        vocab_size=config["vocab_size"], num_layers=config["n_layer"],
        num_heads=config["n_head"], d_model=d,
        d_ff=config.get("n_inner") or 4 * d,
        max_len=config["serve"]["max_len"])


def seeded_params(config: dict, seed: int):
    model = build_model(config)
    return jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))


def adapter_factory(config: dict, params):
    """What ``build_replicas`` calls once per replica."""
    from horovod_tpu.serve.engine import TransformerAdapter
    cfg = build_model(config).cfg
    return lambda: TransformerAdapter(cfg, params,
                                      max_len=config["serve"]["max_len"])


# -- the plain reference -----------------------------------------------------

def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward_logprobs(config: dict, params, tokens):
    """``log_softmax`` of the logits at every position of one sequence:
    ``[T, vocab]``, row ``p`` the distribution of token ``p + 1``."""
    T = tokens.shape[0]
    eps = config["layer_norm_epsilon"]
    head_dim = config["n_embd"] // config["n_head"]
    x = params["wte"]["embedding"][tokens] + params["wpe"]["embedding"][:T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for layer in range(config["n_layer"]):
        blk = params[f"block_{layer}"]
        h = layer_norm(x, blk["ln1"], eps)
        qkv = jnp.einsum("td,dche->tche", h, blk["attn"]["qkv"]["kernel"]) \
            + blk["attn"]["qkv"]["bias"]
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        scores = jnp.einsum("qhe,khe->hqk", q, k) / math.sqrt(head_dim)
        probs = jax.nn.softmax(
            jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("hqk,khe->qhe", probs, v)
        x = x + jnp.einsum("qhe,hed->qd", out,
                           blk["attn"]["proj"]["kernel"]) \
            + blk["attn"]["proj"]["bias"]
        h = layer_norm(x, blk["ln2"], eps)
        h = gelu_tanh(h @ blk["fc1"]["kernel"] + blk["fc1"]["bias"])
        x = x + h @ blk["fc2"]["kernel"] + blk["fc2"]["bias"]
    x = layer_norm(x, params["ln_f"], config["final_layer_norm_epsilon"])
    return jax.nn.log_softmax(x @ params["wte"]["embedding"].T, axis=-1)


def reference_token_logprobs(config: dict, params, tokens):
    """Log-probability of each of ``tokens[1:]`` given everything before
    it: ``T - 1`` floats."""
    tokens = jnp.asarray(tokens, jnp.int32)

    @jax.jit
    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            logp = forward_logprobs(config, params, tokens)
        return jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=1)[:, 0]

    return [float(x) for x in run(params, tokens)]
