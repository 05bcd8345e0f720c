"""Operations of one AFMoE (Trinity-Mini) next-token training sample, from
shapes.

A sample is one sequence of ``S`` tokens.  Counted here, independent of
``horovod_tpu/models/afmoe.py``: the matrix products of the layers held (two
operations a multiply-add, the backward pass twice the forward), and nothing
else: norms, rotary embedding, softmax, the sigmoid gate, router top-k, the
optimizer and the recomputation of each layer in the backward pass are left
out.  By part:

* ``projections``: five a layer (query, key, value, gate, output), every
  position;
* ``attention``: the **attended pairs under each layer's mask**, not the
  tiles the kernels touch: ``attended_pairs(S, window)`` query-key pairs a
  head (a ``full_attention`` layer: the window is the sequence), ``2 x
  head_dim`` multiply-adds each forward (scores and values);
* ``dense_mlp``: three products of width ``intermediate_size`` in each of
  the leading dense layers;
* ``shared``: three products of the shared experts' width in every expert
  layer, every position;
* ``router``: every position over all published experts;
* ``experts``: the **expected** (position, choice) pairs routed to the
  experts held: ``S x k x held / published`` a layer (a seeded router is
  even on average; the step logs the pairs it really routed);
* ``head``: the ``S - 1`` positions that predict, over the rows of the
  vocabulary held.

So the count is at or under the work the kernels do (they also compute the
masked part of the tiles on a mask's edge), and a share of the peak made
from it cannot pass 100 %.
"""

SLIDING = "sliding_attention"


def attended_pairs(length: int, window: int) -> int:
    """Query-key pairs ``0 <= q - k < window`` among ``length`` positions:
    query ``q`` reads ``min(q + 1, window)`` keys."""
    w = min(window, length)
    return w * (w + 1) // 2 + (length - w) * w


def forward_macs_by_part(config: dict) -> dict:
    """Multiply-adds of one sample's forward pass, by part."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    seq = config["assumed"]["sequence_length"]["value"]
    types = config["layer_types"]
    dense_layers = config["num_dense_layers"]
    expert_layers = len(types) - dense_layers
    width = config["moe_intermediate_size"]
    routed, held = config["published"]["num_experts"], config["num_experts"]
    pairs = sum(attended_pairs(
        seq, config["sliding_window"] if kind == SLIDING else seq)
        for kind in types)
    pairs_here = seq * config["num_experts_per_tok"] * held / routed
    return {
        "projections": len(types) * seq * d * hd * (3 * heads
                                                    + 2 * kv_heads),
        "attention": pairs * heads * 2 * hd,
        "dense_mlp": dense_layers * seq * 3 * d
        * config["intermediate_size"],
        "shared": expert_layers * seq * 3 * d * width
        * config["num_shared_experts"],
        "router": expert_layers * seq * d * routed,
        "experts": expert_layers * pairs_here * 3 * d * width,
        "head": (seq - 1) * d * config["vocab_size"],
    }


def train_flops_by_part(config: dict) -> dict:
    """Forward plus backward (2 x forward), 2 operations a multiply-add."""
    return {part: 3 * 2 * macs
            for part, macs in forward_macs_by_part(config).items()}


def train_flops_per_sample(config: dict) -> float:
    return sum(train_flops_by_part(config).values())
