"""Operations of one SDAR-MoE block-diffusion training sample, from shapes.

A sample is one sequence: ``L`` clean tokens, so ``2L`` positions ``[xt ;
x0]``.  Counted here, independent of ``horovod_tpu/models/sdar_moe.py``: the
matrix products of the layers held (two operations a multiply-add, the
backward pass twice the forward), and nothing else: norms, rotary
embedding, softmax, router top-k, the optimizer and the recomputation of
each layer in the backward pass are left out.

* attention's projections and the router: every position;
* attention itself: the **attended pairs under the mask**, not the tiles
  the kernels touch: ``attended_pairs(L, block)`` query-key pairs a head,
  ``4 x head_dim`` operations each forward (scores and values);
* the experts: the **expected** (position, choice) pairs routed to the
  experts held: ``2L x k x held / published`` (a seeded router is even on
  average; the step logs the pairs it really routed);
* the head: the noised half only, ``L`` positions over the rows of the
  vocabulary held.

So the count is at or under the work the kernels do (they also compute the
masked part of the tiles on the mask's edge), and a share of the peak made
from it cannot pass 100 %.
"""


def attended_pairs(length: int, block: int) -> int:
    """Query-key pairs the block-diffusion mask keeps over ``[xt ; x0]``
    of ``length`` clean tokens in blocks of ``block``: with ``n`` blocks,
    a noised query of block ``b`` reads the ``block`` noised keys of its
    block and ``b x block`` clean keys, a clean query ``(b + 1) x block``
    clean keys."""
    n = length // block
    noised = block * block * n + block * block * n * (n - 1) // 2
    clean = block * block * n * (n + 1) // 2
    return noised + clean


def sizes(config: dict) -> dict:
    assumed = config["assumed"]
    return dict(
        d=config["hidden_size"], layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        width=config["moe_intermediate_size"], held=config["num_experts"],
        routed=config["published"]["num_experts"],
        top_k=config["num_experts_per_tok"], vocab=config["vocab_size"],
        length=assumed["sequence_length"]["value"],
        block=assumed["block_length"]["value"])


def forward_macs_by_part(config: dict) -> dict:
    """Multiply-adds of one sample's forward pass, by part."""
    z = sizes(config)
    positions = 2 * z["length"]
    q_width = z["heads"] * z["head_dim"]
    kv_width = z["kv_heads"] * z["head_dim"]
    projections = positions * z["d"] * (2 * q_width + 2 * kv_width)
    attention = (attended_pairs(z["length"], z["block"]) * z["heads"]
                 * 2 * z["head_dim"])
    router = positions * z["d"] * z["routed"]
    pairs_here = positions * z["top_k"] * z["held"] / z["routed"]
    experts = pairs_here * 3 * z["d"] * z["width"]
    return {
        "projections": z["layers"] * projections,
        "attention": z["layers"] * attention,
        "router": z["layers"] * router,
        "experts": z["layers"] * experts,
        "head": z["length"] * z["d"] * z["vocab"],
    }


def train_flops_by_part(config: dict) -> dict:
    """Forward plus backward (2 x forward), 2 operations a multiply-add."""
    return {part: 3 * 2 * macs
            for part, macs in forward_macs_by_part(config).items()}


def train_flops_per_sample(config: dict) -> float:
    return sum(train_flops_by_part(config).values())
