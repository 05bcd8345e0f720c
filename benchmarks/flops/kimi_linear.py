"""Operations of one Kimi-Linear training sample (causal next-token loss),
and the bytes its scan kernels must move, from shapes.

A sample is one sequence of ``S`` tokens.  Counted here, independent of
``horovod_tpu/models/kimi_linear.py``: the matrix products of the layers
held and the recurrence of Kimi Delta Attention (two operations a
multiply-add, the backward pass twice the forward), and nothing else: norms,
the short convolutions, the gates, softmax, router top-k, the optimizer and
the recomputation of each layer in the backward pass are left out.  By part:

* ``kda_projections``: a KDA layer's products, every position: ``W_q``,
  ``W_k``, ``W_v`` into ``heads x head_dim``, the two low-rank pairs
  (``W_fa W_fb`` of the decay, ``W_ga W_gb`` of the output gate), ``W_b``
  and ``W_o``;
* ``kda_recurrence``: **the recurrence's own operations, whatever
  implements it**: a token and head decays nothing on the MXU, reads the
  state (``k^T S``, ``d x d`` multiply-adds), writes its rank-one update
  (``d x d``) and reads it again (``S^T q``, ``d x d``): ``S x heads x 3 x
  d x d`` a layer.  The chunked form does about twice these products (the
  triangular system, ``w``, ``u``, the in-chunk pairs) and is not counted
  by what it does;
* ``projections``: latent attention's four (``W_q``, ``W_kva``, ``W_kvb``,
  ``W_o``; ``q_lora_rank`` null), every position;
* ``attention``: the **attended pairs** of the latent layers under the
  causal mask, not the tiles the kernels touch: ``S (S + 1) / 2`` query-key
  pairs a head, ``qk_nope_head_dim + qk_rope_head_dim + v_head_dim``
  multiply-adds each forward;
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

So the count is at or under the work the kernels do, and a share of the
peak made from it cannot pass 100 %.

``kda_scan_bytes``: what the scan kernels cannot avoid moving, a sample:
``q``, ``k``, ``v``, ``beta`` in and ``o`` out at 2 bytes, ``g`` in at 4,
once forward; backward the same operands and ``dO`` in and the five
gradients out, twice that.  The chunks' states that the forward kernel
writes for the backward one, and the forward kernel's second run where a
layer is recomputed, are the implementation's and not counted.
"""


def attended_pairs(length: int) -> int:
    """Query-key pairs ``k <= q`` among ``length`` positions."""
    return length * (length + 1) // 2


def _layers(config: dict):
    """``(KDA layers, latent layers, dense layers, expert layers)``."""
    linear = config["linear_attn_config"]
    dense = config["first_k_dense_replace"]
    return (len(linear["kda_layers"]), len(linear["full_attn_layers"]),
            dense, config["num_hidden_layers"] - dense)


def forward_macs_by_part(config: dict) -> dict:
    """Multiply-adds of one sample's forward pass, by part."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, kv_rank = config["v_head_dim"], config["kv_lora_rank"]
    linear, assumed = config["linear_attn_config"], config["assumed"]
    kda_heads, kda_dim = linear["num_heads"], linear["head_dim"]
    wide, rank = kda_heads * kda_dim, assumed["gate_rank"]["value"]
    seq = assumed["sequence_length"]["value"]
    kda_layers, latent_layers, dense_layers, expert_layers = _layers(config)
    width = config["moe_intermediate_size"]
    routed = config["published"]["num_experts"]
    pairs_here = seq * config["num_experts_per_token"] \
        * config["num_experts"] / routed
    return {
        "kda_projections": kda_layers * seq * (
            3 * d * wide + 2 * (d * rank + rank * wide) + d * kda_heads
            + wide * d),
        "kda_recurrence": kda_layers * seq * kda_heads * 3 * kda_dim
        * kda_dim,
        "projections": latent_layers * seq * (
            d * heads * (nope + rope) + d * (kv_rank + rope)
            + kv_rank * heads * (nope + v) + heads * v * d),
        "attention": latent_layers * attended_pairs(seq) * heads
        * (nope + rope + v),
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


def kda_scan_bytes(config: dict) -> int:
    """Bytes the scan kernels of all KDA layers move for one sample,
    forward and backward (see above)."""
    linear = config["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    seq = config["assumed"]["sequence_length"]["value"]
    forward = seq * heads * (4 * d * 2 + 2 + d * 4)
    return len(linear["kda_layers"]) * 3 * forward
