"""Operations of one JoyAI-LLM-Flash training sample (next-token loss plus
the depth-1 multi-token-prediction loss), from shapes.

A sample is one sequence of ``S`` tokens.  Counted here, independent of
``horovod_tpu/models/joyai_flash.py``: the matrix products of the layers
held and of the MTP module (two operations a multiply-add, the backward pass
twice the forward), and nothing else: norms, rotary embedding, softmax,
router top-k, the optimizer and the recomputation of each layer in the
backward pass are left out.  The MTP module's block is one more expert
layer, over all ``S`` rows as the program runs it.  By part:

* ``projections``: latent attention's five a layer (``W_qa``, ``W_qb``,
  ``W_kva``, ``W_kvb``, ``W_o``), every position;
* ``attention``: the **attended pairs** under the causal mask, not the tiles
  the kernels touch: ``S (S + 1) / 2`` query-key pairs a head, ``qk_head_dim
  + v_head_dim`` multiply-adds each forward (a score over the whole key, a
  value), the MTP block's pairs among them;
* ``dense_mlp``: three products of width ``intermediate_size`` in each of
  the leading dense layers;
* ``shared``: three products of the shared experts' width in every expert
  layer, every position;
* ``router``: every position over all published experts;
* ``experts``: the **expected** (position, choice) pairs routed to the
  experts held: ``S x k x held / published`` a layer (a seeded router is
  even on average; the step logs the pairs it really routed);
* ``mtp_projection``: ``W_eh``, ``2 x hidden`` into ``hidden``, every
  position;
* ``head``: the ``S - 1`` positions that predict through the main head and
  the ``S - 2`` through the MTP module's, over the rows of the vocabulary
  held.

So the count is at or under the work the kernels do (they also compute the
masked part of the tiles on the mask's edge), and a share of the peak made
from it cannot pass 100 %.
"""


def attended_pairs(length: int) -> int:
    """Query-key pairs ``k <= q`` among ``length`` positions."""
    return length * (length + 1) // 2


def forward_macs_by_part(config: dict) -> dict:
    """Multiply-adds of one sample's forward pass, by part."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v, nope = config["v_head_dim"], config["qk_nope_head_dim"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    seq = config["assumed"]["sequence_length"]["value"]
    dense_layers = config["first_k_dense_replace"]
    mtp = config["num_nextn_predict_layers"]
    expert_layers = config["num_hidden_layers"] - dense_layers + mtp
    layers = dense_layers + expert_layers
    width = config["moe_intermediate_size"]
    routed = config["published"]["n_routed_experts"]
    pairs_here = seq * config["num_experts_per_tok"] \
        * config["n_routed_experts"] / routed
    return {
        "projections": layers * seq * (
            d * q_rank + q_rank * heads * qk
            + d * (kv_rank + config["qk_rope_head_dim"])
            + kv_rank * heads * (nope + v) + heads * v * d),
        "attention": layers * attended_pairs(seq) * heads * (qk + v),
        "dense_mlp": dense_layers * seq * 3 * d
        * config["intermediate_size"],
        "shared": expert_layers * seq * 3 * d * width
        * config["n_shared_experts"],
        "router": expert_layers * seq * d * routed,
        "experts": expert_layers * pairs_here * 3 * d * width,
        "mtp_projection": mtp * seq * 2 * d * d,
        "head": ((seq - 1) + mtp * (seq - 2)) * d * config["vocab_size"],
    }


def train_flops_by_part(config: dict) -> dict:
    """Forward plus backward (2 x forward), 2 operations a multiply-add."""
    return {part: 3 * 2 * macs
            for part, macs in forward_macs_by_part(config).items()}


def train_flops_per_sample(config: dict) -> float:
    return sum(train_flops_by_part(config).values())
