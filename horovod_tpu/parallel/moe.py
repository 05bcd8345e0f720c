"""Expert parallelism: GShard/Switch-style Mixture-of-Experts over all_to_all.

The reference ships only the routing primitive — Alltoallv with per-rank
splits (collective_operations.h:199-268), which SURVEY.md §2.3 identifies as
"the EP routing primitive; no MoE layer ships".  This module completes the
pattern TPU-native: gating, capacity-bucketed dispatch, and the expert
exchange expressed as dense einsums + one ``lax.all_to_all`` each way inside
the compiled program — static shapes throughout (XLA requirement), token
overflow handled by capacity dropping, never by dynamic shapes.

Layout (inside ``shard_map`` over the expert axis, default "hvd"):

* activations  [T_local, d]           — sharded over the axis (data/tokens)
* expert weights [E_local, d, d_ff]   — sharded over the axis (experts)
* dispatch     [T, E, C] one-hot      — built locally per shard
* exchange     [E, C, d] ->(all_to_all)-> [E_local, n*C, d]

so each device computes only its local experts on tokens gathered from every
shard, and a mirror all_to_all routes results back.  Both exchanges ride the
ICI torus; the einsums are MXU-shaped batched matmuls.

Auxiliary load-balancing loss follows Switch Transformer (§2.2 of the paper):
``E * sum_e f_e * P_e`` where f_e is the fraction of tokens routed to expert
e and P_e the mean router probability.

``dropless_expert_ffn`` (second half of the module) is the other kind of
layer: told which experts it holds, it sorts the (token, choice) pairs
routed to them into one buffer and runs grouped matrix products whose work
follows the group sizes (``parallel/grouped.py``): no capacity, no dropped
token, no array over tokens x experts (docs/moe.md).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import scopes as _scopes


class MoEOutput(NamedTuple):
    out: jax.Array          # [T_local, d] combined expert outputs
    aux_loss: jax.Array     # scalar load-balancing loss (Switch style)
    dropped_frac: jax.Array  # scalar: fraction of (token, choice) slots
    # dropped by capacity — monitor; raise capacity_factor if high


def _top_k_gating(logits: jax.Array, top_k: int, score_func: str = "softmax",
                  selection_bias: Optional[jax.Array] = None,
                  route_scale: float = 1.0):
    """Top-k router: returns (indices [T, k], weights [T, k], scores [T, E]).

    The scores are the float32 ``logits``' softmax (GShard convention) or,
    each output alone, their sigmoid.  The ``top_k`` largest of ``scores +
    selection_bias`` are chosen: the bias picks, weighs nothing and takes no
    gradient.  A chosen expert's weight is its score over the chosen
    scores' sum (plus 1e-20, which a sum above 1e-12 does not feel), times
    ``route_scale``."""
    logits = logits.astype(jnp.float32)
    if score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score_func {score_func!r}: softmax or sigmoid")
    if selection_bias is None:
        weights, indices = lax.top_k(scores, top_k)
    else:
        _, indices = lax.top_k(scores + lax.stop_gradient(
            selection_bias.astype(jnp.float32)), top_k)
        weights = jnp.take_along_axis(scores, indices, axis=-1)
    weights = route_scale * weights / (
        weights.sum(axis=-1, keepdims=True) + 1e-20)
    return indices, weights, scores


def _dispatch_combine(indices, weights, probs, num_experts: int,
                      capacity: int):
    """Build the [T, E, C] dispatch (0/1) and combine (weighted) tensors.

    Position-in-expert via cumsum over tokens per (choice, expert) — the
    static-shape GShard bucketing: a token whose position exceeds the
    capacity is dropped (its one-hot row zeroes out)."""
    T, k = indices.shape
    # [k, T, E] one-hot of choices, processed choice-major so primary
    # choices claim capacity before secondary ones.
    onehot = jax.nn.one_hot(indices.T, num_experts, dtype=jnp.float32)
    # Position of each token within its expert bucket, counting all
    # earlier (choice, token) claims.
    flat = onehot.reshape(k * T, num_experts)
    pos = jnp.cumsum(flat, axis=0) - flat          # claims before this one
    in_cap = (pos < capacity).astype(jnp.float32) * flat
    kept = in_cap.reshape(k, T, num_experts)
    pos = pos.reshape(k, T, num_experts)
    # [k, T, E, C] -> summed over k -> [T, E, C].  pos comes from a float
    # cumsum; one_hot wants integer positions (float is deprecated).
    cap_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=jnp.float32) * kept[..., None]
    dispatch = cap_onehot.sum(axis=0)
    combine = jnp.einsum("tk,ktec->tec", weights.astype(jnp.float32),
                         cap_onehot)
    dropped = 1.0 - kept.sum() / (T * k)
    return dispatch, combine, dropped


def switch_aux_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch Transformer load-balancing loss: E * sum_e f_e * P_e."""
    num_experts = probs.shape[-1]
    f = dispatch.sum(axis=2).mean(axis=0)       # fraction routed per expert
    p = probs.mean(axis=0)                      # mean router prob per expert
    return num_experts * jnp.sum(f * p)


def expert_parallel_ffn(x: jax.Array,
                        gate_kernel: jax.Array,
                        w_in: jax.Array,
                        w_out: jax.Array,
                        *,
                        axis_name: Optional[str] = "hvd",
                        top_k: int = 2,
                        capacity_factor: float = 1.25,
                        activation: Callable = jax.nn.gelu) -> MoEOutput:
    """Mixture-of-experts FFN with experts sharded over ``axis_name``.

    Args (shapes per shard, inside shard_map):
      x:           [T, d]   local tokens
      gate_kernel: [d, E]   router (replicated; E = global expert count)
      w_in:        [E_local, d, d_ff]  this shard's expert up-projections
      w_out:       [E_local, d_ff, d]  this shard's expert down-projections

    ``axis_name=None`` runs the same math single-device (E_local = E) —
    the unsharded reference used by the tests.
    """
    n = lax.axis_size(axis_name) if axis_name else 1
    T, d = x.shape
    e_local = w_in.shape[0]
    num_experts = e_local * n
    if gate_kernel.shape[-1] != num_experts:
        raise ValueError(
            f"gate maps to {gate_kernel.shape[-1]} experts but weights "
            f"provide {e_local} local x {n} shards = {num_experts}")
    capacity = max(1, int(capacity_factor * top_k * T / num_experts))

    logits = x.astype(jnp.float32) @ gate_kernel.astype(jnp.float32)
    indices, weights, probs = _top_k_gating(logits, top_k)
    dispatch, combine, dropped = _dispatch_combine(
        indices, weights, probs, num_experts, capacity)
    aux = switch_aux_loss(probs, dispatch)

    # [T, E, C] x [T, d] -> [E, C, d]
    buckets = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    if axis_name:
        # [E, C, d] = [n * E_local, C, d] --all_to_all--> every shard
        # receives the buckets for ITS experts from all n shards:
        # [n, E_local, C, d] -> [E_local, n * C, d].
        buckets = buckets.reshape(n, e_local, capacity, d)
        buckets = lax.all_to_all(buckets, axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
        buckets = buckets.transpose(1, 0, 2, 3).reshape(
            e_local, n * capacity, d)
    else:
        buckets = buckets.reshape(e_local, capacity, d)

    # Batched expert FFN: [E_local, n*C, d] @ [E_local, d, f] -> ... -> d
    h = activation(jnp.einsum("ecd,edf->ecf", buckets, w_in))
    h = jnp.einsum("ecf,efd->ecd", h, w_out)

    if axis_name:
        h = h.reshape(e_local, n, capacity, d).transpose(1, 0, 2, 3)
        h = lax.all_to_all(h, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)
        h = h.reshape(num_experts, capacity, d)
    out = jnp.einsum("tec,ecd->td", combine.astype(h.dtype), h)
    return MoEOutput(out.astype(x.dtype), aux,
                     jnp.asarray(dropped, jnp.float32))


# -- the dropless layer ---------------------------------------------------------

class DroplessOutput(NamedTuple):
    out: jax.Array          # [T, d] what the held experts add for each token
    routed_here: jax.Array  # int32 scalar: (token, choice) pairs the held
    # experts computed (of T * k routed over all experts; with axis_name,
    # pairs received from every shard)
    chosen: jax.Array       # [T, k] int32: every token's experts, of all E


def _with_zero_row(rows):
    """``rows`` with one zero row appended: what a pair with no slot
    (index ``rows.shape[0]``) reads."""
    return jnp.concatenate(
        [rows, jnp.zeros((1,) + rows.shape[1:], rows.dtype)])


@jax.custom_vjp
def _dispatch(x, slot_of_pair, pair_of_slot):
    """``buffer[s] = x[token of the pair in slot s]``: ``[S, d]`` from
    ``[N, d]``.  ``slot_of_pair`` is ``[N, k]`` (``S`` for a pair with no
    slot), ``pair_of_slot`` is ``[S]`` (flat ``token * k + choice``; any
    pair for a slot not in use, whose row nobody reads)."""
    return x[pair_of_slot // slot_of_pair.shape[1]]


def _dispatch_fwd(x, slot_of_pair, pair_of_slot):
    return (_dispatch(x, slot_of_pair, pair_of_slot),
            (slot_of_pair, x[:0]))


def _dispatch_bwd(res, dbuffer):
    # The transpose of a gather is a scatter-add; with the inverse map at
    # hand it is a gather too: each token sums the rows of its own pairs.
    slot_of_pair, like = res
    dbuffer = _with_zero_row(dbuffer)
    dx = 0
    for j in range(slot_of_pair.shape[1]):
        dx += dbuffer[slot_of_pair[:, j]].astype(jnp.float32)
    return dx.astype(like.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(buffer, weights, slot_of_pair, pair_of_slot, in_use):
    """``out[t] = sum_j weights[t, j] * buffer[slot of pair (t, j)]`` in
    float32: the mirror of ``_dispatch``."""
    buffer = _with_zero_row(buffer)
    out = 0
    for j in range(weights.shape[1]):
        out += weights[:, j, None] * buffer[slot_of_pair[:, j]].astype(
            jnp.float32)
    return out


def _combine_fwd(buffer, weights, slot_of_pair, pair_of_slot, in_use):
    return (_combine(buffer, weights, slot_of_pair, pair_of_slot, in_use),
            (buffer, weights, slot_of_pair, pair_of_slot, in_use))


def _combine_bwd(res, dout):
    buffer, weights, slot_of_pair, pair_of_slot, in_use = res
    k = weights.shape[1]
    padded = _with_zero_row(buffer)
    dweights = jnp.stack([
        jnp.sum(dout * padded[slot_of_pair[:, j]].astype(jnp.float32),
                axis=-1) for j in range(k)], axis=1)
    scale = jnp.where(in_use, weights.reshape(-1)[pair_of_slot], 0.0)
    dbuffer = scale[:, None] * dout[pair_of_slot // k]
    return (dbuffer.astype(buffer.dtype), dweights.astype(weights.dtype),
            None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _sort_pairs(key, value, key_bound: int, value_bound: int):
    """``key`` sorted ascending, ties in ``value``'s order, and ``value``
    carried along: both int32, ``0 <= key < key_bound`` and ``0 <= value <
    value_bound``.  Where the two fit one int32 they are sorted as one
    array, which the TPU sorts several times faster than a pair."""
    if key_bound * value_bound < 2 ** 31:
        packed = lax.sort(key * value_bound + value)
        return packed // value_bound, packed % value_bound
    return lax.sort((key, value), num_keys=1)


def _count_below(sorted_key, bounds):
    """How many of ``sorted_key`` are below each of the static ``bounds``:
    a reduction a bound, no bisection (a gather loop on the TPU) and no
    array over pairs x bounds."""
    return jnp.stack([jnp.sum(sorted_key < bound, dtype=jnp.int32)
                      for bound in bounds])


def _slots(key, key_bound: int, num_slots: int, slot_of_rank, rank_of_slot):
    """Sort the flat pairs by ``key`` (``< key_bound``) and give each a
    slot of a buffer of ``num_slots`` rows.  The caller says where the
    pair of each rank goes, ``slot_of_rank(rank, sorted key)``
    (``num_slots`` or more: nowhere; slots rise with the rank), and the
    way back, ``rank_of_slot(slot, sorted key) -> (rank, in use?)``.
    Returns ``(slot_of_pair [P], pair_of_slot [S], in_use [S], sorted
    key)``.  Two sorts and no scatter, no array over pairs x experts."""
    pairs = key.shape[0]
    ranks = jnp.arange(pairs, dtype=jnp.int32)
    sorted_key, order = _sort_pairs(key, ranks, key_bound, pairs)
    slot_of_rank = jnp.minimum(slot_of_rank(ranks, sorted_key), num_slots)
    _, slot_of_pair = _sort_pairs(order, slot_of_rank, pairs, num_slots + 1)
    rank, in_use = rank_of_slot(jnp.arange(num_slots, dtype=jnp.int32),
                                sorted_key)
    return (slot_of_pair, order[jnp.minimum(rank, pairs - 1)], in_use,
            sorted_key)


#: ``jax.checkpoint`` name of what ``_held_experts`` needs again in its
#: backward pass and cannot make from its inputs for an elementwise pass:
#: the sorted buffer, the three products' results, the slots and sizes.
KEPT = "hvd_moe_kept"


def _held_experts(x, expert, weights, w_gate, w_up, w_down, rows: int,
                  interpret):
    """``sum_j weights[t, j] * FFN_{expert[t, j]}(x[t])`` over the pairs
    whose ``expert`` is one of the ``E`` held (``expert == E``: not
    here), in a buffer of ``rows`` rows sorted by expert, which the caller
    has shown to be enough.  Gated SiLU experts as grouped products."""
    from .grouped import gmm
    keep = functools.partial(checkpoint_name, name=KEPT)
    held = w_gate.shape[0]
    n, k = expert.shape
    flat = expert.reshape(-1).astype(jnp.int32)
    # Held pairs sort first, so the pair of rank r sits in slot r.
    here = jnp.sum(flat < held, dtype=jnp.int32)
    slot_of_pair, pair_of_slot, in_use, sorted_expert = _slots(
        flat, held + 1, rows,
        lambda rank, key: jnp.where(key < held, rank, rows),
        lambda slot, key: (slot, slot < here))
    ends = _count_below(sorted_expert, range(1, held + 1))
    group_sizes = keep(ends - jnp.concatenate([jnp.zeros(1, jnp.int32),
                                               ends[:-1]]))
    slot_of_pair, pair_of_slot, in_use = (
        keep(slot_of_pair.reshape(n, k)), keep(pair_of_slot), keep(in_use))
    with _scopes.scope("hvd::moe::experts"):
        xs = keep(_dispatch(x, slot_of_pair, pair_of_slot))
        gate = keep(gmm(xs, w_gate, group_sizes, interpret=interpret))
        up = keep(gmm(xs, w_up, group_sizes, interpret=interpret))
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(xs.dtype)
        ys = keep(gmm(hidden, w_down, group_sizes, interpret=interpret))
    with _scopes.scope("hvd::moe::combine"):
        return _combine(ys, weights, slot_of_pair, pair_of_slot, in_use)


#: Even loads that the buffer of ``_held_experts_any_load`` holds.  The
#: gathers into and out of the buffer and the gated unit between the
#: products run over all of its rows whatever the load (only the grouped
#: products stop at the last group), so the buffer is sized for the load
#: expected, not for the worst: a sequence of the benchmark's cell sends
#: 0.95 to 1.1 even loads here (PERF.md, PR 27), a buffer for the worst
#: routing, 8 even loads, would add some 15 % to that cell's step (those
#: passes take 50 ms of its second at 2 even loads), and more than this
#: goes a chunk at a time (31.6 ms a layer and sequence at 3.3 even loads,
#: 10.4 at 1.35: ``chip_smoke.py``, phase ``sdar``).
BUFFER_LOADS = 2


def _held_experts_any_load(x, expert, weights, w_gate, w_up, w_down,
                           share: float, interpret):
    """``_held_experts`` under any routing.  The buffer holds
    ``BUFFER_LOADS`` times the pairs an even routing sends here (``share``
    of all); where more arrive, the tokens are taken a chunk at a time,
    each chunk so small that all of its pairs fit: slower, never wrong,
    nothing dropped."""
    from .grouped import row_tile
    held = w_gate.shape[0]
    n, k = expert.shape
    want = min(n * k, max(k, int(BUFFER_LOADS * share * n * k)))
    rows = -(-want // row_tile(want)) * row_tile(want)
    def fits(x, expert, weights):
        return _held_experts(x, expert, weights, w_gate, w_up, w_down, rows,
                             interpret)

    if rows >= n * k:
        return fits(x, expert, weights)

    def by_chunks(x, expert, weights):
        chunk = rows // k
        pad = -n % chunk

        def chunks(a, fill):
            a = jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)
            return a.reshape((n + pad) // chunk, chunk, a.shape[1])

        out = lax.map(jax.checkpoint(lambda c: fits(*c)),
                      (chunks(x, 0), chunks(expert, held),
                       chunks(weights, 0)))
        return out.reshape(n + pad, x.shape[1])[:n]

    # The two branches hand the backward pass the union of what each
    # keeps, the other's part as zeros: keep the products' operands and
    # results alone (``KEPT``), not every float32 value of the gated unit.
    kept = jax.checkpoint(
        fits, policy=jax.checkpoint_policies.save_only_these_names(KEPT))
    return lax.cond(jnp.sum(expert < held) <= rows, kept, by_chunks,
                    x, expert, weights)


def dropless_expert_ffn(x: jax.Array,
                        router: jax.Array,
                        w_gate: jax.Array,
                        w_up: jax.Array,
                        w_down: jax.Array,
                        *,
                        top_k: int,
                        first_expert=0,
                        axis_name: Optional[str] = None,
                        score_func: str = "softmax",
                        selection_bias: Optional[jax.Array] = None,
                        route_scale: float = 1.0,
                        interpret: Optional[bool] = None) -> DroplessOutput:
    """Mixture-of-experts FFN that is told which experts it holds, routes
    over all of them and drops nothing.

    Args (per shard):
      x:       [T, d]  tokens (bf16 or float32; the products run in it)
      router:  [d, E]  the router over ALL ``E`` experts
      w_gate, w_up:    [E_held, d, f]   the held experts' gated SiLU
      w_down:          [E_held, f, d]   and down projections, in the dtype
                       they are stored in (float32 parameters beside bf16
                       tokens): ``grouped.gmm`` rounds a matrix to ``x``'s
                       dtype inside its kernel, and their gradients come
                       in their own dtype from float32 sums

    Each token takes its ``top_k`` experts by softmax probability, the
    chosen probabilities divided by their sum (``norm_topk_prob``).  The
    router's variants (``_top_k_gating``; scores, top-k and weights in
    float32 at the highest precision whatever the variant):
    ``score_func="sigmoid"`` scores every expert alone; ``selection_bias
    [E]`` is added to the scores for the choice only, never to a weight, and
    takes no gradient; ``route_scale`` multiplies the weights.  Nothing
    after the routing knows which variant chose.  The (token, choice) pairs
    whose expert is held here, experts
    ``first_expert .. first_expert + E_held - 1``, are sorted by expert
    into one buffer, the three expert products run as grouped matrix
    products whose work follows the group sizes (``parallel/grouped.py``),
    and each token adds up its weighted results: ``out`` is the part of
    the layer's result that the held experts give, zero for a token routed
    wholly elsewhere.  Shapes are static; there is no capacity and no
    array over tokens x experts.

    ``axis_name=None``: one chip's share of a layer whose other experts
    live on chips that are not here; nothing stands in for them.
    With ``axis_name`` (inside ``shard_map``) the layer is whole: shard
    ``i`` holds experts ``i * E_held ..`` (``first_expert`` is not read),
    every pair travels to its expert's shard and back by
    ``lax.all_to_all``, in buffers sized for the worst routing (every
    pair of a shard to one destination), so nothing is dropped there
    either.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    held = w_gate.shape[0]
    num_experts = router.shape[-1]
    n_tokens, d = x.shape
    shards = lax.axis_size(axis_name) if axis_name else 1
    if axis_name and shards * held != num_experts:
        raise ValueError(
            f"router over {num_experts} experts, but {shards} shards hold "
            f"{held} each")
    # As they are stored: the grouped products cast a group's matrix in
    # VMEM, so no copy of the stack in ``x``'s dtype is ever made.
    experts = (w_gate, w_up, w_down)

    with _scopes.scope("hvd::moe::route"):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        chosen, weights, _ = _top_k_gating(logits, top_k, score_func,
                                           selection_bias, route_scale)
        chosen = chosen.astype(jnp.int32)

    if not axis_name:
        local = chosen - first_expert
        local = jnp.where((local >= 0) & (local < held), local, held)
        out = _held_experts_any_load(x, local, weights, *experts,
                                     share=held / num_experts,
                                     interpret=interpret)
        return DroplessOutput(out.astype(x.dtype),
                              jnp.sum(local < held, dtype=jnp.int32), chosen)

    # Every pair to the shard of its expert: destination-major buffers of
    # T * k slots a destination, so that any routing fits.
    per_dest = n_tokens * top_k
    flat = chosen.reshape(-1)

    def dest_starts(expert):
        return _count_below(expert, [d * held for d in range(shards + 1)])

    def slot_of_rank(rank, expert):
        dest = expert // held
        return dest * per_dest + rank - dest_starts(expert)[dest]

    def rank_of_slot(slot, expert):
        dest, place = slot // per_dest, slot % per_dest
        starts = dest_starts(expert)
        return (starts[dest] + place,
                place < starts[dest + 1] - starts[dest])

    with _scopes.scope("hvd::moe::route"):
        slot_of_pair, pair_of_slot, in_use, _ = _slots(
            flat, num_experts, shards * per_dest, slot_of_rank,
            rank_of_slot)
        slot_of_pair = slot_of_pair.reshape(n_tokens, top_k)
        sent = _dispatch(x, slot_of_pair, pair_of_slot)
        sent_expert = jnp.where(in_use, flat[pair_of_slot] % held, held)
        exchange = functools.partial(
            lax.all_to_all, axis_name=axis_name, split_axis=0,
            concat_axis=0, tiled=True)
        received = exchange(sent)
        received_expert = exchange(sent_expert)
    results = _held_experts_any_load(
        received, received_expert[:, None],
        jnp.ones((shards * per_dest, 1), jnp.float32), *experts,
        share=1.0 / shards, interpret=interpret)
    with _scopes.scope("hvd::moe::combine"):
        back = exchange(results.astype(x.dtype))
        out = _combine(back, weights, slot_of_pair, pair_of_slot, in_use)
    return DroplessOutput(out.astype(x.dtype),
                          jnp.sum(received_expert < held, dtype=jnp.int32),
                          chosen)
