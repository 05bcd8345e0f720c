"""BERT pretraining with DistributedOptimizer + gradient accumulation.

BASELINE.json config 3: "BERT-large pretraining (PyTorch backend,
DistributedOptimizer + grad accumulation)" — here TPU-native: bf16 MXU
matmuls, masked-LM objective on synthetic data, grad accumulation via
``backward_passes_per_step`` (torch/optimizer.py:126 semantics), sequence
sharded optionally with ring attention for long contexts.

Run small (emulated 8-rank CPU slice):
    HVD_TPU_EMULATE_RANKS=8 python examples/bert_pretraining.py --size tiny
Run BERT-large on the chip:
    python examples/bert_pretraining.py --size large --steps 10
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if os.environ.get("HVD_TPU_EMULATE_RANKS"):
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
else:
    import jax

import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import (BERT_BASE, BERT_LARGE, Transformer,
                                TransformerConfig, lm_loss)

TINY = TransformerConfig(vocab_size=1024, num_layers=2, num_heads=8,
                         d_model=128, d_ff=256, max_len=128, causal=False,
                         dtype=jnp.float32)

MASK_ID = 103  # [MASK] in the BERT vocab


def mlm_batch(rng, batch, seq_len, vocab, mask_rate=0.15):
    tokens = rng.randint(5, vocab, size=(batch, seq_len)).astype(np.int32)
    mask = rng.rand(batch, seq_len) < mask_rate
    inputs = tokens.copy()
    inputs[mask] = MASK_ID
    return (jnp.asarray(inputs), jnp.asarray(tokens),
            jnp.asarray(mask.astype(np.float32)))


def mlm_batch_fixed_positions(rng, batch, seq_len, vocab, num_positions):
    """Exactly ``num_positions`` masked slots per sequence (standard BERT
    max_predictions_per_seq).  Returns (inputs, positions [B,K], labels
    [B,K]); the LM head runs only at the gathered positions."""
    tokens = rng.randint(5, vocab, size=(batch, seq_len)).astype(np.int32)
    positions = np.stack([
        np.sort(rng.choice(seq_len, size=num_positions, replace=False))
        for _ in range(batch)]).astype(np.int32)
    labels = np.take_along_axis(tokens, positions, axis=1)
    inputs = tokens.copy()
    np.put_along_axis(inputs, positions, MASK_ID, axis=1)
    return jnp.asarray(inputs), jnp.asarray(positions), jnp.asarray(labels)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny", choices=["tiny", "base", "large"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-per-slot", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--accum", type=int, default=2,
                    help="backward_passes_per_step (grad accumulation)")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize blocks (jax.checkpoint); only pays "
                         "off when activations would not fit HBM (long "
                         "seq / large batch) — at seq 128 it costs ~1/3 "
                         "extra forward FLOPs for nothing")
    ap.add_argument("--attention", default="auto",
                    choices=["auto", "dense", "flash"],
                    help="'flash' = Pallas kernel (fwd+bwd); 'auto' picks "
                         "flash on TPU, dense elsewhere")
    ap.add_argument("--mlm-positions", type=int, default=0,
                    help="if >0, generate exactly this many masked "
                         "positions per sequence and apply the LM head "
                         "only at them (standard BERT "
                         "max_predictions_per_seq; the head over all "
                         f"positions wastes ~6x its FLOPs at 15%% masking)")
    args = ap.parse_args(argv)

    hvd.init()
    nslots = hvd.num_slots()
    attn = args.attention
    if attn == "auto":
        attn = "flash" if jax.default_backend() == "tpu" else "dense"
    attn_impl = "flash" if attn == "flash" else None
    if args.size == "tiny":
        cfg = dataclasses.replace(TINY, attention_impl=attn_impl)
    else:
        cfg = {"base": BERT_BASE, "large": BERT_LARGE}[args.size]
        # scan_layers: ~num_layers x faster compile at identical numerics.
        cfg = dataclasses.replace(
            cfg, max_len=args.seq_len, remat=args.remat,
            attention_impl=attn_impl, scan_layers=True)
    model = Transformer(cfg)
    batch = args.batch_per_slot * nslots
    seq_len = min(args.seq_len, cfg.max_len)

    rng = np.random.RandomState(hvd.rank())
    if args.mlm_positions:
        inputs, positions, labels = mlm_batch_fixed_positions(
            rng, batch, seq_len, cfg.vocab_size, args.mlm_positions)
        targets, mask = positions, labels  # ride the same step signature
    else:
        inputs, targets, mask = mlm_batch(rng, batch, seq_len,
                                          cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), inputs[:1])
    params = hvd.broadcast_variables(params, root_rank=0)
    opt = hvd.DistributedOptimizer(
        optax.adamw(1e-4), backward_passes_per_step=args.accum,
        compression=hvd.Compression.none)
    opt_state = opt.init(params)

    def local_step(params, opt_state, inp, tgt, msk):
        def loss_fn(p):
            if args.mlm_positions:
                # tgt = positions [B,K], msk = labels [B,K]
                logits = model.apply(p, inp, predict_positions=tgt)
                return lm_loss(logits, msk.astype(jnp.int32))
            logits = model.apply(p, inp)
            return lm_loss(logits, tgt, msk)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        params2 = optax.apply_updates(params, updates)
        return params2, opt_state2, hvd.allreduce(loss, op=hvd.Average)

    step = hvd.parallel.shard_step(
        local_step, in_specs=(P(), P(), P("hvd"), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P()), donate_argnums=(0, 1),
        # Pallas *interpreter* (flash off-TPU) inlines the kernel, mixing
        # invariant loop indices with varying data; the compiled TPU path
        # needs no escape hatch (parallel/flash.py docstring).
        check_vma=not (attn == "flash"
                       and jax.default_backend() != "tpu"))

    # Keep per-step losses ON DEVICE: a float() per step is a host
    # round-trip that serializes dispatch; fetch the whole trace once at
    # the end.
    losses_dev = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, inputs, targets,
                                       mask)
        losses_dev.append(loss)
        if i == 1:
            float(loss)  # barrier after compile+first step
            t0 = time.perf_counter()
    losses = [float(l) for l in jax.device_get(losses_dev)]  # ONE transfer
    dt = max(time.perf_counter() - t0, 1e-9)
    samples_s = batch * max(args.steps - 2, 0) / dt if args.steps > 2 else 0.0
    if hvd.rank() == 0:
        print(f"mlm loss: {losses[0]:.4f} -> {losses[-1]:.4f}  "
              f"({samples_s:.1f} samples/sec, accum={args.accum})")
    if args.steps > 3:
        assert losses[-1] < losses[0], "loss did not decrease"
    return losses, samples_s


if __name__ == "__main__":
    main()
