"""GPT-2 language modeling with Adasum reduction.

BASELINE.json config 4: "GPT-2 medium with Adasum (examples/adasum, torch
backend)".  Adasum (ops/adasum.py — butterfly ppermute tree with the
orthogonal-projection-corrected pairwise combine, adasum.h:396-409) adapts
between summing and averaging per tensor, letting the learning rate stay
fixed as the world grows.

Run small (emulated 8-rank CPU slice):
    HVD_TPU_EMULATE_RANKS=8 python examples/gpt2_adasum.py --size tiny
GPT-2 medium on the chip:
    python examples/gpt2_adasum.py --size medium --steps 10
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
from horovod_tpu.models import Transformer, TransformerConfig, create_gpt2, \
    lm_loss

TINY = TransformerConfig(vocab_size=512, num_layers=2, num_heads=8,
                         d_model=128, d_ff=256, max_len=128, causal=True,
                         dtype=jnp.float32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny",
                    choices=["tiny", "small", "medium", "large"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-per-slot", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    args = ap.parse_args(argv)

    hvd.init()
    nslots = hvd.num_slots()
    # scan_layers (factory default): ~num_layers x faster compile.
    # Adasum's per-tensor coefficient granularity (adasum.h:396-409)
    # survives the stacked [L, ...] layout via per_layer_stacked below:
    # the scanned blocks get one coefficient pair PER LAYER SLICE, exactly
    # what the unrolled layout computed.
    model = Transformer(TINY) if args.size == "tiny" else \
        create_gpt2(args.size, remat=True)
    cfg = model.cfg
    batch = args.batch_per_slot * nslots
    seq_len = min(args.seq_len, cfg.max_len)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, cfg.vocab_size, size=(batch, seq_len))
        .astype(np.int32))
    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    params = hvd.broadcast_variables(params, root_rank=0)
    # Adasum path: reduce post-optimizer deltas (the reference's
    # _DistributedAdasumOptimizer contract, torch/optimizer.py:345).
    opt = optax.sgd(0.05)
    opt_state = opt.init(params)

    def _stacked_layer_leaf(path):
        # The scanned model's "blocks" subtree stacks per-layer params on
        # axis 0; per-slice Adasum keeps reference granularity there.
        return any(getattr(p, "key", None) == "blocks" for p in path)

    def local_step(params, opt_state, toks):
        def loss_fn(p):
            logits = model.apply(p, toks)
            return lm_loss(logits[:, :-1], toks[:, 1:])
        # LOCAL grads: Adasum adapts from per-rank gradient divergence.
        loss, grads = hvd.local_value_and_grad(loss_fn)(params)
        new_params, opt_state2 = hvd.adasum_delta_step(
            opt, params, grads, opt_state,
            per_layer_stacked=_stacked_layer_leaf if cfg.scan_layers
            else None)
        return new_params, opt_state2, hvd.allreduce(loss, op=hvd.Average)

    step = hvd.parallel.shard_step(
        local_step, in_specs=(P(), P(), P("hvd")),
        out_specs=(P(), P(), P()), donate_argnums=(0, 1),
        check_vma=False)  # Adasum butterfly output: equal but typed varying

    losses = []
    t0 = time.perf_counter()
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
        if i == 1:
            t0 = time.perf_counter()
    dt = max(time.perf_counter() - t0, 1e-9)
    samples_s = batch * max(args.steps - 2, 0) / dt if args.steps > 2 else 0.0
    if hvd.rank() == 0:
        print(f"lm loss: {losses[0]:.4f} -> {losses[-1]:.4f}  "
              f"({samples_s:.1f} samples/sec, Adasum)")
    if args.steps > 3:
        assert losses[-1] < losses[0], "loss did not decrease"
    return losses, samples_s


if __name__ == "__main__":
    main()
