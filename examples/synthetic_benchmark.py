"""User-facing synthetic training benchmark.

Reference analog: examples/pytorch/pytorch_synthetic_benchmark.py and
examples/tensorflow2/tensorflow2_synthetic_benchmark.py:25-80 — the
reference's headline harness: a standard model on synthetic data, full
training steps through DistributedOptimizer, images/sec printed.

(The driver-facing single-JSON-line variant lives at the repo root as
bench.py; this is the argparse'd example users run.)

Usage::

    python examples/synthetic_benchmark.py --model resnet50 --batch-size 128
    python examples/synthetic_benchmark.py --model mlp --num-iters 50
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(model_name: str, batch_per_slot: int, sync_bn: bool = True):
    """The benchmark's training job over the initialized ``hvd`` world:
    returns ``(step, state, batch)`` with ``state = (params, batch_stats,
    opt_state)`` replicated, ``batch = (images, labels)`` seeded, global
    (``batch_per_slot`` a slot) and placed split over the mesh axis, and
    ``step(*state, *batch) -> (*state, loss)`` the jitted ``shard_step``
    (``DistributedOptimizer`` inside, state donated)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    batch = batch_per_slot * hvd.num_slots()
    if model_name == "mlp":
        from horovod_tpu.models import create_mlp
        model = create_mlp((1024, 1024, 1000))
        images = np.random.RandomState(0).rand(batch, 784)
    else:
        from horovod_tpu.models import ResNet50, ResNet101
        cls = ResNet50 if model_name == "resnet50" else ResNet101
        model = cls(num_classes=1000, dtype=jnp.bfloat16,
                    axis_name="hvd" if sync_bn else None)
        images = np.random.RandomState(0).rand(batch, 224, 224, 3)
    labels = np.random.RandomState(1).randint(0, 1000, size=(batch,))
    images, labels = jax.device_put(
        (images.astype(np.float32), labels),
        hvd.parallel.data_parallel_sharding())

    has_bn = model_name != "mlp"
    variables = model.init(jax.random.PRNGKey(0), images[:2],
                           **({"train": False} if has_bn else {}))
    params = variables["params"] if "params" in variables else variables
    batch_stats = variables.get("batch_stats") if has_bn else None
    opt = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    # Replicated over the mesh from the start, as the step returns it: the
    # second call then runs the program the first one compiled.
    state = jax.device_put((params, batch_stats, opt.init(params)),
                           hvd.parallel.replicated_sharding())

    def local_step(params, batch_stats, opt_state, xb, yb):
        def loss_fn(p):
            if has_bn:
                logits, mut = model.apply(
                    {"params": p, "batch_stats": batch_stats}, xb,
                    train=True, mutable=["batch_stats"])
                new_stats = mut["batch_stats"]
            else:
                logits, new_stats = model.apply({"params": p}, xb), None
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, new_stats

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        loss = hvd.allreduce(loss, op=hvd.Average)  # metric averaging
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    step = hvd.shard_step(
        local_step,
        in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P(), P()),
        donate_argnums=(0, 1, 2))
    return step, state, (images, labels)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "mlp"])
    p.add_argument("--batch-size", type=int, default=128,
                   help="per-slot batch size")
    p.add_argument("--num-warmup-batches", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=30)
    p.add_argument("--no-sync-bn", action="store_true")
    args = p.parse_args(argv)

    import jax

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.num_slots()
    step, state, batch = build(args.model, args.batch_size,
                               sync_bn=not args.no_sync_bn)

    for _ in range(args.num_warmup_batches):
        *state, loss = step(*state, *batch)
    warm_loss = float(loss)  # host sync: every warm-up step has executed

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        *state, loss = step(*state, *batch)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    img_s = batch[0].shape[0] * args.num_iters / dt
    if hvd.rank() == 0:
        device = jax.devices()[0]
        print(f"Model: {args.model}, batch {args.batch_size}/slot, "
              f"{n} slot(s), platform {device.platform} "
              f"({device.device_kind})")
        print(f"Loss after warm-up: {warm_loss:.6f}, "
              f"after {args.num_iters} more steps: {final_loss:.6f}")
        print(f"Img/sec total: {img_s:.1f}  (per slot: {img_s / n:.1f})")
    return img_s


if __name__ == "__main__":
    main()
