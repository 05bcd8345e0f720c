"""The ResNet training job and its plain reference.

The program's side is ``examples/synthetic_benchmark.py: build()`` copied
(upstream Horovod's ``tensorflow2_synthetic_benchmark.py``): the model of
``horovod_tpu/models/resnet.py`` with sync-BN over the ``hvd`` axis, SGD
with momentum through ``hvd.DistributedOptimizer`` inside
``hvd.shard_step``, state donated, one batch that lives on the device.
Two changes: weights, images and labels come from the seed (``build()``
fixes them at 0), and ``model.init`` runs under ``jax.jit`` (eager it is
206 small programs and 56 s cold, PERF.md).

The reference is what Horovod promises to equal: the same loss under
``jax.value_and_grad`` and bare ``optax.sgd`` in one ``jax.jit`` on one
device, no ``hvd`` call, the model built with ``axis_name=None``.  It
shares ``models/resnet.py`` with the program (PERF.md, Open questions).
"""

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P


def make_model(config: dict, axis_name):
    from horovod_tpu.models.resnet import ResNet
    return ResNet(stage_sizes=list(config["stage_sizes"]),
                  num_classes=config["num_classes"],
                  num_filters=config["num_filters"],
                  dtype=jnp.dtype(config["compute_dtype"]),
                  axis_name=axis_name)


def make_optimizer(config: dict):
    return optax.sgd(config["learning_rate"], momentum=config["momentum"])


def seeded_batch(config: dict, seed: int, batch: int):
    """``(images, labels)`` on the default device, from the seed."""
    size = config["image_size"]

    @jax.jit
    def make(key):
        k_img, k_lab = jax.random.split(key)
        images = jax.random.uniform(k_img, (batch, size, size, 3),
                                    jnp.float32)
        labels = jax.random.randint(k_lab, (batch,), 0,
                                    config["num_classes"], jnp.int32)
        return images, labels

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), 1))


def seeded_variables(config: dict, seed: int):
    """``(params, batch_stats)`` from the seed, in one jitted call."""
    model = make_model(config, None)
    size = config["image_size"]
    variables = jax.jit(lambda key: model.init(
        key, jnp.zeros((2, size, size, 3), jnp.float32), train=False))(
            jax.random.PRNGKey(seed))
    return variables["params"], variables["batch_stats"]


def loss_and_stats(model, params, batch_stats, images, labels):
    logits, mutated = model.apply(
        {"params": params, "batch_stats": batch_stats}, images,
        train=True, mutable=["batch_stats"])
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()
    return loss, mutated["batch_stats"]


class Program:
    """The system under test: ``step(*state, *batch) -> (*state, loss)``
    over the initialised ``hvd`` world, ``images_per_chip`` a slot."""

    def __init__(self, config: dict, images_per_chip: int, seed: int):
        import horovod_tpu as hvd
        self.config, self.seed = config, seed
        self.global_batch = images_per_chip * hvd.num_slots()
        self.batch = jax.device_put(
            seeded_batch(config, seed, self.global_batch),
            hvd.parallel.data_parallel_sharding())
        model = make_model(config, "hvd")
        self.optimizer = opt = hvd.DistributedOptimizer(
            make_optimizer(config))

        def local_step(params, batch_stats, opt_state, images, labels):
            (loss, new_stats), grads = jax.value_and_grad(
                lambda p: loss_and_stats(model, p, batch_stats, images,
                                         labels), has_aux=True)(params)
            loss = hvd.allreduce(loss, op=hvd.Average)
            updates, opt_state = opt.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_stats,
                    opt_state, loss)

        self.step = hvd.shard_step(
            local_step,
            in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P(), P()),
            donate_argnums=(0, 1, 2))

    def fresh_state(self):
        """The seeded state, replicated over the mesh as the step returns
        it (so the second call runs the program the first compiled)."""
        import horovod_tpu as hvd
        params, batch_stats = seeded_variables(self.config, self.seed)
        return jax.device_put(
            (params, batch_stats, self.optimizer.init(params)),
            hvd.parallel.replicated_sharding())

    def hlo_text(self, state) -> str:
        """The compiled step as text, for counting collectives."""
        return self.step.lower(*state, *self.batch).compile().as_text()


def reference_losses(config: dict, seed: int, global_batch: int,
                     steps: int):
    """Losses of ``steps`` plain steps from the seeded state on the seeded
    batch of ``global_batch`` images, all on one device."""
    model = make_model(config, None)
    opt = make_optimizer(config)
    images, labels = seeded_batch(config, seed, global_batch)
    params, batch_stats = seeded_variables(config, seed)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            lambda p: loss_and_stats(model, p, batch_stats, images, labels),
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_stats, opt_state,
                loss)

    losses = []
    for _ in range(steps):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
        losses.append(float(loss))
    return losses
