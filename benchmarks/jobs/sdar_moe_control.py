"""The control of ``jobs/sdar_moe.py``'s check: must come out NOT correct.

In the program's place stands the plain reference itself, computed in the
nearest precision below the one the configuration states
(``correct.control_dtype``): every matrix of the tree is rounded to it
before each forward and backward pass, the products, the master weights
and AdamW stay in float32.  A configuration that names this job runs
through ``runners/train.py`` like the cell (``benchmarks/tests/control.py``
makes one from a cell's own files); the run's last line has ``correct``
false where the limits of ``correct`` tell the stated precision from the
one below, which is what they are for, and true where they cannot.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from harness import manifest as mf

job = mf.load_module("jobs", "sdar_moe")
reference_losses = job.reference_losses


class Program:
    """``jobs/sdar_moe.py: Program``'s interface over the reference's own
    steps with rounded matrices, on one device."""

    def __init__(self, config: dict, images_per_chip: int, seed: int):
        self.config, self.seed = config, seed
        self.global_batch = images_per_chip
        self.batch = job.seeded_batch(config, seed, images_per_chip)
        self.reference = job.ReferenceSteps(config, images_per_chip)
        self.first = None
        dtype = jnp.dtype(config["correct"]["control_dtype"])
        # Op by op, not under one ``jit``: compiled together, the TPU
        # compiler takes a conversion down and back up for excess
        # precision it may keep, and rounds nothing.
        self.rounded = functools.partial(
            jax.tree_util.tree_map,
            lambda a: a.astype(dtype).astype(a.dtype) if a.ndim > 1 else a)
        opt = self.optimizer = job.make_optimizer(config)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def update(params, opt_state, grads):
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        self.update = update

    def fresh_state(self):
        params = job.unstacked(job.seeded_params(self.config, self.seed))
        return params, self.optimizer.init(params)

    def step(self, params, opt_state, *batch):
        loss, grads, chosen = self.reference.loss_and_grads(
            self.rounded(params), *batch)
        if self.first is None:
            z = job.sizes(self.config)
            here = (chosen >= z["first"]) & (chosen < z["first"] + z["held"])
            self.first = job._first_steps[self.seed, self.global_batch] = \
                job.FirstStep(here.sum(axis=(1, 2)), chosen,
                              jax.tree_util.tree_map(np.asarray, grads))
        return (*self.update(params, opt_state, grads), loss)

    def hlo_text(self, state) -> str:
        return ""
