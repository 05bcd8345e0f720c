"""The control of ``jobs/kimi_linear.py``'s check: must come out NOT
correct.

As ``jobs/afmoe_control.py``: in the program's place stands the plain
reference itself, computed in the nearest precision below the one the
configuration states (``correct.control_dtype``): every matrix of the tree
is rounded to it before each forward and backward pass; the products, the
master weights and AdamW stay in float32.  ``benchmarks/tests/control.py``
makes the control's configuration and cell from the cell's own files.

Memory: AdamW's 4.8 GB of moments do not fit beside the parameters, their
rounded copy, the gradients and the reference's temporaries.  The control
applies AdamW's FIRST update a leaf at a time (``jobs/afmoe.py:
update_by_leaf``, which keeps no moments) and no later one: with
``correct.steps`` 2 the state after the second step is never read.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import manifest as mf

job = mf.load_module("jobs", "kimi_linear")
reference_losses = job.reference_losses


class Program:
    """``jobs/kimi_linear.py: Program``'s interface over the reference's
    own steps with rounded matrices, on one device."""

    def __init__(self, config: dict, images_per_chip: int, seed: int):
        self.config, self.seed = config, seed
        self.global_batch = images_per_chip
        self.batch = job.seeded_batch(config, seed, images_per_chip)
        self.reference = job.ReferenceSteps(config, images_per_chip)
        self.first = None
        dtype = jnp.dtype(config["correct"]["control_dtype"])
        # Op by op, not under one ``jit`` (jobs/sdar_moe_control.py: the
        # TPU compiler may keep a conversion's excess precision).
        self.rounded = functools.partial(
            jax.tree_util.tree_map,
            lambda a: a.astype(dtype).astype(a.dtype) if a.ndim > 1 else a)
        self.optimizer = job.make_optimizer(config)
        if config["correct"]["steps"] > 2:
            raise ValueError("the control keeps no optimizer state past "
                             "AdamW's first step: correct.steps is 1 or 2")

    def fresh_state(self):
        """``(parameters, updates applied)``."""
        return job.unstacked(job.seeded_params(self.config, self.seed)), 0

    def step(self, params, updates, *batch):
        loss, grads, chosen = self.reference.loss_and_grads(
            self.rounded(params), *batch)
        if self.first is None:
            z = job.sizes(self.config)
            here = (chosen >= z["first"]) & (chosen < z["first"] + z["held"])
            self.first = job._first_steps[self.seed, self.global_batch] = \
                job.FirstStep(here.sum(axis=(1, 2)), chosen,
                              jax.tree_util.tree_map(np.asarray, grads))
        if updates == 0:
            params = job.update_by_leaf(self.optimizer, params, grads)
        return params, updates + 1, loss

    def hlo_text(self, state) -> str:
        return ""
