"""Device trace: time in operations traced under ``hvd::optimizer``
(``DistributedOptimizer``'s update: gradient rescaling and the wrapped
optimizer) over the sum of all operations' durations.  Absent where the
program writes no such scope.  It is the time the optimizer runs in kernels
of its own: where no all-reduce stands between them (one chip), XLA fuses a
kernel's update into its weight-gradient convolution, which counts as
backward.  A fusion that ends in the job's own ``optax.apply_updates`` add,
outside every scope, counts for the optimizer's instruction before it."""

from harness import scopes


def read(run):
    return scopes.share(run, lambda t: t["by_phase"]["optimizer"],
                        needs="hvd::optimizer")
