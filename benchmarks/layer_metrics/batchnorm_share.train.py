"""Device trace: time in operations whose own ``op_name`` is under
``hvd::batch_norm``, forward and backward, sync batch norm's all-reduces
among them, over the sum of all operations' durations.  Absent where the
program writes no such scope.  It is the time batch norm runs in kernels of
its own: XLA fuses most of its reductions and elementwise chains into the
convolution fusions, whose ``op_name`` is the convolution's (the logged
table has the time in operations that *hold* a batch-norm instruction)."""

from harness import scopes


def read(run):
    return scopes.share(run, lambda t: t["batch_norm_s"],
                        needs="hvd::batch_norm")
