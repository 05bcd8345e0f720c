"""Device trace: time in operations traced under ``hvd::dense_mlp``
(``models/afmoe.py``: a leading dense layer's second half, the norm before,
the three plain bf16 products of width ``intermediate_size`` with their
gated unit, the norm after), forward, recomputed and backward, over the sum
of all operations' durations.  Absent where the program writes no such
scope, and without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::dense_mlp")
