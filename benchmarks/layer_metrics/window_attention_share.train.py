"""Device trace: time in operations traced under ``hvd::window_attention``
(``models/afmoe.py``, a ``sliding_attention`` layer's attention half: the
first norm, the five projections, the head norms, rotary embedding, the
flash kernels under the causal window, the sigmoid gate, the output
projection and the norm after it), forward, recomputed and backward, over
the sum of all operations' durations.  Absent where the program writes no
such scope, and without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::window_attention")
