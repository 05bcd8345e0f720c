"""Device trace: time in operations traced under ``hvd::bd_attention``
(``models/sdar_moe.py``: the layer's first norm, the four projections, the
head norms and rotary embedding, the flash kernels under the
block-diffusion mask), forward, recomputed and backward, over the sum of
all operations' durations.  Absent where the program writes no such
scope."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::bd_attention")
