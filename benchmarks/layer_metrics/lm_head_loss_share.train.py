"""Device trace: self time of ``hvd::lm_head_loss`` (``models/sdar_moe.py:
head_loss``: the last norm, the head's product and the loss, a chunk of
positions at a time, forward, recomputed and backward; twice on ``joyai``,
whose MTP module goes through the shared head) over the sum of all
operations' durations (``harness/parts.py``).  Absent where the program
exports no such part."""

from harness import parts


def read(run):
    return parts.share(run, "hvd::lm_head_loss")
