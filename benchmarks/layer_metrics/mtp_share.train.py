"""Device trace: time in operations traced under ``hvd::mtp``
(``models/joyai_flash.py``, the multi-token-prediction module whole: its two
norms and ``W_eh`` on the shared embedding, its block (one further expert
layer, whose ``hvd::mla_attention`` and ``hvd::moe`` nest inside), its last
norm and the second pass through the shared head), forward, recomputed and
backward, over the sum of all operations' durations.  Absent where the
program writes no such scope, and without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::mtp")
