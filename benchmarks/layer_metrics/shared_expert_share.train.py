"""Device trace: time in operations traced under ``hvd::moe::shared``
(``models/afmoe.py``: the shared expert's three plain bf16 products and its
gated unit, which every chip of the deployment computes alike), forward,
recomputed and backward, over the sum of all operations' durations; part of
``moe_share.train``'s time.  Absent where the program writes no such scope,
and without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::moe::shared")
