"""Device trace: time in operations traced under ``hvd::moe``
(``parallel/moe.py: dropless_expert_ffn`` and the norm before it: router
and top-k, the sorts that give every pair its slot, gather, the three
grouped products, combine), forward, recomputed and backward, over the sum
of all operations' durations; the log has ``hvd::moe::route``,
``::experts`` and ``::combine`` apart.  Absent where the program writes no
such scope."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::moe")
