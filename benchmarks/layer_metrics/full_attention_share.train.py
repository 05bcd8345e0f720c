"""Device trace: time in operations traced under ``hvd::full_attention``
(``models/afmoe.py``, a ``full_attention`` layer's attention half: as
``window_attention_share.train`` but no rotary embedding, and the flash
kernels under the plain causal mask), forward, recomputed and backward,
over the sum of all operations' durations.  Absent where the program writes
no such scope, and without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::full_attention")
