"""Device trace: time in operations traced under ``hvd::mla_attention``
(``models/joyai_flash.py``, a layer's latent-attention half from its first
norm to the residual sum: the two low-rank projections down with a norm on
each latent, the two back up, rotary embedding on the rotary parts, the one
rotary key spread over the heads, the flash kernels with keys wider than
values, the output projection), the stack's layers and the MTP module's
block alike, forward, recomputed and backward, over the sum of all
operations' durations; the log has ``::compress``, ``::expand`` and
``::out`` apart.  Absent where the program writes no such scope, and
without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::mla_attention")
