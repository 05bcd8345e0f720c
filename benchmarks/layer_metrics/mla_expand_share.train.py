"""Device trace: time in operations traced under
``hvd::mla_attention::expand`` (``models/joyai_flash.py``: ``W_qb`` and
``W_kvb`` from the latents up to 32 heads of queries, keys and values,
rotary embedding on the rotary parts, and the one rotary key written beside
every head's other 128 key dimensions), forward, recomputed and backward,
over the sum of all operations' durations: what the latent costs in
training, where no cache is spared; part of ``mla_attention_share.train``'s
time.  Absent where the program writes no such scope, and without a device
trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::mla_attention::expand")
