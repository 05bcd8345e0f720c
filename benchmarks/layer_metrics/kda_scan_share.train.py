"""Device trace: time in operations traced under
``hvd::kda_attention::scan`` (``models/kimi_linear.py``: the span is around
the call of ``parallel/kda.py: kda_scan`` and nothing else, so the
``hvd_kda_fwd`` custom calls of the forward pass and of the recomputation,
the ``hvd_kda_bwd`` ones, and the turn of ``beta`` and ``dbeta`` between
``[S, heads]`` and the kernels' rows), over the sum of all operations'
durations: the kernels apart from what surrounds them; part of
``kda_attention_share.train``'s time.  Absent where the program writes no
such scope, and without a device trace."""

from harness import scope_times

SCOPE = "hvd::kda_attention::scan"


def read(run):
    return scope_times.share_under(run, SCOPE)
