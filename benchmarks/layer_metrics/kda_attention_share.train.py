"""Device trace: time in operations traced under ``hvd::kda_attention``
(``models/kimi_linear.py``, a layer's Kimi Delta Attention half from its
first norm to the residual sum: the three projections, the two low-rank
pairs and ``W_b``, the three short convolutions with SiLU, the gates and the
L2 norms, the scan kernels, the gated norm and ``W_o``), forward, recomputed
and backward, over the sum of all operations' durations; the log has
``::project``, ``::conv``, ``::gates``, ``::scan`` and ``::out`` apart.
Absent where the program writes no such scope, and without a device
trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::kda_attention")
