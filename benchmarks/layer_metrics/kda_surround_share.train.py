"""Device trace: time in operations traced under
``hvd::kda_attention::conv``, ``hvd::kda_attention::gates`` and
``hvd::kda_attention::out`` (``models/kimi_linear.py``: what lies between a
KDA layer's projections and its scan, the three short convolutions with
SiLU, the L2 norms of ``q`` and ``k``, the decay ``g`` and ``beta``, and
between the scan and the residual sum, the gated norm and the product with
``W_o``), forward, recomputed and backward, over the sum of all operations'
durations.  The three spans are siblings under ``hvd::kda_attention``, so
no time is counted twice; whether a span holds Pallas kernels
(``parallel/kda_surround.py``) or plain fusions, it reads the same way: a
before and after.  Part of ``kda_attention_share.train``'s time, beside
``kda_scan_share.train``'s.  Absent where the program writes none of the
three scopes, and without a device trace."""

from harness import scope_times

SCOPES = ("hvd::kda_attention::conv", "hvd::kda_attention::gates",
          "hvd::kda_attention::out")


def read(run):
    shares = [scope_times.share_under(run, scope) for scope in SCOPES]
    found = [share for share in shares if share is not None]
    return sum(found) if found else None
