"""Host plane of the trace: mean duration of the ``hvd::shard_step::*``
spans, which is what one call of the compiled step costs the host.  Absent
where the program writes no such span, or the trace holds no device plane
(the CPU rehearsal)."""

import statistics

from harness import scopes


def read(run):
    t = scopes.table(run)
    if t is None or not t["host_dispatch_ms"]:
        return None
    return statistics.fmean(t["host_dispatch_ms"])
