"""Gradient all-reduces of the compiled step's text that run asynchronously:
an ``all-reduce-start``, or the ``all-reduce`` in the start half of an
``async_collective_fusion`` (``harness/async_collectives.py`` has the
form).  A counter that says the step wrapper's mechanism engages, not a
goal: 0 where every gradient all-reduce is synchronous (the parent's
program, one device, the CPU), the number of gradient leaves that cross
alone where it does; on the v5e more of them measured slower, not faster
(PERF.md, PR 26), and what they are worth is
``grad_allreduce_exposed_ms.train``.  It repeats exactly."""

from harness import async_collectives


def read(run):
    return sum(1 for r in async_collectives.table(run)["found"]
               if r.scope == async_collectives.GRADIENTS and r.start)
