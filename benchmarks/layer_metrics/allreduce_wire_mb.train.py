"""Bytes of the all-reduces the compiled step runs, each counted once, in
MB (1e6 bytes) a step and chip: those of the entry computation and those
in the start half of an asynchronous fusion
(``harness/async_collectives.py``).  ``allreduce_mb.train`` counts an
asynchronous one once per piece of its fusion; on a step whose all-reduces
are all synchronous the two read the same.  A count: it repeats exactly
and reads the same on the CPU."""

from harness import async_collectives


def read(run):
    return sum(r.nbytes
               for r in async_collectives.table(run)["found"]) / 1e6
