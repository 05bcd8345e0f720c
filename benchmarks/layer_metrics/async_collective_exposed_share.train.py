"""Device trace: the time in the start and done pieces of every
asynchronous collective fusion that holds an all-reduce (the gradients'
and sync batch norm's alike), over the traced window, averaged over the
chips.  The pieces run on the core with nothing beside them and have
opcode ``fusion``, so ``exposed_collective_share.train`` and
``collective_share.train``, which find collectives by opcode and by name,
leave them out: this is the part to add to either.  Absent where the step
has no such fusion (the parent's program)."""

from harness import async_collectives


def read(run):
    t = async_collectives.table(run)
    if not t["device_window_ns"] or not any(r.start for r in t["found"]):
        return None
    return 100.0 * sum(e["pieces_ns"] for e in t["by_scope"].values()) \
        / t["device_window_ns"]
