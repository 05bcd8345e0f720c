"""Device trace: milliseconds of a step, on one chip, that the gradients'
all-reduces hold the core with nothing beside them: the synchronous ones'
whole durations, and the start and done pieces of the asynchronous ones
(``harness/async_collectives.py``); what is in flight between two pieces
hides behind compute and is left out.  This is what hiding the gradients'
all-reduce is to shorten; the parent's program, whose two gradient
all-reduces are synchronous, reads it too."""

from harness import async_collectives


def read(run):
    t = async_collectives.table(run)
    e = t["by_scope"].get(async_collectives.GRADIENTS)
    if not e or not t["steps"]:
        return None
    return 1e-6 * (e["synchronous_ns"] + e["pieces_ns"]) / t["steps"]
