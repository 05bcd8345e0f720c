"""Device trace: time in operations of the forward pass (``jvp(`` in the
``op_name``, no ``transpose(``, not under ``hvd::optimizer``) over the sum
of all operations' durations, averaged over the chips.  A fusion counts
whole for its root's ``op_name`` (``harness/scopes.py``).  This reader also
writes the whole table the scope metrics are cut from into the log."""

from harness import scopes


def read(run):
    scopes.log_table(run)
    return scopes.share(run, lambda t: t["by_phase"]["forward"])
