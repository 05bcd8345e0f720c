"""Device trace: time in operations of the backward pass (``transpose(jvp(``
in the ``op_name``, the gradients' and sync batch norm's all-reduces among
them) over the sum of all operations' durations, averaged over the
chips."""

from harness import scopes


def read(run):
    return scopes.share(run, lambda t: t["by_phase"]["backward"])
