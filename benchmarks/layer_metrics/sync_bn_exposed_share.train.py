"""Device trace: as ``exposed_collective_share.train``, over sync batch
norm's collectives alone: the statistics' all-reduce under
``hvd::sync_bn_stats`` and the backward pass's all-reduce under
``hvd::batch_norm`` (``harness/scopes.py: collective_scope``).  Absent
where the program writes no such scope."""

from harness import scopes


def read(run):
    return scopes.share(run, lambda t: t["exposed_s"].get("sync_bn", 0.0),
                        needs="hvd::sync_bn_stats", of="window_s")
