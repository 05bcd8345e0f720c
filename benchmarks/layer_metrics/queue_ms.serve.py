"""``ServeMetrics`` stage ``queue`` (submitted to admitted, per request
completed in the window): change of its sum over change of its count."""


def read(run):
    d = run.results["delta"]
    return d["queue_sum_ms"] / d["queue_count"] if d["queue_count"] else None
