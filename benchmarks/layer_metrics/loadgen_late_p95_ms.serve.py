"""The benchmark's own client: 95th percentile, over requests due in the
window, of the instant a request was sent minus the instant it was due.  A
starved generator must not be read as a fast server."""


def read(run):
    return run.results["loadgen_late_p95_ms"]
