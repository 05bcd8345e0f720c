"""``ServeMetrics.token_step_ms`` (host clock inside the engine, from one
decode step's end to the next, prefill chunks between them included):
change of its sum over change of its count over the window."""


def read(run):
    d = run.results["delta"]
    return d["step_sum_ms"] / d["step_count"] if d["step_count"] else None
