"""Percentile arithmetic.  Nearest rank, and every value goes with the
number of samples it was taken from."""

import math


def percentile(values, q: float):
    """``(value, n)``: the nearest-rank ``q``-th percentile (0 < q <= 100)
    of ``values`` and their count; ``(None, 0)`` of nothing."""
    ordered = sorted(values)
    n = len(ordered)
    if not n:
        return None, 0
    rank = max(1, math.ceil(q / 100.0 * n))
    return ordered[rank - 1], n


def summary(values) -> dict:
    """Median, p95 (nearest rank), mean and the sample count, for the
    lines printed before the last one."""
    n = len(values)
    if not n:
        return {"n": 0}
    return {"n": n, "p50": percentile(values, 50)[0],
            "p95": percentile(values, 95)[0],
            "mean": sum(values) / n, "max": max(values)}
