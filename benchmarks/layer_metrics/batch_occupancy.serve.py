"""Sequences in a decode step, mean over the window's steps:
``ServeMetrics`` occupancy sum over samples, each as its change."""


def read(run):
    d = run.results["delta"]
    return (d["occupancy_sum"] / d["occupancy_samples"]
            if d["occupancy_samples"] else None)
