"""Programs the backend compiled, or loaded from the persistent cache,
between the start and the end of the measured window (a ``jax.monitoring``
listener, ``harness/compiles.py``).  Should be 0."""


def read(run):
    return run.results["compiles_in_window"]
