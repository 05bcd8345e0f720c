"""A per-layer metric added as a new file: the steps the window held."""


def read(run):
    return run.results["attempted"]
