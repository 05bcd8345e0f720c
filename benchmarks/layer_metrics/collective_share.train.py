"""Device trace: union of the collective operations' intervals on a device
over the traced window, averaged over the chips.  With asynchronous
collectives this is the time inside the ``-start`` and ``-done`` operations,
not the time the transfer is in flight between them."""


def read(run):
    if not run.traced:
        return None
    return 100.0 * run.traced["collective_s"] / run.traced["window_s"]
