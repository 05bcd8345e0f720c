"""Device trace: 1 - (union of all device operations' intervals) / traced
window, averaged over the chips used.  Absent where no device was traced."""


def read(run):
    if not run.traced:
        return None
    return 100.0 * (1.0 - run.traced["busy_s"] / run.traced["window_s"])
