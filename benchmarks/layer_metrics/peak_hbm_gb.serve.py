"""``device.memory_stats()`` of the fullest chip after the window, in GB
(1e9 bytes): ``peak_bytes_in_use`` plus ``peak_bytes_reserved``, the live
arrays and the temporaries of the programs that ran
(``harness/result.py: memory_peak_bytes``).  Absent where the backend
keeps none."""


def read(run):
    peak = run.results["memory_peak_bytes"]
    return peak / 1e9 if peak else None
