"""Device trace: self time of ``hvd::embed`` (``models/sdar_moe.py:
embed``: the embedding's gather, its scale and cast and, in the backward
pass, its scatter-add; ``joyai``'s look-ahead embedding for the MTP module
too) over the sum of all operations' durations (``harness/parts.py``).
Absent where the program exports no such part."""

from harness import parts


def read(run):
    return parts.share(run, "hvd::embed")
