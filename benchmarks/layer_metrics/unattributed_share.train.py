"""Device trace: time in operations that no part of the program owns, after
the def-use rules have placed what the compiler made (``harness/parts.py``:
every operation but ``while`` / ``conditional`` / ``call`` has exactly one
owner; what is left is the caller's own code, "named, no part", and
instructions without a name that no rule reaches), over the sum of all
operations' durations.  The remainder of the partition: the parts' self
times and this sum to 100 %.  Absent where the program exports no parts."""

from harness import parts


def read(run):
    return parts.share(run, parts.UNATTRIBUTED)
