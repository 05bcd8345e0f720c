"""Device trace: time in ``copy-start``, ``copy-done`` (and their sliced
form, ``slice-start`` / ``slice-done``) and ``copy`` instructions without
source metadata (the copies XLA's memory-space assignment and layout
assignment put in) over the sum of all operations' durations, whatever
part ``harness/parts.py`` placed them in; the log has the fifteen longest
with shape, bytes, memory spaces and the consumer each feeds.  Absent where
the program exports no parts."""

from harness import parts


def read(run):
    return parts.copy_share(run)
