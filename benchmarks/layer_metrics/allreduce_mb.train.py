"""Bytes of every ``all-reduce`` and ``all-reduce-start`` of the compiled
step's text, in MB (1e6 bytes) a step and chip.  A count: it repeats
exactly and reads the same on the CPU."""

from harness import scopes


def read(run):
    return sum(nbytes for _, _, nbytes in
               scopes.all_reduces(scopes.hlo_text(run))) / 1e6
