"""Rehearsal only: the instructions of the compiled step under
``hvd::optimizer``, as ``harness/scopes.py`` reads them from its text; the
instructions by phase and the scopes found go into the log."""

import collections

from harness import result, scopes


def read(run):
    names = scopes.op_names(scopes.hlo_text(run))
    phases = collections.Counter(
        scopes.classify(op_name)["phase"] for op_name in names.values())
    found = sorted({step for op_name in filter(None, names.values())
                    for step in op_name.split("/")
                    if step.startswith(scopes.SPAN)
                    or step in scopes.PARTS})
    result.log(f"scopes: instructions by phase {dict(phases)}")
    result.log(f"scopes: found {' '.join(found)}")
    scopes.log_all_reduces(run)
    return phases["optimizer"]
