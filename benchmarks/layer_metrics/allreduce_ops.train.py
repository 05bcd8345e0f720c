"""``all-reduce`` and ``all-reduce-start`` operations in the compiled step's
HLO text, after XLA's combining (as ``chip_smoke.py`` counts them).  A
count: it repeats exactly."""

import re


def read(run):
    return len(re.findall(r"\ball-reduce(?:-start)?\(",
                          run.results["hlo_text"]()))
