"""Self-hvdmem regression gate: the repo must stay hvdmem-clean.

The analog of tests/test_lint_self.py / test_race_self.py for the HBM
donation analysis (analysis/memplan.py): runs ``--mem`` over
``horovod_tpu/`` + ``examples/`` in-process and fails on ANY unsuppressed
HVD3xx finding — a new donated-then-used cache read (the PR 4 hazard
class) or an undonated functionally-updated jit arg fails tier-1 before
it can OOM or crash a serving fleet.

To silence a deliberate pattern, add ``# hvdlint: disable=HVD30x`` on
the flagged line WITH a reasoned comment (docs/static_analysis.md).
"""

import glob
import os

from horovod_tpu.analysis import mem_paths, unsuppressed
from horovod_tpu.analysis.cli import main as cli_main

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PATHS = [os.path.join(_REPO, "horovod_tpu"),
          os.path.join(_REPO, "examples")]


def test_repo_is_hvdmem_clean():
    findings = mem_paths(_PATHS)
    active = unsuppressed(findings)
    assert not active, (
        "hvdmem found HBM donation hazards — fix them (rebind the "
        "donated name / add donate_argnums) or suppress each with a "
        "reasoned '# hvdlint: disable=...' comment:\n"
        + "\n".join(f.format() for f in active))


def test_mem_suppressions_are_auditable():
    """Every suppressed hvdmem finding still surfaces with
    suppressed=True — the audit trail the dogfooding satellite
    requires."""
    for f in mem_paths(_PATHS):
        assert f.suppressed, f.format()


def test_mem_walk_covers_the_donating_tree():
    """Guard the gate itself: the walk must actually reach the donation-
    heavy subsystems — zero findings would mean nothing if the walker
    silently skipped the serve engine (five donated jit programs) or the
    analyzer's own modules."""
    from horovod_tpu.analysis.linter import iter_python_files
    files = iter_python_files(_PATHS)
    assert len(files) > 50
    missing = set(glob.glob(os.path.join(_REPO, "horovod_tpu", "**", "*.py"),
                            recursive=True)) - set(files)
    assert not missing, f"not analyzed: {sorted(missing)}"
    assert not any("__pycache__" in f for f in files)


def test_mem_dogfood_cli_exits_zero(capsys):
    """The acceptance command, through the registry dispatch:
    python -m horovod_tpu.analysis --mem horovod_tpu examples."""
    rc = cli_main(["--mem"] + _PATHS)
    capsys.readouterr()
    assert rc == 0
