"""Self-lint regression gate: the repo must stay hvdlint-clean.

Runs the AST linter in-process over ``horovod_tpu/`` and ``examples/``
(the same paths the dogfooding command ``python -m horovod_tpu.analysis
horovod_tpu examples`` covers) and fails on ANY unsuppressed finding —
so a new rank-guarded collective, swallowed-collective try/except,
unseeded-randomness-in-traced-code, etc. anywhere in the framework or
its examples fails tier-1 instead of wedging a job at runtime.

To silence a deliberate pattern, add ``# hvdlint: disable=HVDxxx`` on
the flagged line WITH a reasoned comment (docs/static_analysis.md).
"""

import glob
import os

from horovod_tpu.analysis import lint_paths, unsuppressed

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LINT_PATHS = [os.path.join(_REPO, "horovod_tpu"),
               os.path.join(_REPO, "examples")]


def test_repo_is_hvdlint_clean():
    findings = lint_paths(_LINT_PATHS)
    active = unsuppressed(findings)
    assert not active, (
        "hvdlint found new distributed-correctness antipatterns — fix "
        "them or suppress each with a reasoned '# hvdlint: disable=...' "
        "comment:\n" + "\n".join(f.format() for f in active))


def test_lint_covers_the_whole_tree():
    """Guard the gate itself: if path walking ever silently breaks (e.g.
    an overzealous skip list), this fails before a regression can hide."""
    from horovod_tpu.analysis import iter_python_files
    files = iter_python_files(_LINT_PATHS)
    # The seed tree has ~90 framework files + 8 examples; a collapse of
    # the walker to a handful of files must trip this.
    assert len(files) > 50
    assert any(f.endswith("optimizer.py") for f in files)
    assert any(f.endswith("mnist_mlp.py") for f in files)
    # Every module of the package, found by a walk of its own: a
    # skip-list regression would let a subsystem land unlinted.
    missing = set(glob.glob(os.path.join(_REPO, "horovod_tpu", "**", "*.py"),
                            recursive=True)) - set(files)
    assert not missing, f"not linted: {sorted(missing)}"
    assert not any("__pycache__" in f for f in files)


def test_suppressions_are_auditable():
    """Every suppressed finding in the repo still surfaces with
    suppressed=True — the audit trail the dogfooding satellite requires."""
    findings = lint_paths(_LINT_PATHS)
    for f in findings:
        assert f.suppressed, f.format()
