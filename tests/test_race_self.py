"""Self-race-analysis regression gate: the repo must stay hvdrace-clean.

The analog of tests/test_lint_self.py for the lock-order &
thread-lifecycle analysis (analysis/lockgraph.py): runs ``--race`` over
``horovod_tpu/`` + ``examples/`` in-process and fails on ANY unsuppressed
HVD2xx finding — a new AB/BA lock nesting, a blocking call smuggled into
a critical section, or an unjoined non-daemon thread fails tier-1 before
it can deadlock a fleet.

To silence a deliberate pattern, add ``# hvdlint: disable=HVD2xx`` on the
flagged line WITH a reasoned comment, or declare the intended order with
``# hvdrace: order=A<B`` (docs/static_analysis.md).
"""

import glob
import os

from horovod_tpu.analysis import lint_paths, race_paths, unsuppressed
from horovod_tpu.analysis.cli import main as cli_main

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PATHS = [os.path.join(_REPO, "horovod_tpu"),
          os.path.join(_REPO, "examples")]


def test_repo_is_hvdrace_clean():
    findings = race_paths(_PATHS)
    active = unsuppressed(findings)
    assert not active, (
        "hvdrace found lock-order / thread-lifecycle antipatterns — fix "
        "them, declare the intended order with '# hvdrace: order=A<B', "
        "or suppress each with a reasoned '# hvdlint: disable=...' "
        "comment:\n" + "\n".join(f.format() for f in active))


def test_race_suppressions_are_auditable():
    """Every suppressed hvdrace finding still surfaces with
    suppressed=True (the audit trail the dogfooding requires), and the
    repo carries at least the negotiation flush-under-lock audit."""
    findings = race_paths(_PATHS)
    for f in findings:
        assert f.suppressed, f.format()
    assert any("negotiation" in f.path and f.rule == "HVD201"
               for f in findings), \
        "the audited flush-under-lock suppression disappeared"


def test_race_walk_covers_the_threaded_tree():
    """Guard the gate itself: the analyzer must actually index the
    threaded subsystems' locks — if the walk or the lock indexing ever
    silently breaks, zero findings would mean nothing."""
    from horovod_tpu.analysis.lockgraph import _Analyzer
    from horovod_tpu.analysis.linter import iter_python_files
    import ast

    analyzer = _Analyzer()
    files = iter_python_files(_PATHS)
    assert len(files) > 50
    # Every module of the package, found by a walk of its own: modules
    # that are lock-free by design (the Pallas kernels, sampling, the
    # analyzers) hold that property only if the walker visits them.
    missing = set(glob.glob(os.path.join(_REPO, "horovod_tpu", "**", "*.py"),
                            recursive=True)) - set(files)
    assert not missing, f"not analyzed: {sorted(missing)}"
    for path in files:
        with open(path, "rb") as fh:
            src = fh.read().decode("utf-8", errors="replace")
        try:
            analyzer.add_module(ast.parse(src, filename=path), path, src)
        except SyntaxError:  # pragma: no cover - repo parses
            pass
    analyzer.run()
    # The serve/elastic control plane's locks must be in the registry
    # under their class identities.
    for label in ("DynamicBatcher._lock", "ServeMetrics._lock",
                  "InferenceEngine._lock", "ReplicaScheduler._lock",
                  "BlockManager._lock", "ElasticDriver._lock",
                  "Negotiator._buf_lock", "Negotiator._flush_lock",
                  "Tracer._lock", "FleetController._lock",
                  "ModelRegistry._lock", "TieredBlockManager._lock",
                  "Router._lock", "RouterMetrics._lock"):
        assert label in analyzer.lock_sites, \
            f"{label} missing from the witness registry"
    # Condition-wraps-lock aliasing: the batcher's _cond must NOT appear
    # as a separate lock (it IS _lock).
    assert "DynamicBatcher._cond" not in analyzer.lock_sites
    # The engine's lock participates in observed ordering edges.
    assert any("InferenceEngine._lock" in k for k in analyzer.graph), \
        "no ordering edges recorded for the engine lock"


def test_analyzer_modules_are_hvdlint_clean():
    """lockgraph.py and witness.py must themselves pass the hvdlint the
    rest of the repo is held to (test_lint_self covers the tree; this
    pins the two new modules explicitly per the CI satellite)."""
    targets = [os.path.join(_REPO, "horovod_tpu", "analysis", m)
               for m in ("lockgraph.py", "witness.py")]
    for t in targets:
        assert os.path.exists(t)
    assert not unsuppressed(lint_paths(targets))


def test_race_cli_exit_contract_matches_hvdlint(tmp_path, capsys):
    """--race honors the exact 0/1/2 contract hvdlint defines: 0 clean,
    1 findings (incl. HVD000 parse failures), same paths, same flags."""
    clean = tmp_path / "clean.py"
    clean.write_text("import threading\n\n"
                     "def go():\n"
                     "    threading.Thread(target=print, daemon=True)"
                     ".start()\n")
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import threading\n\n"
                     "def go():\n"
                     "    threading.Thread(target=print).start()\n")
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")

    for args, expected in (
            ([str(clean)], 0),
            ([str(dirty)], 1),
            ([str(bad)], 1),
            (["/nonexistent/race/path"], 1)):
        rc_race = cli_main(["--race"] + args)
        capsys.readouterr()
        assert rc_race == expected, (args, rc_race)
    # The lint mode agrees on the parse-failure and missing-path classes
    # (finding, not crash) — one shared contract.
    for args in ([str(bad)], ["/nonexistent/race/path"]):
        rc_lint = cli_main(args)
        capsys.readouterr()
        rc_race = cli_main(["--race"] + args)
        capsys.readouterr()
        assert rc_lint == rc_race == 1


def test_race_cli_dogfood_command_exits_zero(capsys):
    """The acceptance command: python -m horovod_tpu.analysis --race
    horovod_tpu (in-process — same code path as the module entry)."""
    rc = cli_main(["--race", os.path.join(_REPO, "horovod_tpu")])
    capsys.readouterr()
    assert rc == 0
