"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q``.  Cells run as child processes in a temporary copy
of the benchmark (``overlay.py``), so nothing here imports JAX."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))  # harness, by name
sys.path.insert(0, HERE)                   # overlay


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory):
    """The committed benchmark plus the rehearsal cells, added as new
    files and manifest entries only."""
    import overlay
    return overlay.make_copy(str(tmp_path_factory.mktemp("bench")))


def run_cell(copy: str, cell: str, trace: int, devices: int = 1,
             seconds: float = 2, seed: int = 3000000019):
    """Run one cell in ``copy``; returns ``(returncode, stdout lines)``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("HVD_TPU_EMULATE_RANKS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmarks", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    return proc.returncode, proc.stdout.strip().split("\n"), proc.stderr


@pytest.fixture(scope="session")
def run_in_copy(bench_copy):
    def run(cell, trace, **kw):
        rc, lines, err = run_cell(bench_copy, cell, trace, **kw)
        assert rc == 0, err[-3000:]
        return json.loads(lines[-1]), lines
    return run
