"""The rehearsal cell of ``benchmarks/tests/test_kimi_cell.py`` (the
Kimi-Linear job through ``runners/train.py`` on the CPU, its control, its
readers, its flops file and its manifest entries) as counted cases of this
suite."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("test", [
    "test_cell_and_its_reference",
    "test_cell_traced_reports_counts_but_no_device_metric",
    "test_control_in_a_lower_precision_comes_out_not_correct",
    "test_the_scans_time_over_its_roofline_and_over_its_chunks",
    "test_operations_of_the_cell_by_part",
    "test_every_new_reader_has_its_file_and_its_entry"])
def test_rehearsal_cell_through_the_train_runner(test):
    """``benchmarks/tests/test_kimi_cell.py`` (the rehearsal cell of
    ``benchmarks/tests/cells/`` through ``runners/train.py``, in a child
    process) as counted cases of this suite."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "HVD_TPU_EMULATE_RANKS")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-x", "-p",
         "no:cacheprovider", "-p", "no:xdist",
         f"benchmarks/tests/test_kimi_cell.py::{test}"],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
