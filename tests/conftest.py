"""Hermetic test environment: 8 virtual CPU devices emulate an 8-chip slice.

This is the TPU analog of the reference running its parallel suite under
``horovodrun -np 2`` with CPU Gloo as the hermetic backend (SURVEY.md §4):
multi-chip is simulated as multi-device in one process via
``--xla_force_host_platform_device_count``, and every collective really
executes through XLA's CPU collective implementation.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("HVD_TPU_EMULATE_RANKS", "8")
# The persistent compile cache hvd.init() sets up is for the chip; a test
# run (and the workers it spawns) neither reads nor fills the checkout's.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    """xdist scheduling policy (--dist loadgroup, pyproject addopts).

    Subprocess-world e2e tests (multi-process jax + gloo + rendezvous)
    thrash each other when they overlap on this box's single host core —
    cascading spurious stall timeouts and elastic resets.  Files that
    spawn such worlds declare ``pytestmark = pytest.mark.xdist_group
    ("heavy_e2e")`` so they all serialize on ONE xdist worker; every
    unmarked test inherits its module as its group, preserving the
    per-file serialization of plain --dist loadfile for the light
    in-process tests."""
    for item in items:
        if not any(m.name == "xdist_group" for m in item.iter_markers()):
            item.add_marker(
                pytest.mark.xdist_group(item.module.__name__))


@pytest.fixture()
def hvd8():
    """Initialized runtime with 8 emulated ranks; torn down after the test."""
    import horovod_tpu as hvd
    hvd.shutdown()
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="session")
def bench_job():
    """``bench_job(name)``: the module ``benchmarks/jobs/<name>.py``, loaded
    as the benchmark loads it (it finds ``harness`` by name)."""
    import importlib.util
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")

    def load(name):
        sys.path.insert(0, bench)
        try:
            spec = importlib.util.spec_from_file_location(
                "bench_jobs_" + name, os.path.join(bench, "jobs",
                                                   name + ".py"))
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
        finally:
            sys.path.remove(bench)
        return module

    return load


@pytest.fixture(scope="session", autouse=True)
def _lock_witness_session():
    """HVD_SANITIZE=1 runs the whole suite under the lock-witness
    sanitizer (analysis/witness.py): locks constructed during the run are
    order-checked live, and the session FAILS at teardown on any
    inversion/naked-wait finding left standing (tests that seed
    violations deliberately reset the witness themselves).  A no-op (one
    env read) when the env is unset."""
    from horovod_tpu.analysis import witness
    installed = witness.maybe_install_from_env()
    yield
    if installed:
        findings = witness.findings()
        witness.uninstall()
        assert not findings, (
            "HVD_SANITIZE: the suite left lock-witness findings "
            "standing:\n" + "\n".join(f.format() for f in findings))
