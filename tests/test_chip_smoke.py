"""chip_smoke.py away from the chip: it must fail, and say nothing.

The script's value is that a pass means the chip ran the system.  On the
CPU these tests hold it to the other half of that contract — a platform
other than ``tpu`` is a failure, not a fallback — and pin the output of
the example that the trainer phase parses, and run the checks that need
no chip in Pallas's interpreter.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, **env):
    return subprocess.run(cmd, cwd=_REPO, env=dict(os.environ, **env),
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_the_chip():
    r = _run([sys.executable, "chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not 'tpu'" in r.stderr


def test_trainer_example_prints_what_chip_smoke_parses():
    """The trainer phase's command at a toy size on the CPU: through the
    launcher, the example prints its platform and the two synced losses
    in the form ``chip_smoke.run_trainer`` reads."""
    import re

    import chip_smoke
    cmd = [{"resnet50": "mlp", "128": "8"}.get(a, a)
           for a in chip_smoke.TRAINER_CMD]
    r = _run(cmd, JAX_PLATFORMS="cpu", XLA_FLAGS="", HVD_TPU_EMULATE_RANKS="")
    assert r.returncode == 0, r.stderr[-2000:]
    assert re.search(r"platform cpu \(", r.stdout), r.stdout
    warm, final = re.search(
        r"Loss after warm-up: (\S+), after 5 more steps: (\S+)",
        r.stdout).groups()
    assert float(warm) > float(final) > 0  # seven SGD steps on one batch


@pytest.mark.parametrize("pieces", [3, 2])
def test_the_kda_products_check_passes_float32s_and_refuses_a_lost_piece(
        pieces, monkeypatch):
    """``kda_sums_products`` (``--phase kda``'s hold of the 0/1 matrix's
    products on float64, in float32 ulps) in the interpreter at a toy size:
    the kernels' three pieces of the float32 operand pass, two (its last 8
    bits lost) do not."""
    import jax
    import jax.numpy as jnp

    import chip_smoke
    from horovod_tpu.parallel import kda
    if pieces == 2:
        split = kda._split
        monkeypatch.setattr(kda, "_split", lambda x: (
            *split(x)[:2], jnp.zeros(x.shape, jnp.bfloat16)))
    g = -16.0 * jax.nn.softplus(jax.random.normal(
        jax.random.PRNGKey(0), (4 * kda.CHUNK, 2, 128)))
    if pieces == 3:
        chip_smoke.kda_sums_products(g, interpret=True)
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="ulps"):
            chip_smoke.kda_sums_products(g, interpret=True)
