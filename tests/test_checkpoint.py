"""Checkpoint helpers: rank-0 save + broadcast restore (SURVEY.md §5.4)."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MULTIPROC_WORKER = '''
import os
import sys
sys.path.insert(0, r"{repo}")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
import jax
jax.config.update("jax_platforms", "cpu")
import optax
import horovod_tpu as hvd

hvd.init()
path = os.path.join(r"{ckpt_dir}", "model")
params = {{"w": jax.numpy.ones((4,)) * (1.0 if hvd.rank() == 0 else 99.0)}}
base = optax.sgd(0.1, momentum=0.9)
opt = hvd.DistributedOptimizer(base)
opt_state = opt.init(params)
# rank 0 writes; the extra.json sidecar must exist before ANY rank is
# released from save's barrier, so the coordinated immediate load sees it.
hvd.checkpoint.save_model(path, params, opt_state, extra={{"epoch": 7}})
p, o, os_, extra = hvd.checkpoint.load_model(path, optimizer=base,
                                             params_template=params)
assert extra == {{"epoch": 7}}, f"rank {{hvd.rank()}} got extra={{extra}}"
assert float(p["w"][0]) == 1.0, "did not adopt rank 0 params"
print(f"CKPT_OK rank={{hvd.rank()}}")
'''


@pytest.mark.integration
@pytest.mark.xdist_group("heavy_e2e")
def test_save_model_load_model_two_processes(tmp_path):
    """Real 2-process world (launcher + jax.distributed): rank-0-only
    orbax write must not deadlock against the release barrier (orbax's own
    multihost sync is scoped to the writing process — see _ckptr), and the
    sidecar is visible to the immediate coordinated load on both ranks."""
    script = tmp_path / "ckpt_worker.py"
    script.write_text(MULTIPROC_WORKER.format(ckpt_dir=str(tmp_path),
                                              repo=REPO))
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(script)],
        cwd=REPO, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CKPT_OK rank=0" in proc.stdout
    assert "CKPT_OK rank=1" in proc.stdout


def test_save_restore_roundtrip(tmp_path, hvd8):
    state = {"w": jnp.arange(6.0).reshape(2, 3), "step": jnp.asarray(7)}
    path = str(tmp_path / "ckpt")
    hvd.checkpoint.save(path, state)
    restored = hvd.checkpoint.restore(path, template=state)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.arange(6.0).reshape(2, 3))
    assert int(restored["step"]) == 7


def test_restore_without_template_single(tmp_path, hvd8):
    state = {"a": jnp.ones((3,))}
    path = str(tmp_path / "ckpt2")
    hvd.checkpoint.save(path, state)
    restored = hvd.checkpoint.restore(path)
    np.testing.assert_allclose(np.asarray(restored["a"]), np.ones(3))


def test_load_model_resumes_identical_trajectory(tmp_path, hvd8):
    """save_model/load_model (keras/__init__.py:268 analog): restore the
    wrapped optimizer's FULL state — adam moments AND the local gradient-
    aggregation counter mid-cycle — and the continued run must reproduce
    the uninterrupted run's losses exactly."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import create_mlp

    model = create_mlp(features=(16, 4))
    X = jnp.asarray(np.random.RandomState(0).randn(16, 8).astype(np.float32))
    Y = jnp.asarray(np.random.RandomState(1).randn(16, 4).astype(np.float32))
    params0 = model.init(jax.random.PRNGKey(0), X[:1])

    def make(opt_state=None, params=None):
        opt = hvd8.DistributedOptimizer(optax.adam(1e-2),
                                        backward_passes_per_step=2)
        params = params if params is not None else params0
        opt_state = opt_state if opt_state is not None else opt.init(params)

        def local_step(p, s, xb, yb):
            loss, g = jax.value_and_grad(
                lambda p: jnp.mean((model.apply(p, xb) - yb) ** 2))(p)
            u, s = opt.update(g, s, p)
            return optax.apply_updates(p, u), s, hvd8.allreduce(
                loss, op=hvd8.Average)

        step = hvd8.parallel.shard_step(
            local_step, in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P()))
        return opt, params, opt_state, step

    # Uninterrupted reference run: 3 steps (ODD — the accumulation cycle
    # of backward_passes_per_step=2 is mid-flight at the save point), then
    # 4 more.
    _, p, s, step = make()
    for _ in range(3):
        p, s, _loss = step(p, s, X, Y)
    ref_losses = []
    for _ in range(4):
        p, s, loss = step(p, s, X, Y)
        ref_losses.append(float(loss))

    # Interrupted run: same 3 steps, save_model, load_model, 4 more.
    _, p, s, step = make()
    for _ in range(3):
        p, s, _loss = step(p, s, X, Y)
    path = str(tmp_path / "model_ckpt")
    hvd8.checkpoint.save_model(path, p, s, extra={"epoch": 3})
    params_r, opt_r, state_r, extra = hvd8.checkpoint.load_model(
        path, optimizer=optax.adam(1e-2), params_template=params0,
        backward_passes_per_step=2)
    assert extra == {"epoch": 3}
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)), p,
        params_r)
    _, p2, s2, step2 = make(opt_state=state_r, params=params_r)
    resumed = []
    for _ in range(4):
        p2, s2, loss = step2(p2, s2, X, Y)
        resumed.append(float(loss))
    np.testing.assert_allclose(resumed, ref_losses, rtol=0, atol=0)
