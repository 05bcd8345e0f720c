"""What a window is for a training job.

Everything runs in this process, which holds the chip(s).  Steps are
dispatched back to back with a few in flight: the host blocks on the loss
of the step it dispatched ``steps_in_flight`` steps earlier, so the
device's queue neither drains nor grows.  The window starts after a sync
and ends at the sync of the last step dispatched inside it;
``train_samples_per_s`` is all its steps times the global batch over all
its time.  With ``--trace 1`` the profiler runs for ``trace_seconds`` after
the window, on the same stream of steps, so that starting and stopping it
costs the rate nothing.
"""

import collections
import math
import os
import time

from harness import manifest as mf
from harness import result, trace as tracing


def run_steps(step, state, batch, until, depth: int):
    """Dispatch steps until ``until()`` says stop, ``depth`` in flight;
    returns ``(state, losses, elapsed)`` with every step synced."""
    in_flight, losses = collections.deque(), []
    t0 = time.monotonic()
    while True:
        *state, loss = step(*state, *batch)
        in_flight.append(loss)
        if len(in_flight) > depth:
            losses.append(float(in_flight.popleft()))
        if until(time.monotonic() - t0, len(losses) + len(in_flight)):
            break
    while in_flight:
        losses.append(float(in_flight.popleft()))
    return state, losses, time.monotonic() - t0


def run(run) -> dict:
    import jax
    import horovod_tpu as hvd

    cell, config = run.cell, run.config
    traffic = cell["traffic"]
    result.mark(run, "imports done, hvd.init")
    hvd.init()
    if hvd.num_slots() != run.chips:
        raise SystemExit(f"hvd.num_slots() is {hvd.num_slots()}, the cell "
                         f"asks for {run.chips}")
    job = mf.load_module("jobs", config["job"])
    devices = jax.devices()[:run.chips]

    # -- correct: the program against the plain reference, three steps ----
    spec = config["correct"]
    check_batch = traffic["check_global_batch"]
    per_chip = traffic["images_per_chip"]
    checked = job.Program(config, check_batch // run.chips, run.seed)
    holders = {s.device for s in checked.batch[0].addressable_shards}
    state = checked.fresh_state()
    got = []
    for _ in range(spec["steps"]):
        *state, loss = checked.step(*state, *checked.batch)
        got.append(float(loss))
    del state
    result.mark(run, "program's check steps done, reference")
    want = job.reference_losses(config, run.seed, check_batch,
                                spec["steps"])
    errors = [abs(g - w) for g, w in zip(got, want)]
    result.log(f"correct: program losses {got} reference {want} "
               f"errors {errors} tolerance {spec['loss_tolerance']}; "
               f"shards on {len(holders)} device(s)")
    correct = (all(math.isfinite(g) for g in got)
               and max(errors) <= spec["loss_tolerance"]
               and len(holders) == run.chips)

    result.mark(run, "reference done, warm-up")
    # -- warm-up: the measured shape, from the seeded state ---------------
    program = (checked if check_batch // run.chips == per_chip
               else job.Program(config, per_chip, run.seed))
    depth = traffic["steps_in_flight"]
    state = program.fresh_state()
    state, warm, _ = run_steps(
        program.step, state, program.batch,
        lambda t, n: n >= traffic["warmup_steps"], depth)
    setup = run.compiles.snapshot()
    result.log(f"set-up: {setup['programs']} programs requested, "
               f"{setup['cache_hits']} found in the compile cache, "
               f"{setup['seconds']:.1f} s in the backend; warm-up losses "
               f"{warm}")

    # -- the window -------------------------------------------------------
    setup_s = time.monotonic() - run.process_start
    state, losses, elapsed = run_steps(
        program.step, state, program.batch,
        lambda t, n: t >= run.seconds, depth)
    in_window = run.compiles.snapshot()["programs"] - setup["programs"]
    steps = len(losses)
    rate = steps * program.global_batch / elapsed
    bad = sum(1 for x in losses if not math.isfinite(x))
    correct = correct and bad == 0 and losses[-1] < warm[0]
    result.log(f"window: {steps} steps of {program.global_batch} samples "
               f"in {elapsed:.4f} s = {rate:.2f} samples/s; loss "
               f"{warm[0]:.4f} -> {losses[-1]:.4f}; {bad} non-finite; "
               f"{in_window} programs compiled or loaded in the window")

    results = {
        "correct": correct, "attempted": steps, "failed": bad,
        "end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
        "compiles_in_window": in_window, "trace_dir": None,
        "hlo_text": lambda: program.hlo_text(state),
    }
    if run.trace:
        trace_dir = os.path.join(run.out_dir, "trace")
        tracing.start(trace_dir)
        state, traced, t = run_steps(
            program.step, state, program.batch,
            lambda t, n: t >= traffic["trace_seconds"], depth)
        tracing.stop()
        result.log(f"traced {len(traced)} steps in {t:.3f} s into "
                   f"{trace_dir}")
        results["trace_dir"] = trace_dir
    result.log(f"memory: {devices[0].memory_stats()}")
    results["memory_peak_bytes"] = result.memory_peak_bytes(devices)
    return results
