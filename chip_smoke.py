#!/usr/bin/env python
"""The quickest proof that horovod_tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip: kernels, trainer, server
    python chip_smoke.py --chips 4  # four chips: the data-parallel trainer

Drives the repo's two programs through the entry points a user calls, at
full width with seeded random weights, and checks what comes out by the
repo's own references:

* **kernels** — the four Pallas kernels compiled for the chip
  (``interpret=False``) against their dense / gather references;
* **sdar** — SDAR-30B-A3B-Chat's block-diffusion step at the benchmark
  cell's sizes (``benchmarks/configs/sdar-30b-a3b-ep8.json``, 4 sequences of
  4,096 clean tokens): loss and every gradient leaf of
  ``models/sdar_moe.py`` (flash kernels under the block-diffusion mask,
  the dropless expert layer) against the plain float32 reference of
  ``benchmarks/jobs/sdar_moe.py``;
* **afmoe** — Trinity-Mini's next-token step at the benchmark cell's sizes
  (``benchmarks/configs/trinity-mini-ep8.json``, 4 sequences of 8,192
  tokens): loss
  and every gradient leaf of ``models/afmoe.py`` (window and full attention
  in one stack, the gated attention output, the sigmoid router with a
  shared expert, a leading dense layer) against the plain float32 reference
  of ``benchmarks/jobs/afmoe.py``; then the three flash kernels' time a tile
  under the window, ``MASK_CAUSAL`` and ``MASK_NONE``
  (``tools/flash_tile_times.py``);
* **joyai** — JoyAI-LLM-Flash's step (next-token loss plus the depth-1
  multi-token-prediction loss) at the benchmark cell's sizes
  (``benchmarks/configs/joyai-llm-flash-ep16.json``, 4 sequences of 8,192
  tokens): both losses and every gradient leaf of ``models/joyai_flash.py``
  (latent attention through the flash kernels with keys of 192 and values
  of 128, the MTP module on the shared embedding and head) against the
  plain float32 reference of ``benchmarks/jobs/joyai_flash.py``;
* **kda** — Kimi Delta Attention alone at the Kimi-Linear cell's shapes (one
  sequence of 8,192 positions, 32 heads of 128): the scan kernels of
  ``parallel/kda.py`` against the token-by-token recurrence, and the passes
  round the scan (``parallel/kda_surround.py``: the short convolutions with
  SiLU and the L2 norms, the decay, the gated norm) against the plain
  definitions of ``models/kimi_linear.py``, with their times: microseconds
  a chunk, milliseconds a pass and its share of the HBM's rate;
* **trainer** — ``horovodrun -np 1 python examples/synthetic_benchmark.py``:
  ResNet-50, 1000 classes, 224², bf16, sync-BN, batch 128, seven steps;
* **server** — ``hvdserve --model gpt2-small`` answering ``/generate``
  requests of 5 to 300 prompt tokens over HTTP, then draining on SIGTERM.

With ``--chips 4`` it runs only the ResNet-50 ``shard_step`` over four
chips and the same global batch on one chip, which must agree.

A chip belongs to one process at a time, so this process never
initialises a JAX backend: it runs each phase as a child, one after
another, and builds its last line from what the children reported.  A
platform other than ``tpu``, a value out of tolerance, a child that exits
non-zero or outlives its limit: each ends the script with a non-zero code
and no ``ok`` line.  The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import contextlib
import functools
import json
import math
import os
import random
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
REPORT = "chip_smoke report: "  # a child's last line: this + one JSON object
PLATFORM = "tpu"
SEED = 0

# -- kernels: the shapes tests/test_tpu_compile.py compiles ---------------
FLASH_SHAPE = dict(batch=8, heads=16, head_dim=64)   # bf16, S in FLASH_SEQS
FLASH_SEQS = (128, 1024)
POOL = dict(num_blocks=256, block_tokens=16, heads=12, head_dim=64)
SERVE_BATCH, TABLE_BLOCKS, PREFILL_CHUNK = 8, 16, 64
# Max-abs error allowed, as a share of the reference's largest magnitude
# (or of 1 where that is smaller).  Every dot in these kernels goes through
# the MXU as one bf16 pass — Mosaic's default for float32 operands, as it
# is XLA's — so the float32 and int8 pools see the same 2^-9 rounding of
# each operand as the bf16 flash inputs, and flash rounds its outputs and
# gradients to bf16 once more.  Two bf16 ulps, against references computed
# at the highest matmul precision.
MXU_BF16_TOL = 2.0 ** -7

# -- block diffusion -------------------------------------------------------
SDAR_CONFIG = "benchmarks/configs/sdar-30b-a3b-ep8.json"
SDAR_SEQUENCES = 4      # the cell's step
# The program computes in bf16 on float32 parameters, the reference in
# float32 at the highest matmul precision.  Loss (about ln 18,992 = 9.85):
# absolute; measured 3.1e-4.  Gradients: |got - want| / |want| in the
# 2-norm by leaf, each held to the limit the cell's own check has for it
# (``correct.gradient_limits`` of the configuration, where the readings
# behind each limit are given).
SDAR_LOSS_TOL = 2e-2
# The expert layer alone, a sequence of the cell (8,192 positions) under
# independent seeded router columns: once as they fall (about the even
# load: one buffer) and once with the held experts' columns moved up by
# SDAR_SKEW standard deviations, which sends several times the even load
# here: past the buffer, so the chunked path runs.  Against every held
# expert on every position in float32; bf16 products on both sides of a
# 768-wide gated unit: a few bf16 ulps of a 2-norm.
SDAR_SKEW = 1.0
SDAR_LAYER_TOL = 3e-2

# -- window and full attention, a shared expert ------------------------------
AFMOE_CONFIG = "benchmarks/configs/trinity-mini-ep8.json"
AFMOE_SEQUENCES = 4     # the cell's step, which its limits were read on
AFMOE_LOSS_TOL = 2e-2   # about ln 25,024 = 10.1; as SDAR_LOSS_TOL
# The three flash kernels alone under the window, the causal mask and no
# mask (us a tile, from a device trace): run after the phase, by the parent.
AFMOE_TILE_TIMES = [sys.executable, "tools/flash_tile_times.py", "--masks",
                    "window", "causal", "none"]

# -- latent attention, a multi-token-prediction module -----------------------
JOYAI_CONFIG = "benchmarks/configs/joyai-llm-flash-ep16.json"
JOYAI_SEQUENCES = 4     # the cell's step, which its limits were read on
JOYAI_LOSS_TOL = 2e-2   # about ln 16,160 = 9.7; as SDAR_LOSS_TOL

# -- the chunked scan of Kimi Delta Attention, alone --------------------------
# One sequence at the kimi-linear-train-8k cell's shapes, bf16 operands at
# the decays its seeded gates give, against the token-by-token recurrence in
# float32 on the same (rounded) operands: the output in the largest absolute
# difference as a share of the largest output, the five gradients in the
# 2-norm.
KDA_SEQ, KDA_HEADS, KDA_D = 8192, 32, 128
KDA_SEGMENT = 256       # tokens whose states the recurrence's backward keeps
KDA_OUT_TOL = 3e-2
KDA_GRAD_TOL = 3e-2
KDA_REPEATS = 5
# What surrounds the scan (``parallel/kda_surround.py``), alone at the same
# shapes: each pass's results and gradients against the plain definitions
# in float32 on the same (rounded) operands, in the 2-norm (a bf16 store is
# 2e-3 of it), and its time against the bytes it has to move, every operand
# read and every result written once: bytes an element of ``[S, P]``.
KDA_PASS_TOL = 1e-2
KDA_PASS_BYTES = {"front": (4 * 2 + 3 * 2 + 4, 3 * 2 + 4 + 4 * 2 + 4 * 2),
                  "out": (2 * 2 + 2, 3 * 2 + 2 * 2)}
HBM_BYTES_PER_S = 819e9

# -- trainer ---------------------------------------------------------------
TRAINER_CMD = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "1",
               sys.executable, "examples/synthetic_benchmark.py",
               "--model", "resnet50", "--batch-size", "128",
               "--num-warmup-batches", "2", "--num-iters", "5"]

# -- server ----------------------------------------------------------------
SERVER_CMD = [sys.executable, "-m", "horovod_tpu.serve",
              "--model", "gpt2-small", "--replicas", "1", "--port", "0",
              "--max-len", "512", "--max-batch", "8"]
VOCAB = 50257
PROMPT_LENGTHS = (5, 40, 130, 300)  # one block .. five 64-token chunks
TWIN_LENGTH = 70                    # sent twice, concurrently with the rest
NEW_TOKENS = 16

# -- four chips ------------------------------------------------------------
DP_MODEL, DP_GLOBAL_BATCH, DP_STEPS = "resnet50", 128, 3
# Sync-BN and hvd.Average make the four-chip and the one-chip step the same
# computation in another order of summation, carried in bf16 activations:
# the losses (about ln 1000 = 6.9) agree to a few bf16 ulps of the logits.
DP_LOSS_TOL = 5e-2

# Seconds a phase may take, compilation included; the whole stays inside
# the 1200 s the contract allows.
LIMITS = {"kernels": 300, "sdar": 600, "afmoe": 600, "joyai": 600,
          "kda": 300, "tile_times": 300,
          "trainer": 400, "server": 400, "dp4": 900}


class SmokeFailure(Exception):
    """A phase failed; the message says which check."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Phases that hold the chip themselves (run as `--phase NAME` children)
# ---------------------------------------------------------------------------

def require_platform() -> dict:
    """The device as JAX reports it; anything but the chip is a failure,
    not a fallback.  First thing a chip-holding phase does."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    check(device["platform"] == PLATFORM,
          f"JAX found platform {device['platform']!r}, not {PLATFORM!r}")
    return device


def max_abs_error(got, want):
    """``(max |got - want|, max |want|)`` of two arrays of one shape."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite values from a kernel")
    return float(np.abs(got - want).max()), float(np.abs(want).max())


def flash_errors(interpret: bool):
    """``flash_attention`` forward and ``jax.grad`` against the dense
    attention the tests use, on seeded bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.parallel.flash import flash_attention
    from horovod_tpu.parallel.ring import ring_attention_reference

    B, H, D = (FLASH_SHAPE[k] for k in ("batch", "heads", "head_dim"))
    for seq in FLASH_SEQS:
        rng = np.random.RandomState(SEED + seq)
        q, k, v = (jnp.asarray(rng.randn(B, seq, H, D), jnp.bfloat16)
                   for _ in range(3))
        cotangent = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
        for causal in (False, True):
            # The cotangent is an argument, not a closed-over constant: a
            # constant is compiled into the executable, 32 MB of it here.
            def kernel(q, k, v, cotangent):
                out = flash_attention(q, k, v, causal=causal,
                                      interpret=interpret)
                return (out.astype(jnp.float32) * cotangent).sum(), out

            def dense(q, k, v, cotangent):
                with jax.default_matmul_precision("highest"):
                    out = ring_attention_reference(q, k, v, causal=causal)
                return (out * cotangent).sum(), out

            grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
                f, argnums=(0, 1, 2), has_aux=True))
            (_, out), grads = grad(kernel)(q, k, v, cotangent)
            (_, want), want_grads = grad(dense)(
                *(x.astype(jnp.float32) for x in (q, k, v)), cotangent)
            name = f"flash S={seq} {'causal' if causal else 'full'}"
            yield f"{name} forward", max_abs_error(out, want), \
                MXU_BF16_TOL
            for which, g, w in zip("qkv", grads, want_grads):
                yield f"{name} d{which}", max_abs_error(g, w), \
                    MXU_BF16_TOL


def paged_inputs(rng, chunk: int):
    """A seeded pool and ``SERVE_BATCH`` sequences of mixed length laid
    over it the way the engine does: distinct physical blocks in table
    order, the hole sentinel past each sequence's last block.  ``chunk``
    query rows per sequence (1 = decode); returns the first query
    position of each row."""
    import numpy as np
    NB, BT = POOL["num_blocks"], POOL["block_tokens"]
    span = TABLE_BLOCKS * BT
    firsts = rng.randint(0, span - chunk + 1, size=SERVE_BATCH)
    firsts[0], firsts[1] = 0, span - chunk  # no context / a full table
    tables = np.full((SERVE_BATCH, TABLE_BLOCKS), NB, np.int32)
    free = rng.permutation(NB)
    for b, first in enumerate(firsts):
        need = (first + chunk - 1) // BT + 1
        tables[b, :need], free = free[:need], free[need:]
    return tables, firsts.astype(np.int32)


def paged_errors(interpret: bool):
    """``paged_decode_attention`` / ``paged_prefill_attention`` against
    ``paged_attention_reference`` over a float32 and an int8 pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.serve import paged_attention as pa

    NB, BT, H, Dh = (POOL[k] for k in ("num_blocks", "block_tokens",
                                       "heads", "head_dim"))
    rng = np.random.RandomState(SEED)
    k_pool, v_pool = (jnp.asarray(rng.randn(NB, BT, H, Dh), jnp.float32)
                      for _ in range(2))
    pools = {"f32": dict(k=k_pool, v=v_pool, scales={})}
    (k8, ks), (v8, vs) = (pa.quantize_kv(p, "int8")
                          for p in (k_pool, v_pool))
    pools["int8"] = dict(k=k8, v=v8, scales=dict(k_scale=ks, v_scale=vs))
    for phase, chunk, attend in (
            ("decode", 1, pa.paged_decode_attention),
            ("prefill", PREFILL_CHUNK, pa.paged_prefill_attention)):
        tables, firsts = paged_inputs(rng, chunk)
        q = jnp.asarray(rng.randn(SERVE_BATCH, chunk, H, Dh), jnp.float32)
        if phase == "decode":
            q = q[:, 0]
        for kv, pool in pools.items():
            got = jax.jit(lambda q, k, v, scales: attend(
                q, k, v, tables, firsts, interpret=interpret, **scales))(
                    q, pool["k"], pool["v"], pool["scales"])
            reference = functools.partial(
                pa.paged_attention_reference, q, pool["k"], pool["v"],
                jnp.asarray(tables), jnp.asarray(firsts), **pool["scales"])
            with jax.default_matmul_precision("highest"):
                want = reference()
            yield f"paged {phase} {kv} pool", max_abs_error(got, want), \
                MXU_BF16_TOL
            # For the record, not a check: the gather path the kernel
            # replaces, at the precision the engine runs it.
            yield f"paged {phase} {kv} pool, gather path at default " \
                f"precision", max_abs_error(reference(), want), math.inf


def phase_kernels() -> dict:
    device = require_platform()
    import horovod_tpu as hvd
    hvd.init()  # the compile cache
    print(f"kernels: compiled for {device}", flush=True)
    outside = []
    for errors in (flash_errors, paged_errors):
        for name, (err, scale), tol in errors(interpret=False):
            allowed = tol * max(1.0, scale)
            print(f"kernels: {name}: max-abs error {err:.3e} against a "
                  f"reference of magnitude {scale:.2f} (allowed "
                  f"{allowed:.1e})", flush=True)
            if not err <= allowed:
                outside.append(name)
    check(not outside, f"kernels out of tolerance: {', '.join(outside)}")
    return device


def phase_dp4() -> dict:
    """The trainer's ``shard_step`` over four chips, then the same global
    batch on a one-device mesh in this same process."""
    device = require_platform()
    check(device["count"] == 4, f"{device['count']} devices, not 4")
    import jax
    import horovod_tpu as hvd
    from examples import synthetic_benchmark

    losses = {}
    for slots in (4, 1):
        if slots == 1:
            # A one-rank world over the first device only: the knob for
            # fewer ranks than local devices (topology.detect).
            os.environ["HVD_TPU_EMULATE_RANKS"] = "1"
        hvd.init()
        check(hvd.num_slots() == slots,
              f"num_slots() is {hvd.num_slots()}, not {slots}")
        step, state, batch = synthetic_benchmark.build(
            DP_MODEL, DP_GLOBAL_BATCH // slots)
        holders = {s.device for s in batch[0].addressable_shards}
        check(len(holders) == slots,
              f"the batch's shards sit on {len(holders)} devices, "
              f"not {slots}")
        print(f"dp4: {slots}-chip world: batch {batch[0].shape} in "
              f"{len(batch[0].addressable_shards)} shards on "
              f"{sorted(d.id for d in holders)}", flush=True)
        if slots > 1:
            text = step.lower(*state, *batch).compile().as_text()
            all_reduces = re.findall(r"\ball-reduce(?:-start)?\(", text)
            check(bool(all_reduces), "the compiled step holds no all-reduce")
            print(f"dp4: the compiled step holds {len(all_reduces)} "
                  f"all-reduce ops", flush=True)
        losses[slots] = []
        for _ in range(DP_STEPS):
            *state, loss = step(*state, *batch)
            losses[slots].append(float(loss))
        print(f"dp4: {slots}-chip losses {losses[slots]}", flush=True)
        del step, state, batch
        hvd.shutdown()
    for four, one in zip(losses[4], losses[1]):
        check(math.isfinite(four) and math.isfinite(one),
              "non-finite loss")
        check(abs(four - one) <= DP_LOSS_TOL,
              f"four-chip loss {four} and one-chip loss {one} differ by "
              f"more than {DP_LOSS_TOL}")
    check(losses[4][0] != losses[4][-1], "the parameters did not move")
    return device


def phase_sdar() -> dict:
    """Loss and gradients of the block-diffusion step at the published
    widths and the cell's sizes, program against reference."""
    device = require_platform()
    import importlib.util
    import jax
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.models import sdar_moe
    hvd.init()  # the compile cache
    spec = importlib.util.spec_from_file_location(
        "sdar_job", os.path.join(REPO, "benchmarks/jobs/sdar_moe.py"))
    job = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(job)
    with open(os.path.join(REPO, SDAR_CONFIG)) as f:
        config = json.load(f)
    cfg = job.model_config(config)
    params = job.seeded_params(config, SEED)
    batch = job.seeded_batch(config, SEED, SDAR_SEQUENCES)
    t0 = time.monotonic()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p, *b: sdar_moe.loss_fn(p, *b, cfg), has_aux=True))(
            params, *batch)
    loss = float(loss)
    print(f"sdar: program loss {loss:.6f}, pairs routed to the held "
          f"experts by layer {np.asarray(aux.routed_here).tolist()} "
          f"({time.monotonic() - t0:.0f} s)", flush=True)
    t0 = time.monotonic()
    want_loss, want, chosen = job.ReferenceSteps(
        config, SDAR_SEQUENCES).loss_and_grads(job.unstacked(params), *batch)
    print(f"sdar: reference loss {want_loss:.6f} "
          f"({time.monotonic() - t0:.0f} s); "
          f"{100 * job.choices_that_differ(aux.chosen, chosen):.3f} % of the "
          f"program's routing choices are not the reference's", flush=True)
    check(math.isfinite(loss) and abs(loss - want_loss) <= SDAR_LOSS_TOL,
          f"sdar: loss {loss} against the reference's {want_loss}")
    outside = job.leaves_outside(config, job.gradient_errors(grads, want))
    check(not outside, f"sdar: gradients out of their limits: {outside}")
    del params, grads, want
    for skew in (0.0, SDAR_SKEW):
        sdar_expert_layer(job, config, skew)
    return device


def sdar_expert_layer(job, config: dict, skew: float) -> None:
    """``dropless_expert_ffn`` on one sequence of the cell, forward and
    every gradient, against every held expert applied to every position;
    ``skew`` moves the held experts' router columns up."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.parallel.moe import dropless_expert_ffn
    z = job.sizes(config)
    n, d, f, held, first = 2 * z["length"], z["d"], z["width"], z["held"], \
        z["first"]
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 6)
    normal = lambda key, *shape: jax.random.normal(key, shape, jnp.float32)
    # Every position shares one direction, as every token's state shares
    # a mean; the held experts' columns lean on it.
    shared = normal(keys[0], d)
    x = (normal(keys[1], n, d) + shared).astype(jnp.bfloat16)
    cot = normal(keys[1], n, d)
    router = normal(keys[2], d, z["routed"]) * (4.0 / d ** 0.5)
    router = router.at[:, first:first + held].add(
        (skew * 4.0 / d) * shared[:, None])
    w_gate, w_up = (normal(k, held, d, f) / d ** 0.5 for k in keys[3:5])
    w_down = normal(keys[5], held, f, d) / f ** 0.5

    def program(x, *weights):
        out = dropless_expert_ffn(x, router, *weights, top_k=z["top_k"],
                                  first_expert=first)
        return jnp.sum(out.out.astype(jnp.float32) * cot), out.routed_here

    @job.highest
    def plain(x, w_gate, w_up, w_down):
        h = x.astype(jnp.float32)
        top_p, chosen = jax.lax.top_k(jax.nn.softmax(h @ router, -1),
                                      z["top_k"])
        top_p = top_p / top_p.sum(-1, keepdims=True)

        def add_expert(acc, e_and_weights):
            e, *weights = e_and_weights
            gate = jnp.sum(jnp.where(chosen == first + e, top_p, 0.0), -1,
                           keepdims=True)
            return acc + gate * job.expert(h, *weights), None

        out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h), (
            jnp.arange(held), w_gate, w_up, w_down))
        return jnp.sum(out * cot)

    args = (x, w_gate, w_up, w_down)
    step = jax.jit(jax.value_and_grad(program, argnums=(0, 1, 2, 3),
                                      has_aux=True))
    (got, routed), got_grads = jax.block_until_ready(step(*args))
    seconds = []
    for _ in range(3):
        t0 = time.monotonic()
        jax.block_until_ready(step(*args))
        seconds.append(time.monotonic() - t0)
    want, want_grads = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2, 3)))(*args)
    errors = [abs(float(got) - float(want)) / abs(float(want))] + [
        float(jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
              / jnp.linalg.norm(w.ravel()))
        for g, w in zip(got_grads, want_grads)]
    even = n * z["top_k"] * held // z["routed"]
    print(f"sdar: expert layer, held columns up by {skew}: "
          f"{int(routed)} pairs routed here ({int(routed) / even:.2f} x the "
          f"even load), forward and backward {1e3 * min(seconds):.2f} ms; "
          f"relative errors of the result and the gradients by x, w_gate, "
          f"w_up, w_down: {[f'{e:.2e}' for e in errors]}", flush=True)
    check(bool(np.isfinite(errors).all()) and max(errors) <= SDAR_LAYER_TOL,
          f"sdar: expert layer with held columns up by {skew}: {errors}")


def phase_afmoe() -> dict:
    """Loss and every gradient leaf of the Trinity-Mini cell's step at the
    published widths, program against reference, each leaf held to the
    limit the cell's own check has for it."""
    device = require_platform()
    import jax
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.models import afmoe
    hvd.init()  # the compile cache
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from harness import manifest as mf
    job = mf.load_module("jobs", "afmoe")
    with open(os.path.join(REPO, AFMOE_CONFIG)) as f:
        config = json.load(f)
    cfg = job.model_config(config)
    params = job.seeded_params(config, SEED)
    batch = job.seeded_batch(config, SEED, AFMOE_SEQUENCES)
    t0 = time.monotonic()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p, *b: afmoe.loss_fn(p, *b, cfg), has_aux=True))(
            params, *batch)
    loss = float(loss)
    # To the host, and the stacked tree gone, before the reference's own
    # 5.6 GB of parameters and gradients.
    grads, chosen = jax.tree_util.tree_map(np.asarray, (grads, aux.chosen))
    print(f"afmoe: program loss {loss:.6f}, pairs routed to the held "
          f"experts by expert layer {np.asarray(aux.routed_here).tolist()} "
          f"({time.monotonic() - t0:.0f} s)", flush=True)
    layers = job.unstacked(params)
    del params, aux
    t0 = time.monotonic()
    # As the cell's check: the reference follows the program's choices, and
    # those are held to its own by their own limit.
    want_loss, want, want_chosen = job.ReferenceSteps(
        config, AFMOE_SEQUENCES).loss_and_grads(layers, *batch,
                                                imposed=chosen)
    differ = job.choices_that_differ(chosen, want_chosen)
    print(f"afmoe: reference loss {want_loss:.6f} "
          f"({time.monotonic() - t0:.0f} s); {100 * differ:.3f} % of "
          f"the program's routing choices are not the reference's",
          flush=True)
    check(math.isfinite(loss) and abs(loss - want_loss) <= AFMOE_LOSS_TOL,
          f"afmoe: loss {loss} against the reference's {want_loss}")
    check(differ <= config["correct"]["choices_limit"],
          f"afmoe: {differ} of the routing choices are not the reference's")
    outside = job.leaves_outside(config, job.gradient_errors(grads, want))
    check(not outside, f"afmoe: gradients out of their limits: {outside}")
    return device


def phase_joyai() -> dict:
    """Both losses and every gradient leaf of the JoyAI-LLM-Flash cell's
    step at the published widths, program against reference, each leaf held
    to the limit the cell's own check has for it."""
    device = require_platform()
    import jax
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.models import joyai_flash
    hvd.init()  # the compile cache
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from harness import manifest as mf
    job = mf.load_module("jobs", "joyai_flash")
    with open(os.path.join(REPO, JOYAI_CONFIG)) as f:
        config = json.load(f)
    cfg = job.model_config(config)
    params = job.seeded_params(config, SEED)
    batch = job.seeded_batch(config, SEED, JOYAI_SEQUENCES)
    t0 = time.monotonic()
    (_, (aux, *got)), grads = jax.jit(jax.value_and_grad(
        lambda p, *b: joyai_flash.loss_fn(p, *b, cfg), has_aux=True))(
            params, *batch)
    got = [float(x) for x in got]
    # To the host, and the stacked tree gone, before the reference's own
    # 5.4 GB of parameters and gradients.
    grads, chosen = jax.tree_util.tree_map(np.asarray, (grads, aux.chosen))
    print(f"joyai: program L_main {got[0]:.6f} L_mtp {got[1]:.6f}, pairs "
          f"routed to the held experts by expert layer, the MTP block last, "
          f"{np.asarray(aux.routed_here).tolist()} "
          f"({time.monotonic() - t0:.0f} s)", flush=True)
    layers = job.unstacked(params)
    del params, aux
    t0 = time.monotonic()
    # As the cell's check: the reference follows the program's choices, and
    # those are held to its own by their own limit.
    want, want_grads, want_chosen = job.ReferenceSteps(
        config, JOYAI_SEQUENCES).loss_and_grads(layers, *batch,
                                                imposed=chosen)
    want = [want[0], want[1] / cfg.mtp_loss_weight]
    differ = job.choices_that_differ(chosen, want_chosen)
    print(f"joyai: reference L_main {want[0]:.6f} L_mtp {want[1]:.6f} "
          f"({time.monotonic() - t0:.0f} s); {100 * differ:.3f} % of "
          f"the program's routing choices are not the reference's",
          flush=True)
    check(all(math.isfinite(mine) and abs(mine - theirs) <= JOYAI_LOSS_TOL
              for mine, theirs in zip(got, want)),
          f"joyai: losses {got} against the reference's {want}")
    check(differ <= config["correct"]["choices_limit"],
          f"joyai: {differ} of the routing choices are not the reference's")
    outside = job.leaves_outside(config,
                                 job.gradient_errors(grads, want_grads))
    check(not outside, f"joyai: gradients out of their limits: {outside}")
    return device


def seconds_a_call(f, *args) -> float:
    """The host's clock around ``KDA_REPEATS`` calls of a compiled ``f``,
    after one that compiles it."""
    import jax
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(KDA_REPEATS):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / KDA_REPEATS


def phase_kda() -> dict:
    """The scan kernels of ``parallel/kda.py`` alone at the Kimi-Linear
    cell's shapes against the recurrence they stand for (output and all five
    gradients), and their microseconds a chunk, forward and backward, by the
    host's clock around ``KDA_REPEATS`` calls; then what surrounds the scan
    (:func:`kda_passes`)."""
    device = require_platform()
    import jax
    import jax.numpy as jnp
    from horovod_tpu.parallel import kda
    seq, heads, d = KDA_SEQ, KDA_HEADS, KDA_D
    keys = jax.random.split(jax.random.PRNGKey(SEED), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (seq, heads, d)
    q = (unit(jax.random.normal(keys[0], shape)) * d ** -0.5).astype(
        jnp.bfloat16)
    # Keys that share a direction, as the positions of a seeded decoder's
    # deeper layers do (a chunk's keys 0.9 alike): the triangular system of
    # a chunk is then far from the identity.
    k = unit(jax.random.normal(keys[1], shape) + 3.0 * jax.random.normal(
        jax.random.PRNGKey(SEED + 2), (1, heads, d))).astype(jnp.bfloat16)
    v = jax.nn.silu(jax.random.normal(keys[2], shape)).astype(jnp.bfloat16)
    # The seeded gates' range: A in [1, 16) a head, dt in [1e-3, 0.1) a
    # channel, a unit normal in front of the softplus.
    rate = jax.random.uniform(keys[3], (heads, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(keys[4], (heads, d),
                                    minval=math.log(1e-3),
                                    maxval=math.log(0.1)))
    g = -rate * jax.nn.softplus(jax.random.normal(keys[5], shape)
                                + jnp.log(jnp.expm1(dt)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[6], (seq, heads)))
    weight = jax.random.normal(jax.random.PRNGKey(SEED + 1), shape)

    def recurrence(q, k, v, g, beta):
        pieces = lambda x: x.reshape(seq // KDA_SEGMENT, KDA_SEGMENT,
                                     *x.shape[1:])

        @jax.checkpoint
        def segment(state, rows):
            o, state = kda.kda_recurrence(*rows, state=state)
            return state, o

        _, o = jax.lax.scan(
            segment, jnp.zeros((heads, d, d), jnp.float32),
            tuple(pieces(x) for x in (q, k, v, g, beta)))
        return o.reshape(shape)

    forward = jax.jit(kda.kda_scan)
    # The weight is an argument: closed over, its 134 MB would be compiled
    # into both executables.
    both = lambda f: jax.jit(jax.value_and_grad(
        lambda q, k, v, g, beta, weight: jnp.sum(
            f(q, k, v, g, beta).astype(jnp.float32) * weight),
        argnums=(0, 1, 2, 3, 4)))
    program, plain = both(kda.kda_scan), both(recurrence)
    args = (q, k, v, g, beta)
    got = jax.block_until_ready(forward(*args))
    want = jax.jit(recurrence)(*args)
    error, size = max_abs_error(got, want)
    print(f"kda: output off the recurrence by at most {error:.2e} of "
          f"{size:.2e} (tolerance {KDA_OUT_TOL:g} of that; decays down to "
          f"{float(g.min()):.1f} a step, {float(g.sum(0).min() / seq * kda.CHUNK):.1f} "
          f"a chunk)", flush=True)
    check(error <= KDA_OUT_TOL * size, f"kda: output off by {error}")
    (_, grads), (_, want_grads) = program(*args, weight), plain(*args,
                                                                weight)
    for name, mine, theirs in zip(("q", "k", "v", "g", "beta"), grads,
                                  want_grads):
        mine, theirs = (x.astype(jnp.float32) for x in (mine, theirs))
        off = float(jnp.linalg.norm(mine - theirs)
                    / jnp.linalg.norm(theirs))
        print(f"kda: gradient of {name} off by {off:.2e} in the 2-norm "
              f"(tolerance {KDA_GRAD_TOL:g})", flush=True)
        check(off <= KDA_GRAD_TOL, f"kda: gradient of {name} off by {off}")

    count = kda.chunks(seq, kda.CHUNK, heads)[1]
    t_forward = seconds_a_call(forward, *args)
    t_both = seconds_a_call(program, *args, weight)
    print(f"kda: {1e6 * t_forward / count:.2f} us a chunk forward, "
          f"{1e6 * (t_both - t_forward) / count:.2f} us a chunk backward "
          f"({count} chunks of {kda.CHUNK} a sequence of {seq} and {heads} "
          f"heads; {1e3 * t_forward:.1f} ms and {1e3 * t_both:.1f} ms a "
          f"call, the second forward and backward together)", flush=True)
    kda_passes(seq, heads, d)
    return device


def kda_passes(seq, heads, d) -> None:
    """The passes in front of the scan (three convolutions with SiLU, two
    L2 norms, the decay) and behind it (the gated norm) at one (layer,
    sequence) of the cell: held to ``models/kimi_linear.py``'s plain
    definitions, and their milliseconds forward and backward against the
    bytes they move."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import kimi_linear
    from horovod_tpu.models.sdar_moe import rms_norm
    from horovod_tpu.parallel import kda_surround as surround
    wide, f32 = heads * d, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED + 3), 24))
    rows = lambda dtype=jnp.bfloat16, scale=1.0: (scale * jax.random.normal(
        next(keys), (seq, wide))).astype(dtype)
    by_head = lambda t: t.reshape(seq, heads, d)
    flat = lambda t: t.reshape(seq, wide)
    units = (d ** -0.5, 1.0, None)

    def plain_front(xq, xk, xv, xd, wq, wk, wv, dt_bias, a_log):
        made = []
        for x, w, unit in zip((xq, xk, xv), (wq, wk, wv), units):
            a = kimi_linear.short_conv(x.astype(f32), w)
            made.append(a if unit is None else flat(
                kimi_linear._unit(by_head(a), 1e-6) * unit))
        return (*made, flat(-jnp.exp(a_log)[None, :, None] * by_head(
            jax.nn.softplus(xd.astype(f32) + dt_bias))))

    def front(xq, xk, xv, xd, wq, wk, wv, dt_bias, a_log):
        return (*(surround.short_conv_silu(x, w, heads, unit, 1e-6)
                  for x, w, unit in zip((xq, xk, xv), (wq, wk, wv), units)),
                surround.decay(xd, dt_bias, a_log, heads))

    def plain_out(o, gate, weight):
        return flat(rms_norm(by_head(o.astype(f32)), weight, 1e-5)) \
            * jax.nn.sigmoid(gate.astype(f32))

    def out(o, gate, weight):
        return (surround.gated_norm(o, gate, weight, heads, 1e-5),)

    # The seeded gates' range, as the scan's operands above.
    dt = jnp.exp(jax.random.uniform(next(keys), (wide,), minval=math.log(
        1e-3), maxval=math.log(0.1)))
    passes = {
        "front": (front, plain_front, (
            rows(), rows(), rows(), rows(),
            *(0.5 * jax.random.normal(next(keys), (wide, 4))
              for _ in range(3)), jnp.log(jnp.expm1(dt)),
            jnp.log(jax.random.uniform(next(keys), (heads,), minval=1.0,
                                       maxval=16.0))),
            (rows(), rows(), rows(), rows(f32)),
            ("q", "k", "v", "g", "x_q", "x_k", "x_v", "x_decay", "conv_q",
             "conv_k", "conv_v", "dt_bias", "A_log")),
        "out": (out, lambda *a: (plain_out(*a),), (
            rows(scale=0.1), rows(scale=2.0),
            1 + 0.2 * jax.random.normal(next(keys), (d,))), (rows(),),
            ("y", "o", "gate", "o_norm"))}
    # Operands and cotangents are arguments: closed over, their 67 to 134
    # MB each would be compiled into the executables.
    both = lambda f: jax.jit(lambda args, cts: (
        lambda made, vjp: (*made, *vjp(cts)))(*jax.vjp(f, *args)))
    for which, (fast, plain, args, cotangents, names) in passes.items():
        got = both(fast)(args, cotangents)
        want = both(plain)(args, tuple(c.astype(f32) for c in cotangents))
        for name, mine, theirs in zip(names, got, want):
            mine, theirs = mine.astype(f32), theirs.astype(f32)
            check(bool(jnp.isfinite(mine).all()),
                  f"kda: non-finite {name} from the {which} pass")
            off = float(jnp.linalg.norm(mine - theirs)
                        / jnp.linalg.norm(theirs))
            print(f"kda: {which} pass, {name} off its definition by "
                  f"{off:.2e} in the 2-norm (tolerance {KDA_PASS_TOL:g})",
                  flush=True)
            check(off <= KDA_PASS_TOL, f"kda: {which} pass: {name} off by "
                                       f"{off}")
        del got, want
        # The backward kernels alone: nothing reads the forward's results.
        times = (seconds_a_call(jax.jit(fast), *args),
                 seconds_a_call(jax.jit(lambda args, cts: jax.vjp(
                     fast, *args)[1](cts)), args, cotangents))
        print(f"kda: the {which} pass " + ", ".join(
            f"{1e3 * t:.3f} ms {way} ({size * seq * wide / 1e6:.0f} MB: "
            f"{100 * size * seq * wide / t / HBM_BYTES_PER_S:.1f} % of "
            f"{HBM_BYTES_PER_S / 1e9:.0f} GB/s)" for way, t, size in zip(
                ("forward", "backward"), times, KDA_PASS_BYTES[which]))
              + f" a (layer, sequence) of {seq} x {wide}, row blocks of "
              f"{surround.ROWS}", flush=True)


CHILD_PHASES = {"kernels": phase_kernels, "dp4": phase_dp4,
                "sdar": phase_sdar, "afmoe": phase_afmoe,
                "joyai": phase_joyai, "kda": phase_kda}


# ---------------------------------------------------------------------------
# The parent: never touches a JAX backend
# ---------------------------------------------------------------------------

def stop(proc: subprocess.Popen) -> None:
    """Kill a child and whatever it started (it leads its own session)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait()


@contextlib.contextmanager
def child(name: str, cmd):
    """``cmd`` as a child with its output piped here, killed with whatever
    it started when the phase's limit runs out or the block is left."""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        filter(None, [REPO, os.environ.get("PYTHONPATH")])))
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    timer = threading.Timer(LIMITS[name], stop, [proc])
    timer.start()
    try:
        yield proc
    finally:
        timer.cancel()
        stop(proc)


def run_child(name: str, cmd) -> str:
    """Run ``cmd`` to its end inside the phase's limit, passing its output
    through; returns that output.  Non-zero exit or timeout fails."""
    lines = []
    with child(name, cmd) as proc:
        for line in proc.stdout:
            print(line, end="", flush=True)
            lines.append(line)
        rc = proc.wait()
    check(rc == 0, f"{name}: exit code {rc} from {' '.join(cmd)}")
    return "".join(lines)


def child_report(name: str) -> dict:
    out = run_child(name, [sys.executable, os.path.abspath(__file__),
                           "--phase", name])
    last = out.rstrip("\n").rsplit("\n", 1)[-1]
    check(last.startswith(REPORT), f"{name}: no report line")
    return json.loads(last[len(REPORT):])


def run_trainer() -> None:
    out = run_child("trainer", TRAINER_CMD)
    platform = re.search(r"platform (\w+)", out)
    losses = re.search(
        r"Loss after warm-up: (\S+), after \d+ more steps: (\S+)", out)
    check(platform is not None and losses is not None,
          "trainer: no platform or loss line")
    check(platform.group(1) == PLATFORM,
          f"trainer ran on {platform.group(1)!r}, not {PLATFORM!r}")
    warm, final = (float(x) for x in losses.groups())
    check(math.isfinite(warm) and math.isfinite(final),
          f"trainer: non-finite loss ({warm}, {final})")
    check(warm != final, "trainer: the loss did not move in five steps")
    print(f"trainer: losses {warm} -> {final} on {PLATFORM}", flush=True)


def http(port: int, path: str, payload=None, timeout: float = 300):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(
            urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                   data=data), timeout=timeout) as resp:
        check(resp.status == 200, f"{path}: HTTP {resp.status}")
        return resp.read().decode()


def run_server() -> None:
    with child("server", SERVER_CMD) as proc:
        port = None
        for line in proc.stdout:
            print(line, end="", flush=True)
            banner = re.search(r"listening on :(\d+)", line)
            if banner:
                port = int(banner.group(1))
                break
        check(port is not None, "server: exited before its banner")

        rng = random.Random(SEED)
        prompts = [[rng.randrange(VOCAB) for _ in range(n)]
                   for n in PROMPT_LENGTHS + (TWIN_LENGTH,)]
        prompts.append(list(prompts[-1]))  # the twin
        answers = [None] * len(prompts)

        def ask(i: int) -> None:
            answers[i] = json.loads(http(port, "/generate", {
                "tokens": prompts[i], "max_new_tokens": NEW_TOKENS}))

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        check(all(a is not None for a in answers),
              "server: a /generate request failed")
        for prompt, answer in zip(prompts, answers):
            tokens = answer["tokens"]
            check(len(tokens) == NEW_TOKENS
                  and all(0 <= t < VOCAB for t in tokens),
                  f"server: bad tokens for a {len(prompt)}-token prompt: "
                  f"{tokens}")
            print(f"server: {len(prompt):3d}-token prompt -> {tokens}",
                  flush=True)
        check(answers[-1]["tokens"] == answers[-2]["tokens"],
              "server: identical prompts gave different tokens")
        print("server: identical prompts gave identical tokens",
              flush=True)

        health = json.loads(http(port, "/healthz"))
        check(health["status"] == "ok", f"server: /healthz says {health}")
        impls = [r["attn_impl"] for r in health["replicas"]]
        check(impls == ["kernel"],
              f"server: attention {impls}, not the kernel 'auto' picks "
              f"on a TPU")
        counted = re.search(r"^hvd_serve_tokens_total (\d+)$",
                            http(port, "/metrics"), re.M)
        served = sum(len(a["tokens"]) for a in answers)
        check(counted is not None and int(counted.group(1)) == served,
              f"server: /metrics counts {counted and counted.group(1)} "
              f"tokens, {served} were served")
        print(f"server: healthz ok, attention {impls[0]}, /metrics counts "
              f"{served} tokens", flush=True)

        proc.send_signal(signal.SIGTERM)
        for line in proc.stdout:
            print(line, end="", flush=True)
        rc = proc.wait()
        check(rc == 0, f"server: exit code {rc} after SIGTERM")
        print("server: drained and exited 0 on SIGTERM", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)  # how the parent runs a child
    args = ap.parse_args(argv)
    started = time.monotonic()
    try:
        if args.phase:
            sys.path.insert(0, REPO)
            print(REPORT + json.dumps(CHILD_PHASES[args.phase]()),
                  flush=True)
            return 0
        def timed(name, phase, *phase_args):
            t0 = time.monotonic()
            result = phase(*phase_args)
            print(f"chip_smoke: {name} passed in "
                  f"{time.monotonic() - t0:.0f} s", flush=True)
            return result

        if args.chips == 4:
            device = timed("dp4", child_report, "dp4")
        else:
            device = timed("kernels", child_report, "kernels")
            timed("sdar", child_report, "sdar")
            timed("afmoe", child_report, "afmoe")
            timed("joyai", child_report, "joyai")
            timed("kda", child_report, "kda")
            timed("tile_times", run_child, "tile_times", AFMOE_TILE_TIMES)
            timed("trainer", run_trainer)
            timed("server", run_server)
        check(device["platform"] == PLATFORM
              and device["count"] == args.chips,
              f"device {device} is not {args.chips} x {PLATFORM}")
    except SmokeFailure as failure:
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke: every phase passed in "
          f"{time.monotonic() - started:.0f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
