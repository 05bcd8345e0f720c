#!/usr/bin/env python3
"""The three flash kernels alone on the chip, per call and per tile.

    python3 tools/flash_tile_times.py [--masks NAME ...] [--against other/flash.py ...]

One sequence at the language-model cells' sizes (8,192 positions, 32
query heads on 4 key/value heads of 128, tiles of 512; ``--widths 192 128
--kv-heads 32`` for latent attention's, whose keys are wider than its
values) through
``flash_attention`` forward and backward under block diffusion,
``MASK_CAUSAL``, ``MASK_STRICT``, ``MASK_NONE`` and the causal window of
2,048 (``--masks``: any of ``block_diffusion``, ``causal``, ``strict``,
``none``, ``window``; all five by default; a copy of ``flash.py`` that lacks
a mode is left out under it); times are the kernels' own events in a
device trace of ten calls (``benchmarks/harness/trace.py``).  ``--against``
names further copies of ``parallel/flash.py`` (a parent's, a variant's) to
time beside this tree's in the same process, and compares their output,
logsumexp and three gradients with this tree's: bit-equal, or the largest
absolute and relative difference of each.  Needs the TPU: a CPU run has
no device plane and prints no time.  PERF.md section 6, PR 28 and PR 30,
has the numbers this printed.
"""

import argparse
import collections
import importlib.util
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

SEQ, HEADS, KV_HEADS, HEAD_DIM, TILE, CALLS = 8192, 32, 4, 128, 512, 10
V_DIM = HEAD_DIM  # the values' width; the queries' and keys' is HEAD_DIM


def add_size_arguments(ap):
    ap.add_argument("--widths", nargs=2, type=int, metavar=("QK", "V"),
                    default=[HEAD_DIM, V_DIM],
                    help="head size of queries and keys, and of values "
                    "(latent attention: 192 128)")
    ap.add_argument("--kv-heads", type=int, default=KV_HEADS,
                    help="key/value heads (latent attention: 32)")


def set_sizes(args):
    global HEAD_DIM, V_DIM, KV_HEADS
    (HEAD_DIM, V_DIM), KV_HEADS = args.widths, args.kv_heads


def load(path):
    name = "flash_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    sys.modules[name] = module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


WINDOW = 2048


def mask_modes(flash):
    """The masks the tools run, by name, as ``flash`` spells them; the
    window where ``flash`` has one."""
    modes = {"block_diffusion": flash.block_diffusion_mask(4, SEQ // 2),
             "causal": flash.MASK_CAUSAL, "strict": flash.MASK_STRICT,
             "none": flash.MASK_NONE}
    if hasattr(flash, "window_mask"):
        modes["window"] = flash.window_mask(WINDOW)
    return modes


def differences(mine, theirs):
    """``name abs rel of-largest`` for each array of ``theirs`` that is not
    bit-equal to ``mine``'s: the largest absolute difference, the largest
    relative one (over the larger of an element's two magnitudes: a unit
    in the last place of bf16 reads up to 0.0078) and the absolute one over
    the array's largest magnitude (what a sum that cancels is held to)."""
    import numpy as np
    found = []
    for name in mine:
        a, b = (np.asarray(x[name], np.float64) for x in (mine, theirs))
        if not np.array_equal(a, b):
            gap, size = np.abs(a - b), np.maximum(np.abs(a), np.abs(b))
            found.append(f"{name} {gap.max():.3g} "
                         f"{(gap[size > 0] / size[size > 0]).max():.3g} "
                         f"{gap.max() / size.max():.3g}")
    return found


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import trace as tracing

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", nargs="*", default=[])
    ap.add_argument("--masks", nargs="*", default=[
        "block_diffusion", "causal", "strict", "none", "window"])
    add_size_arguments(ap)
    args = ap.parse_args()
    set_sizes(args)
    paths = [os.path.join(ROOT, "horovod_tpu", "parallel", "flash.py")] \
        + args.against
    rng = np.random.RandomState(0)
    q, k, v, weight = (jnp.asarray(rng.randn(1, SEQ, h, d), jnp.bfloat16)
                       for h, d in ((HEADS, HEAD_DIM), (KV_HEADS, HEAD_DIM),
                                    (KV_HEADS, V_DIM), (HEADS, V_DIM)))
    print("device", jax.devices()[0].device_kind, flush=True)
    for mode_name in args.masks:
        mine = None
        for path in paths:
            flash = load(path)
            mode = mask_modes(flash).get(mode_name)
            if mode is None:
                continue
            # ``weight`` is an argument: closed over, it would be compiled
            # into the executable (400 MB, half a minute a compile).
            def loss(q, k, v, weight):
                out, lse = flash.flash_attention_lse(
                    q, k, v, mask_mode=mode, block_q=TILE, block_k=TILE)
                return (out.astype(jnp.float32)
                        * weight.astype(jnp.float32)).sum(), (out, lse)

            call = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                              has_aux=True))
            (_, (out, lse)), grads = call(q, k, v, weight)
            got = dict(zip(("out", "lse", "dq", "dk", "dv"),
                           (out, lse) + grads))
            trace_dir = os.path.join(ROOT, "benchmarks_out", "flash_tiles")
            tracing.start(trace_dir)
            for _ in range(CALLS):
                last = call(q, k, v, weight)
            jax.block_until_ready(last)
            tracing.stop()
            ns, calls = collections.Counter(), collections.Counter()
            for plane in tracing.load(trace_dir).values():
                for event, _, duration in plane["ops"]:
                    kernel = re.search(r"hvd_flash_(fwd|bwd_dq|bwd_dkv)",
                                       event)
                    if kernel:
                        ns[kernel.group(0)] += duration
                        calls[kernel.group(0)] += 1
            tiles = HEADS * int(np.sum(
                [[flash.block_contributes(mode, a, a + TILE - 1, b,
                                          b + TILE - 1)
                  for b in range(0, SEQ, TILE)]
                 for a in range(0, SEQ, TILE)]))
            times = ", ".join(
                f"{name} {ns[name] / calls[name] / 1e6:.4f} ms a call, "
                f"{ns[name] / calls[name] / 1e3 / tiles:.3f} us a tile"
                for name in sorted(ns))
            same = "" if mine is None else (
                "; largest difference from this tree's (absolute, relative"
                ", over the largest): "
                + ("; ".join(differences(mine, got)) or "none, bit-equal"))
            mine = got if mine is None else mine
            print(f"{mode_name} {os.path.relpath(path, ROOT)} ({tiles} "
                  f"tiles a kernel): {times or 'no device plane'}{same}",
                  flush=True)


if __name__ == "__main__":
    main()
