#!/usr/bin/env python3
"""The KDA scan kernels' instruction schedules, counted without the chip.

    python3 tools/kda_bundles.py [--against other/kda.py ...]

Compiles ``kda.kda_scan`` forward and backward over one sequence at the
Kimi-Linear cell's sizes (8,192 positions, 32 heads of 128, bf16 ``q, k,
v``, float32 ``g`` and ``beta``) for a *described* v5e (no chip; the method
of ``tools/flash_bundles.py``, whose child process and reader of the
compiler's final schedules this takes) through this tree's
``parallel/kda.py`` and every ``--against`` copy, and prints, for each
copy, the regions over 30 bundles of ``hvd_kda_fwd`` and ``hvd_kda_bwd``
with what they hold (loads, stores, MXU pushes, exponents), then one line
a kernel: its unrolled loop of
``kda.UNROLL`` chunks (the region that pushes most to the MXU), in bundles,
``vmatmul``, ``vld``, ``vst`` and ``vpow2``, and bundles a chunk, for every
copy side by side.

A count, not a time: it orders variants of a chunk's chain of products
before a chip call is spent on them; a bundle issues in a cycle at best
(1.5 GHz), the scan kernels measure 1.7 to 2.6 cycles a bundle in the cell,
and bundles a variant takes out come back at under a cycle each, since the
chain's stalls grow into what they leave (PERF.md section 6).  The counted
loop is one grid step's: the number of heads and a sequence of whole
blocks do not change it.
"""

import argparse
import importlib.util
import os
import re
import sys

import flash_bundles  # beside this file: the schedules' reader

ROOT = flash_bundles.ROOT
SEQ, HEADS, HEAD_DIM = 8192, 32, 128
COUNTED = re.compile(r"= (vmatmul|vld|vst|vpow2)\b")
COLUMNS = ("vmatmul", "vld", "vst", "vpow2")


def load(path):
    """The copy of ``kda.py`` at ``path`` as a module of this tree's
    ``horovod_tpu.parallel`` (its relative imports resolve there)."""
    import horovod_tpu.parallel  # noqa: F401  (the package it joins)
    name = "horovod_tpu.parallel._kda_" + re.sub(
        r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    sys.modules[name] = module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def compile_in_child(path):
    """Compile forward and backward through the copy of ``kda.py`` at
    ``path`` for the described chip."""
    chip = flash_bundles.described_chip()
    import jax
    import jax.numpy as jnp
    kda = load(path)
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                       sharding=chip)
    rows = shaped((SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
    jax.jit(jax.grad(
        lambda q, k, v, g, beta: kda.kda_scan(
            q, k, v, g, beta, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(
        rows, rows, rows, shaped((SEQ, HEADS, HEAD_DIM), jnp.float32),
        shaped((SEQ, HEADS), jnp.float32)).compile()


def loop(parts):
    """``(bundles, ops)`` of a kernel's unrolled loop of chunks: of its
    regions, the one that pushes most to the MXU."""
    return max(parts, key=lambda part: part[1].get("vmatmul", 0))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", nargs="*", default=[])
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return compile_in_child(args.child)
    sys.path.insert(0, ROOT)
    from horovod_tpu.parallel import kda
    paths = [os.path.join(ROOT, "horovod_tpu", "parallel", "kda.py")] \
        + args.against
    found = {path: flash_bundles.dump_schedules(
        "hvd_kda_", [__file__, "--child", path], COUNTED) for path in paths}
    for path, kernels in found.items():
        print(os.path.relpath(path, ROOT)
              + ("" if kernels else ": no schedule was written"))
        for kernel, parts in sorted(kernels.items()):
            for bundles, ops in parts:
                print(f"  {kernel} {bundles} bundles: "
                      + ", ".join(f"{op} {n}" for op, n in ops.items()))
    print(f"the unrolled loop of {kda.UNROLL} chunks: bundles (a chunk), "
          + ", ".join(COLUMNS) + "; by copy in the order above")
    for kernel in sorted(set().union(*found.values())):
        cells = []
        for kernels in found.values():
            if kernel not in kernels:
                cells.append("-")
                continue
            bundles, ops = loop(kernels[kernel])
            cells.append(f"{bundles} ({bundles / kda.UNROLL:.0f}), "
                         + ", ".join(str(ops.get(op, 0)) for op in COLUMNS))
        print(f"  {kernel} " + " | ".join(cells))


if __name__ == "__main__":
    main()
