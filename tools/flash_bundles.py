#!/usr/bin/env python3
"""The flash kernels' instruction schedules, counted without the chip.

    python3 tools/flash_bundles.py [--against other/flash.py ...]

Compiles ``flash_attention`` forward and backward at the language-model
cells' sizes (``--widths 192 128 --kv-heads 32``: latent attention's, keys
wider than values) under block diffusion, the causal window of 2,048,
``MASK_CAUSAL`` and ``MASK_NONE`` for a *described* v5e (no chip:
``tests/test_tpu_compile.py`` has the method), has the TPU compiler write
each kernel's final schedule (``--xla_jf_dump_llo_text``) and prints, for
every region of a kernel over 30 bundles (in the forward kernel: the set-up
of a query tile, the step on a tile the mask cuts, the step on a full tile,
the flush), its VLIW bundles and what they hold: stores, loads, MXU pushes,
exponents, lane reductions (``xlane``) and lane permutes (``vperm``: a
``[:, :1]`` column broadcast over the lanes costs one a register).  After
each mask, one line a kernel with the bundles of its two steps (on a tile
the mask cuts / on a full tile) for this tree's ``flash.py`` and every
``--against`` copy side by side: the difference of the two is what the mask
costs an edge tile.

A count, not a time: a bundle issues in a cycle at best (1.5 GHz), and the
kernels measure 1.3 to 1.7 cycles a bundle (PERF.md section 6, PR 30).  It
orders variants of one kernel before a chip call is spent on them;
``tools/flash_tile_times.py`` then times what is left.  The compiler aborts
after the dump (it looks for a report template that is not installed), so
each compile runs in a child process whose exit code is not read.
"""

import argparse
import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

import flash_tile_times as sizes  # beside this file: sizes, load, masks

ROOT = sizes.ROOT
MODES = ("block_diffusion", "window", "causal", "none")
COUNTED = re.compile(  # the lane units' pushes, not their ``vpop``s
    r"= (vst|vld|vmatmul|vmatpush|vpow2|vperm|v(?:max|add)\.xlane)")


def compile_in_child(path, mode_name):
    """Compile forward and backward through the copy of ``flash.py`` at
    ``path`` under ``mode_name``, for the described chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    flash = sizes.load(path)
    mode = sizes.mask_modes(flash)[mode_name]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    q, k, v = (jax.ShapeDtypeStruct((1, sizes.SEQ, heads, width),
                                    jnp.bfloat16, sharding=chip)
               for heads, width in ((sizes.HEADS, sizes.HEAD_DIM),
                                    (sizes.KV_HEADS, sizes.HEAD_DIM),
                                    (sizes.KV_HEADS, sizes.V_DIM)))
    jax.jit(jax.grad(
        lambda q, k, v: flash.flash_attention(
            q, k, v, mask_mode=mode, block_q=sizes.TILE, block_k=sizes.TILE,
            interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(q, k, v).compile()


def regions(path, counted=COUNTED):
    """``[(bundles, {opcode: count})]`` of the schedule's regions (between
    the control targets the dump marks) that hold over 30 bundles; the
    opcodes ``counted`` finds."""
    lines = [line for line in open(path)
             if re.match(r" *(0x)?[0-9a-f]+ ", line)]
    marks = [n for n, line in enumerate(lines)
             if re.match(r" *(0x)?[0-9a-f]+ +(PF|LB|LE|CT|PB)", line)]
    found = []
    for start, end in zip(marks, marks[1:] + [len(lines)]):
        if end - start > 30:
            ops = collections.Counter(
                re.sub(r"^v\w+\.xlane", "xlane", op)
                for op in counted.findall("".join(lines[start:end])))
            found.append((end - start, dict(sorted(ops.items()))))
    return found


def schedules_of(path, mode):
    """``{kernel: regions}`` of the three kernels compiled through the copy
    of ``flash.py`` at ``path`` under ``mode``; empty where the compiler
    wrote no schedule."""
    with tempfile.TemporaryDirectory() as dump:
        subprocess.run(
            [sys.executable, __file__, "--child", path, mode, "--widths",
             str(sizes.HEAD_DIM), str(sizes.V_DIM), "--kv-heads",
             str(sizes.KV_HEADS)],
            env=dict(os.environ, LIBTPU_INIT_ARGS=(
                f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true"
                " --xla_jf_dump_llo_pass_label_regex=final_bundles")),
            capture_output=True)
        return {re.search(r"hvd_flash_\w+?(?=_*\.)", name).group(0):
                regions(name)
                for name in glob.glob(os.path.join(
                    dump, "*hvd_flash*-final_bundles.txt"))}


def steps(found):
    """``cut / full``: the bundles of a kernel's two steps, which are its
    regions that push to the MXU, in ``_on_tile``'s order: the step on a
    tile the mask cuts, then the step on a full tile."""
    return " / ".join(str(bundles) for bundles, ops in found
                      if "vmatmul" in ops)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", nargs="*", default=[])
    ap.add_argument("--child", nargs=2, help=argparse.SUPPRESS)
    sizes.add_size_arguments(ap)
    args = ap.parse_args()
    sizes.set_sizes(args)
    if args.child:
        return compile_in_child(*args.child)
    paths = [os.path.join(ROOT, "horovod_tpu", "parallel", "flash.py")] \
        + args.against
    for mode in MODES:
        found = {path: schedules_of(path, mode) for path in paths}
        for path, kernels in found.items():
            print(f"{mode} {os.path.relpath(path, ROOT)}"
                  + ("" if kernels else ": no schedule was written"))
            for kernel, parts in sorted(kernels.items()):
                for bundles, ops in parts:
                    print(f"  {kernel} {bundles} bundles: "
                          + ", ".join(f"{op} {n}" for op, n in ops.items()))
        print(f"{mode}: a step on a tile the mask cuts / on a full tile, "
              "bundles, by copy in the order above")
        for kernel in sorted(set().union(*found.values())):
            print(f"  {kernel} " + " | ".join(
                steps(kernels.get(kernel, [])) or "-"
                for kernels in found.values()))


if __name__ == "__main__":
    main()
