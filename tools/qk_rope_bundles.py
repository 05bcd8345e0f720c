#!/usr/bin/env python3
"""The QK-norm and rotary pass's instruction schedules, counted without the chip.

    python3 tools/qk_rope_bundles.py [--rows 256 512 1024] [--heads 32 4] [--tables bfloat16] [--plain]

Compiles ``qk_rope.qk_norm_rope`` forward and backward over one sequence at
the language-model cells' sizes (8,192 positions, heads of 128, bf16) for a
*described* v5e (no chip; the method of ``tools/flash_bundles.py``, whose
reader of the compiler's final schedules this takes) and prints, for each
row block of ``--rows`` (``qk_rope.ROWS`` set to it) and each of ``--heads``,
the regions over 30 bundles of ``hvd_qk_rope_fwd`` and ``hvd_qk_rope_bwd``
with what they hold (loads, stores, lane reductions, ``vrsqrt``, lane
rotations ``vrot``, lane permutes), then one line a kernel: the bundles of
its body (its largest region), per block and per 512 rows, and the blocks a
call launches.  ``--tables`` gives the two rotary tables another dtype
(float32 as the models make them); ``--plain`` leaves the rotation out (a
layer without positions).

A count, not a time: it orders variants of the pass (row block, tables'
dtype) before a chip call is spent on them; a bundle issues in a cycle at
best (1.5 GHz), the flash kernels measure 1.3 to 1.7 cycles a bundle, and a
grid step costs about 0.35 us whatever it holds (PERF.md section 6).
"""

import argparse
import glob
import os
import re
import subprocess
import sys
import tempfile

import flash_bundles  # beside this file: the schedules' reader

ROOT = flash_bundles.ROOT
SEQ, HEAD_DIM = 8192, 128
COUNTED = re.compile(
    r"= (vst|vld|vrsqrt|vrot|vperm|v(?:max|add)\.xlane)")


def compile_in_child(rows, heads, tables_dtype, plain):
    """Compile the pass forward and backward for the described chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from horovod_tpu.parallel import qk_rope
    qk_rope.ROWS = rows
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                       sharding=chip)
    table = shaped((SEQ, HEAD_DIM), jnp.dtype(tables_dtype))
    jax.jit(jax.value_and_grad(
        lambda x, w, tables, dy: (qk_rope.qk_norm_rope(
            x, w, tables, heads, 1e-6, interpret=False).astype(jnp.float32)
            * dy).sum(), argnums=(0, 1))).lower(
        shaped((SEQ, heads * HEAD_DIM), jnp.bfloat16),
        shaped((HEAD_DIM,), jnp.float32), None if plain else (table, table),
        shaped((heads, SEQ, HEAD_DIM), jnp.bfloat16)).compile()


def schedules_of(rows, heads, tables_dtype, plain):
    """``{kernel: regions}`` of the two kernels; empty where the compiler
    wrote no schedule."""
    with tempfile.TemporaryDirectory() as dump:
        subprocess.run(
            [sys.executable, __file__, "--child", "--rows", str(rows),
             "--heads", str(heads), "--tables", tables_dtype]
            + ["--plain"] * plain,
            env=dict(os.environ, LIBTPU_INIT_ARGS=(
                f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true"
                " --xla_jf_dump_llo_pass_label_regex=final_bundles")),
            capture_output=True)
        return {re.search(r"hvd_qk_rope_\w+?(?=_*\.)", name).group(0):
                flash_bundles.regions(name, COUNTED)
                for name in glob.glob(os.path.join(
                    dump, "*hvd_qk_rope*-final_bundles.txt"))}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", nargs="*", type=int, default=[512])
    ap.add_argument("--heads", nargs="*", type=int, default=[32, 4])
    ap.add_argument("--tables", default="float32")
    ap.add_argument("--plain", action="store_true")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return compile_in_child(args.rows[0], args.heads[0], args.tables,
                                args.plain)
    for rows in args.rows:
        for heads in args.heads:
            kernels = schedules_of(rows, heads, args.tables, args.plain)
            print(f"rows {rows} heads {heads} tables {args.tables}"
                  + (" no rotation" if args.plain else "")
                  + ("" if kernels else ": no schedule was written"))
            for kernel, parts in sorted(kernels.items()):
                for bundles, ops in parts:
                    print(f"  {kernel} {bundles} bundles: "
                          + ", ".join(f"{op} {n}" for op, n in ops.items()))
            for kernel, parts in sorted(kernels.items()):
                body = max(bundles for bundles, _ in parts)
                print(f"  {kernel}: body {body} bundles a block, "
                      f"{body * 512 / rows:.0f} for 512 rows, "
                      f"{SEQ // rows * heads} blocks a call")


if __name__ == "__main__":
    main()
