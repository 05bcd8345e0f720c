#!/usr/bin/env python3
"""A training cell's compiled step and its memory, without the chip.

    python3 tools/step_memory.py <cell> [NAME=VALUE ...] [--root DIR]

Builds the cell's ``Program`` (``benchmarks/jobs/<job>.py``) as the
benchmark does, hands ``hvd.shard_step`` a mesh of one *described* v5e chip
and compiles the step at the cell's real sizes from shapes alone (no chip,
nothing runs: ``tests/test_tpu_compile.py`` has the method).  Prints the
TPU compiler's own total for the program (its ``memory-usage-report``, the
number that must stay under the allocator's 15.75 GiB;
``compiled.memory_analysis()`` overcounts temporaries), how often XLA had to
re-lay or recompute a value to fit (``remat_``; fusions it runs a second
time are ``<name>.remat``, listed with their shapes), the Pallas calls in the
text, and every bf16 value shaped like a stack of the held experts'
matrices (a copy of the float32 parameters that lives through the step).  A
step that does not fit fails with the compiler's list of the largest
buffers.

``NAME=VALUE`` sets a constant of the cell's model module before the step
is traced, the value evaluated in that module
(``"KEPT_ATTENTION=(FULL,)"``, ``KEEP_ATTENTION=False``); ``--root`` takes
another checkout's program and benchmark (a copy of the parent commit);
``--dump DIR`` keeps XLA's dump (the buffer assignment, the text).  One to
two minutes a step; several at once need ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``.
A compile is never a time.
"""

import argparse
import functools
import glob
import importlib
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cell")
    parser.add_argument("constants", nargs="*", metavar="NAME=VALUE")
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--dump")
    args = parser.parse_args()
    if args.dump:
        return report(args, args.dump)
    with tempfile.TemporaryDirectory(prefix="step_memory_") as dump:
        return report(args, dump)


def report(args, dump):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_dump_to={dump}")
    bench = os.path.join(args.root, "benchmarks")
    sys.path[:0] = [args.root, bench]

    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)
    import horovod_tpu as hvd
    from harness import manifest as mf

    with open(os.path.join(bench, "workloads", args.cell + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(bench, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    job = mf.load_module("jobs", config["job"])
    model = importlib.import_module("horovod_tpu.models." + config["job"])
    for constant in args.constants:
        name, value = constant.split("=", 1)
        setattr(model, name, eval(value, vars(model)))
        print(f"{config['job']}.{name} = {getattr(model, name)!r}")

    hvd.init()
    chip = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices[:1]
    mesh = Mesh(chip, ("hvd",))
    # The job asks the backend whether to compile its kernels and builds
    # its step over hvd's own mesh: steer both from here.
    jax.default_backend = lambda: "tpu"
    hvd.shard_step = functools.partial(hvd.shard_step, mesh=mesh)
    program = job.Program(config, cell["traffic"]["images_per_chip"], 0)

    def shaped(tree, spec):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    params = jax.eval_shape(lambda: job.seeded_params(config, 0))
    state = shaped((params, jax.eval_shape(program.optimizer.init, params)),
                   P())
    start = time.monotonic()
    compiled = program.compiled.lower(
        *state, *shaped(program.batch, P("hvd"))).compile()
    text = compiled.as_text()
    print(f"compiled in {time.monotonic() - start:.0f} s")
    for path in glob.glob(os.path.join(
            dump, "*jit_local_step*memory-usage-report.txt")):
        with open(path) as f:
            print("the compiler's report:", f.readline().strip())
    print("values XLA re-laid or recomputed to fit:",
          len(re.findall(r"^\s*%\S*remat_\S* = ", text, re.M)))
    # Fusions the compiler runs a second time rather than keep their result
    # (``<name>.remat``), with more than a vector to make.
    again = re.findall(r"^\s*%(\S+\.remat\d*) = \(?(\w+\[\d+,\d[\d,]*\])"
                       r".*?op_name=\"([^\"]*)\"", text, re.M)
    print("fusions run again to fit:", len(again))
    for name, shape, op_name in again:
        print(f"  {name} {shape} {op_name[-70:]}")
    print("Pallas calls:", {k: len(re.findall(
        rf"custom-call\(.*{k}", text)) for k in (
            "hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv",
            "hvd_gmm", "hvd_tgmm", "hvd_qk_rope_fwd", "hvd_qk_rope_bwd",
            "hvd_kda_fwd", "hvd_kda_bwd", "hvd_kda_conv_fwd",
            "hvd_kda_conv_bwd", "hvd_kda_decay_fwd", "hvd_kda_decay_bwd",
            "hvd_kda_out_fwd", "hvd_kda_out_bwd")})
    z = job.sizes(config)
    held, d, f = z["held"], z["d"], z["width"]
    stacks = sorted(set(re.findall(
        rf"= (bf16\[(?:\d+,)?{held},(?:{d},{f}|{f},{d})\])", text)))
    print("bf16 values shaped like the held experts' matrices:",
          stacks or "none")


if __name__ == "__main__":
    main()
