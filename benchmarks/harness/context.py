"""What a runner and the readers are handed: one run of one cell."""

import os
import types

from . import manifest as mf
from . import result
from .compiles import CompileCounter
from .peaks import peaks_of


def open_run(workload: str, seed: int, seconds: float, trace: bool,
             process_start: float):
    """``(manifest, run)``: the cell's files read, the device checked
    against what the cell names (no result otherwise), the peaks looked up,
    the output directory made and the compile counter listening."""
    manifest = mf.Manifest()
    entry, cell, config = manifest.cell(workload)
    device = result.require_device(cell["platform"], entry["chips"])
    out_dir = os.path.join(mf.ROOT, "benchmarks_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    return manifest, types.SimpleNamespace(
        cell=cell, config=config, chips=entry["chips"], seed=seed,
        seconds=seconds, trace=trace, out_dir=out_dir, device=device,
        peaks=peaks_of(device["kind"]) if cell["platform"] == "tpu" else None,
        process_start=process_start, compiles=CompileCounter(),
        results={}, traced={},
        flops=(mf.load_module("flops", config["flops"])
               if "flops" in config else None))
