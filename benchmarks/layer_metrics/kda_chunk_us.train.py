"""Device trace: microseconds the scan kernels of Kimi Delta Attention take
a chunk: the time under ``hvd::kda_attention::scan`` (the forward kernel,
its second run where a layer is recomputed, and the backward kernel) over
the chunks of the traced steps, as the program counts them
(``kda_grid_steps_per_chunk.train.py: counted``: 32 heads x 128 chunks a
layer and sequence at 8,192 positions) x the KDA layers x the sequences the
traced steps trained.  What ``flash_fwd_tile_us.train`` is to a tile; on
the v5e a chunk alone takes 2.0 us forward and 2.5 us backward
(``chip_smoke.py --phase kda``, PR 37).  Absent without a device trace, and
where the program writes no such scope or exports no such count."""

from harness import manifest as mf
from harness import scope_times

SCOPE = mf.load_module("layer_metrics", "kda_scan_share.train").SCOPE


def read(run):
    t = scope_times.table(run)
    found = mf.load_module(
        "layer_metrics", "kda_grid_steps_per_chunk.train").counted(run)
    if t is None or found is None or not t["by_scope"].get(SCOPE):
        return None
    layers = len(run.config["linear_attn_config"]["kda_layers"])
    sequences = t["programs"] * run.cell["traffic"]["images_per_chip"]
    return 1e6 * t["by_scope"][SCOPE] / (found[1] * layers * sequences)
