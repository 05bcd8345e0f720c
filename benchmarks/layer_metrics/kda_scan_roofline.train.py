"""Device trace: share of its roofline that the chunked scan of Kimi Delta
Attention reaches.  The least time the chip could take for a sample: the
larger of ``flops/kimi_linear.py``'s ``kda_recurrence`` (the recurrence's
own ``3 x d x d`` multiply-adds a token and head, forward and twice
backward, two operations each) over the bf16 peak, and its
``kda_scan_bytes`` (``q``, ``k``, ``v``, ``beta``, ``o`` at 2 bytes and
``g`` at 4, once forward and twice more backward with the gradients) over
the HBM's bandwidth (``harness/peaks.json``); times the sequences the traced
steps trained; over the time under ``hvd::kda_attention::scan``.  **The
bound is memory** at the cell's sizes: 4.84 GB a sequence over the four KDA
layers are 5.9 ms at 819 GB/s, 0.31 TFLOP are 1.6 ms at 197 TFLOP/s: the
first kernel of the benchmark whose ideal is the HBM's, not the MXU's.  The
kernels do more than is counted (the chunked form's products are about
twice the recurrence's, the forward kernel runs again where a layer is
recomputed, and it writes the chunks' states, 268 MB a layer and sequence,
which the backward kernel reads), so this cannot pass 100.  Absent where the
program writes no such scope, and without a device trace."""

from harness import manifest as mf
from harness import scope_times

SCOPE = mf.load_module("layer_metrics", "kda_scan_share.train").SCOPE


def read(run):
    t = scope_times.table(run)
    if t is None or run.peaks is None or run.flops is None \
            or not t["by_scope"].get(SCOPE):
        return None
    least = max(
        run.flops.train_flops_by_part(run.config)["kda_recurrence"]
        / run.peaks["bf16_flops_per_s"],
        run.flops.kda_scan_bytes(run.config) / run.peaks["hbm_bytes_per_s"])
    samples = t["programs"] * run.cell["traffic"]["images_per_chip"]
    return 100.0 * least * samples / t["by_scope"][SCOPE]
