"""Device trace: share of the chip's bf16 peak that the flash kernels reach
over a stack of window and full layers.  Operations: ``flops/afmoe.py``'s
``attention``, the **attended pairs under each layer's mask** x ``4 x
head_dim`` x 3 (backward twice the forward) x the sequences the traced
steps trained; time: the ``hvd_flash_fwd``, ``hvd_flash_bwd_dq`` and
``hvd_flash_bwd_dkv`` custom calls.  The kernels do more than is counted
(the masked part of the tiles on a mask's edge, the forward pass again
where a layer type is not kept across the recomputation, scores and
probabilities again in both backward kernels), so this cannot pass 100;
compute-bound: 128 operations a byte of K and V read."""

from harness import scope_times


def read(run):
    return scope_times.share_of_peak(
        run, "attention",
        ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"))
