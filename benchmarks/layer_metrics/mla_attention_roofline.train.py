"""Device trace: share of the chip's bf16 peak that the flash kernels reach
under latent attention, keys 192 and values 128 wide.  Operations:
``flops/joyai_flash.py``'s ``attention``, the **attended pairs** under the
causal mask x ``2 x (qk_head_dim + v_head_dim)`` x 3 (backward twice the
forward) x heads x layers (the MTP module's block among them) x the
sequences the traced steps trained; time: the ``hvd_flash_fwd``,
``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` custom calls.  The kernels do
more than is counted (the masked part of the tiles on the mask's edge, the
forward pass again where a layer's output is not kept across the
recomputation, scores and probabilities again in both backward kernels, and
the MXU's second pass over a key of one and a half lane widths, which costs
what 256 would), so this cannot pass 100; compute-bound."""

from harness import scope_times


def read(run):
    return scope_times.share_of_peak(
        run, "attention",
        ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"))
