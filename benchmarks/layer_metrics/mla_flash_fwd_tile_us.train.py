"""Device trace: microseconds the forward flash kernel takes a tile under
latent attention, keys 192 and values 128 wide.  Time: the
``hvd_flash_fwd`` custom calls of the traced steps
(``harness/scope_times.py``: ``by_kernel``); tiles: those the causal mask
keeps, a query head, as the program counts them
(``mla_flash_grid_steps_per_tile.train.py: tiles_a_head``, a third of the
three kernels' tiles) x layers (the MTP module's block among them) x the
sequences the traced steps trained, twice where the program does not keep a
layer's flash output across the recomputation (``models/joyai_flash.py:
KEEP_ATTENTION``: its forward kernel runs again in the backward pass).  The
forward kernel apart from the two backward ones, which
``mla_attention_roofline.train`` sums; a tile of 512 x 512 is a product
over 192 and one over 128, 0.85 us of the MXU's time by the operations and
1.02 us by its passes (192 takes the two that 256 would).  Absent without a
device trace, and where the step runs no such kernel or the program has no
such model."""

from harness import manifest as mf
from harness import scope_times

KERNEL = "hvd_flash_fwd"


def read(run):
    t = scope_times.table(run)
    counted = mf.load_module(
        "layer_metrics", "mla_flash_grid_steps_per_tile.train"
    ).tiles_a_head(run)
    if t is None or not t["by_kernel"].get(KERNEL) or counted is None:
        return None
    try:
        from horovod_tpu.models import joyai_flash
    except ImportError:
        return None
    config = run.config
    layers = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    tiles = (2 - joyai_flash.KEEP_ATTENTION) * layers * counted[1] // 3
    sequences = t["programs"] * run.cell["traffic"]["images_per_chip"]
    return 1e6 * t["by_kernel"][KERNEL] / (tiles * sequences)
