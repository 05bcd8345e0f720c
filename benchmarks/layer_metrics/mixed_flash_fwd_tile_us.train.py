"""Device trace: microseconds the forward flash kernel takes a tile over a
stack of window and full layers.  Time: the ``hvd_flash_fwd`` custom calls
of the traced steps (``harness/scope_times.py``: ``by_kernel``); tiles:
those each layer's mask keeps, a query head, as the program counts them
(``mixed_flash_grid_steps_per_tile.train.py: by_layer``, a third of the
three kernels' tiles) x the sequences the traced steps trained, a layer
counted twice where its type is not among the program's
``models/afmoe.py: KEPT_ATTENTION`` (its forward kernel runs again in the
backward pass).  The forward kernel apart from the two backward ones,
which ``mixed_attention_roofline.train`` sums; a tile of 512 x 512 at head
size 128 is two products, 0.68 us of the MXU's time.  Absent without a
device trace, and where the step runs no such kernel or the program has no
window mode."""

from harness import manifest as mf
from harness import scope_times

KERNEL = "hvd_flash_fwd"


def read(run):
    t = scope_times.table(run)
    layers = mf.load_module(
        "layer_metrics", "mixed_flash_grid_steps_per_tile.train").by_layer(run)
    if t is None or not t["by_kernel"].get(KERNEL) or layers is None:
        return None
    from horovod_tpu.models import afmoe
    runs = {True: 2 - (afmoe.SLIDING in afmoe.KEPT_ATTENTION),
            False: 2 - (afmoe.FULL in afmoe.KEPT_ATTENTION)}
    tiles = sum(runs[window] * tiles // 3 for window, _, tiles in layers)
    sequences = t["programs"] * run.cell["traffic"]["images_per_chip"]
    return 1e6 * t["by_kernel"][KERNEL] / (tiles * sequences)
