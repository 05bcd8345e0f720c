"""Device trace: microseconds the forward flash kernel takes a tile.  Time:
the ``hvd_flash_fwd`` custom calls of the traced steps
(``harness/scope_times.py``: ``by_kernel``); tiles: those the mask keeps, a
query head, as the program counts them (``horovod_tpu/parallel/flash.py:
grid_steps`` at the cell's sizes, a third of its three kernels' tiles) x
layers x the sequences the traced steps trained.  The kernel runs once a
(layer, sequence): its output and logsumexp are kept across the
recomputation.  A tile of 512 x 512 at head size 128 is two products,
0.68 us of the MXU's time.  Absent without a device trace, and where the
step runs no such kernel or the program exports no count."""

from harness import scope_times

KERNEL = "hvd_flash_fwd"


def read(run):
    from horovod_tpu.parallel import flash
    t = scope_times.table(run)
    if t is None or not t["by_kernel"].get(KERNEL) \
            or not hasattr(flash, "grid_steps"):
        return None
    config, assumed = run.config, run.config["assumed"]
    length = assumed["sequence_length"]["value"]
    tile = min(assumed["attention_tile"]["value"], 2 * length)
    _, tiles = flash.grid_steps(
        flash.block_diffusion_mask(assumed["block_length"]["value"], length),
        2 * length, tile, tile, config["num_attention_heads"],
        config["num_key_value_heads"])
    sequences = t["programs"] * run.cell["traffic"]["images_per_chip"]
    return 1e6 * t["by_kernel"][KERNEL] / (
        tiles // 3 * config["num_hidden_layers"] * sequences)
