"""Program counter: grid steps the three flash kernels (``hvd_flash_fwd``,
``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``) launch along their sequential
dimension over the tiles they compute, one sequence through a stack of
``sliding_attention`` and ``full_attention`` layers at the cell's sizes,
each layer under its own mask (``horovod_tpu/parallel/flash.py:
grid_steps`` under ``window_mask(sliding_window)`` and ``MASK_CAUSAL``,
summed over ``layer_types``).  1.0 means no step that computes nothing
under either mask; rectangles padded to the longest row read 1.38 here
(a band's rows are 1 to 5 tiles long, a triangle's 1 to 16).  A count: it
repeats exactly and reads the same on the CPU.  Absent where the program
has no window mode."""


def by_layer(run):
    """``[(window?, steps, tiles)]`` of the cell's layers, or ``None``."""
    from horovod_tpu.parallel import flash
    if not hasattr(flash, "window_mask"):
        return None
    config, assumed = run.config, run.config["assumed"]
    seq = assumed["sequence_length"]["value"]
    tile = min(assumed["attention_tile"]["value"], seq)
    counts = {
        window: flash.grid_steps(
            flash.window_mask(config["sliding_window"]) if window
            else flash.MASK_CAUSAL, seq, tile, tile,
            config["num_attention_heads"], config["num_key_value_heads"])
        for window in (True, False)}
    return [(kind == "sliding_attention",
             *counts[kind == "sliding_attention"])
            for kind in config["layer_types"]]


def read(run):
    layers = by_layer(run)
    if layers is None:
        return None
    return sum(steps for _, steps, _ in layers) \
        / sum(tiles for _, _, tiles in layers)
