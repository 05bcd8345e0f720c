"""Program counter: tiles the three flash kernels compute for a
``sliding_attention`` layer over the tiles they compute for a
``full_attention`` one, a sequence at the cell's sizes, as the program
counts them (``horovod_tpu/parallel/flash.py: grid_steps`` under
``window_mask(sliding_window)`` and under ``MASK_CAUSAL``).  The band of
2,048 in 8,192 positions at tiles of 512 reads 70 / 136 = 0.515; a window
that is masked and not skipped reads 1.0.  A count: it repeats exactly and
reads the same on the CPU.  Absent where the program has no window mode."""


def read(run):
    from horovod_tpu.parallel import flash
    if not hasattr(flash, "window_mask"):
        return None
    config, assumed = run.config, run.config["assumed"]
    seq = assumed["sequence_length"]["value"]
    tile = min(assumed["attention_tile"]["value"], seq)
    tiles = lambda mode: flash.grid_steps(
        mode, seq, tile, tile, config["num_attention_heads"],
        config["num_key_value_heads"])[1]
    return tiles(flash.window_mask(config["sliding_window"])) \
        / tiles(flash.MASK_CAUSAL)
