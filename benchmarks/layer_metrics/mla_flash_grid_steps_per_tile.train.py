"""Program counter: grid steps the three flash kernels (``hvd_flash_fwd``,
``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``) launch along their sequential
dimension over the tiles they compute, one sequence through one layer of
latent attention at the cell's sizes (``horovod_tpu/parallel/flash.py:
grid_steps`` under ``MASK_CAUSAL``, as many key/value heads as query
heads): 136 tiles a head at 8,192 positions in tiles of 512, 16 of them on
the mask's edge.  1.0 means no step that computes nothing; a rectangle
padded to the longest row reads 1.88.  A count: it repeats exactly and reads
the same on the CPU.  Absent where the program exports no such count."""


def tiles_a_head(run):
    """``(grid steps, tiles)`` of the three kernels, a layer and sequence,
    or ``None``."""
    from horovod_tpu.parallel import flash
    if not hasattr(flash, "grid_steps"):
        return None
    config, assumed = run.config, run.config["assumed"]
    seq = assumed["sequence_length"]["value"]
    tile = min(assumed["attention_tile"]["value"], seq)
    heads = config["num_attention_heads"]
    return flash.grid_steps(flash.MASK_CAUSAL, seq, tile, tile, heads, heads)


def read(run):
    counted = tiles_a_head(run)
    return None if counted is None else counted[0] / counted[1]
