"""Program counter: grid steps the three flash kernels (``hvd_flash_fwd``,
``hvd_flash_bwd_dq``, ``hvd_flash_bwd_dkv``) launch along their sequential
dimension for one (layer, sequence) at the cell's sizes, over the tiles
they compute there, as the program counts both
(``horovod_tpu/parallel/flash.py: grid_steps``, the lengths its own grids
are sized from).  1.0 means no step that computes nothing; a rectangle
padded to the longest row reads 2.27 under this cell's mask.  A count: it
repeats exactly and reads the same on the CPU.  Absent where the program
exports no such count."""


def read(run):
    from horovod_tpu.parallel import flash
    if not hasattr(flash, "grid_steps"):
        return None
    config, assumed = run.config, run.config["assumed"]
    length = assumed["sequence_length"]["value"]
    tile = min(assumed["attention_tile"]["value"], 2 * length)
    steps, tiles = flash.grid_steps(
        flash.block_diffusion_mask(assumed["block_length"]["value"], length),
        2 * length, tile, tile, config["num_attention_heads"],
        config["num_key_value_heads"])
    return steps / tiles
