"""Program counter: grid steps a scan kernel of Kimi Delta Attention
(``hvd_kda_fwd``, ``hvd_kda_bwd``: one grid, walked forward or in reverse)
launches along its sequential dimension over the chunks it computes, one
sequence through one KDA layer at the cell's sizes
(``horovod_tpu/parallel/kda.py: chunks``): 128 chunks of 64 positions a
head at 8,192.  1.0 means a grid step a chunk and none that computes
nothing; a step that walks several chunks of its block reads under 1 (512
rows a step: 0.125) and spares the steps' fixed cost.  A count: it repeats
exactly and reads the same on the CPU.  Absent where the program exports no
such count."""


def counted(run):
    """``(grid steps, chunks)`` of one scan kernel, a layer and sequence,
    or ``None``."""
    try:
        from horovod_tpu.parallel import kda
    except ImportError:
        return None
    linear, assumed = run.config["linear_attn_config"], run.config["assumed"]
    return kda.chunks(assumed["sequence_length"]["value"],
                      assumed["kda_chunk"]["value"], linear["num_heads"])


def read(run):
    found = counted(run)
    return None if found is None else found[0] / found[1]
