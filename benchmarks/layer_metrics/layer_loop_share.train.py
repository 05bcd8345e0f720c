"""Device trace: self time of ``hvd::layer_loop`` (``models/sdar_moe.py:
through_layers``): what a (layer, sequence) costs outside the layer's own
parts, in both loops and both passes: the slices of the stacked
parameters, the sums of their gradients over a layer's sequences
(``add_any``), the auxiliary outputs and the copies the compiler feeds the
loops with; over the sum of all operations' durations
(``harness/parts.py``; the log has it by opcode).  Absent where the program
exports no such part."""

from harness import parts


def read(run):
    return parts.share(run, "hvd::layer_loop")
