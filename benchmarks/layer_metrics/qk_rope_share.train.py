"""Device trace: time in operations traced under ``hvd::qk_rope``
(``models/sdar_moe.py: heads_first_qkv``, nested in a layer's attention
scope: every query and key head's RMSNorm, the rotary embedding and the
head-major layout as one pass over the projection's output, the Pallas
kernels ``hvd_qk_rope_fwd`` and ``hvd_qk_rope_bwd`` of
``parallel/qk_rope.py`` and whatever else the compiler leaves under the
span), forward, recomputed and backward, over the sum of all operations'
durations.  It says that the pass engages and what it costs; what it
replaced shows as the fall of the attention shares.  Absent where the
program writes no such scope (the parent, the cells without QK-norm), and
without a device trace."""

from harness import scope_times


def read(run):
    return scope_times.share_under(run, "hvd::qk_rope")
