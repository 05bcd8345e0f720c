"""Device trace: share of the chip's bf16 peak that the grouped expert
products reach.  Operations: ``flops/sdar_moe.py``, the **expected**
(position, choice) pairs routed to the experts held x ``3 x hidden x
expert width`` multiply-adds x 3 (backward twice the forward) x the
sequences the traced steps trained; time: the ``hvd_gmm`` and ``hvd_tgmm``
custom calls (``parallel/grouped.py``).  The kernels do more than is
counted (whole row tiles where a group ends inside one, the forward
products again where a layer is recomputed), so this cannot pass 100;
compute-bound at 512 rows a tile."""

from harness import scope_times


def read(run):
    return scope_times.share_of_peak(run, "experts",
                                     ("hvd_gmm", "hvd_tgmm"))
