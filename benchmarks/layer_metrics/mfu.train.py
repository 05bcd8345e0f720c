"""Model FLOP/s utilization: operations of forward and backward per sample
(``flops/<config>.py``, from shapes) times the window's samples per second,
over chips times the bf16 peak of the device kind.  Absent off the chip."""


def read(run):
    if run.peaks is None or run.flops is None:
        return None
    achieved = (run.flops.train_flops_per_sample(run.config)
                * run.results["end_to_end"]["train_samples_per_s"])
    return 100.0 * achieved / (run.chips * run.peaks["bf16_flops_per_s"])
