"""Executor: model FLOP/s utilisation end to end. Forward and backward
FLOPs per token from shapes, times the tokens per second of the run's
whole epochs, over chips times the bf16 peak. Recomputation does not
count. It is not a kernel's roofline share."""

from benchmarks.lib import readers


def read(run):
    got = readers.train_rate(run)
    if got is None or run.peaks is None:
        return None
    peak = run.device["count"] * run.peaks["bf16_tflops"] * 1e12
    return 100.0 * run.record["flops_per_token"] * got[0] / peak
