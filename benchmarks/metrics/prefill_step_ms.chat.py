"""Engine: mean device time of the prefill programs in the traced part
(every bucket together)."""

from benchmarks.lib import readers


def read(run):
    return readers.module_mean_ms(run, "prefill_module")
