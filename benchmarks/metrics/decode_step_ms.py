"""Engine: mean device time of the decode program in the traced part."""

from benchmarks.lib import readers


def read(run):
    return readers.module_mean_ms(run, "decode_module")
