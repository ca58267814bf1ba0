"""Executor: mean device time of the train step program in the trace."""

from benchmarks.lib import readers


def read(run):
    return readers.module_mean_ms(run, "step_module")
