"""Executor: idle device milliseconds per train step under the
program's `train.input.dispatch` span (the rng split and the call of the
jitted step, until it returns)."""

from benchmarks.lib import spans


def read(run):
    return spans.train_phase_idle_ms(run, ("train.input.dispatch",))
