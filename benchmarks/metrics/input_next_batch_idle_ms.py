"""Executor: idle device milliseconds per train step under the
program's `train.input.next_batch` span (`loader.next_batch()`: the
host gather of one batch)."""

from benchmarks.lib import spans


def read(run):
    return spans.train_phase_idle_ms(run, ("train.input.next_batch",))
