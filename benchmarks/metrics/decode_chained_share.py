"""Scheduler: the decode steps dispatched with another program still in
flight, over the window's decode steps, in percent: how far the
overlapped loop engaged (the device did not wait for the host between
them)."""

from benchmarks.lib import steplog


def read(run):
    records = steplog.of_kind(run, steplog.DECODE)
    if not records:
        return None
    return 100.0 * sum(r.chained for r in records) / len(records)
