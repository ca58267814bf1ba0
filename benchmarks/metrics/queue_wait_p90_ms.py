"""Scheduler: `submit` accepted to admission (the request's own `admit`
event), 90th percentile."""

from benchmarks.lib import readers


def read(run):
    waits = [1e3 * (r.admit - r.accepted) for r in readers.counted(run) if r.admit and r.accepted]
    return readers.pct(run, waits, 90, "queue_wait")
