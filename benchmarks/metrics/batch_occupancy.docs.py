"""Scheduler: running sequences over slots, per decode step, over the
window (`SchedulerStats.busy_slot_steps / slot_steps`)."""

from benchmarks.lib import readers


def read(run):
    return readers.occupancy(run)
