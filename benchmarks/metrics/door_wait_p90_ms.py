"""Front door: due time to `submit` accepted, 90th percentile. The door
and the engine share one loop, so a request due while a step runs waits
for it."""

from benchmarks.lib import readers


def read(run):
    waits = [1e3 * (r.accepted - r.due) for r in readers.counted(run) if r.accepted]
    return readers.pct(run, waits, 90, "door_wait")
