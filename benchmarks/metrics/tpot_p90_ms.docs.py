"""A request's mean gap between tokens, 90th percentile over the
window's requests that were served. Recorded, not judged."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.tpots_ms(run, finished_only=True), 90, "tpot")
