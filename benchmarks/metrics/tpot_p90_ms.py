"""A request's mean gap between its tokens, 90th percentile across the
requests due in the window."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.tpots_ms(run), 90, "tpot")
