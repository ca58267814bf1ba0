"""A request's mean gap between its tokens, median across requests."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.tpots_ms(run), 50, "tpot")
