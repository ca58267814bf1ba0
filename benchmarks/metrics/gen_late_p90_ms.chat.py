"""Harness health: how late the generator started a request beyond what
the door's own loop imposed, 90th percentile (a 95th would have fewer
than ten samples beyond it). A late generator is a
starved one, and its run is not a measurement."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.gen_late_ms(run), 90, "gen_late")
