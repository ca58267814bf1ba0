"""Device: 1 minus the union of device op intervals over the traced
window, averaged over the chips."""

from benchmarks.lib import readers


def read(run):
    return readers.idle_share(run)
