"""KV manager: the most pages in use at the end of any step of the
window, over the pool (allocator counts)."""

from benchmarks.lib import readers


def read(run):
    return readers.kv_pages_peak_share(run)
