"""Due time to first token, median over the requests due in the window;
a failed or unfinished request counts as the worst."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.ttfts_ms(run), 50, "ttft")
