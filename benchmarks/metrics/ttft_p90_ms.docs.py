"""Due time to first token, 90th percentile over the window's requests
that were served (above the knee the backlog cancelled at the window's
end is left out). Recorded, not judged: it swings with the queue."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.ttfts_ms(run, finished_only=True), 90, "ttft")
