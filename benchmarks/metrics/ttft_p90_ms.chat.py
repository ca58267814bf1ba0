"""Due time to first token, 90th percentile over the requests due in
the window; a failed or unfinished request counts as the worst. Not an
end-to-end metric: in a cycle with one burst of a tenth of the requests it
sits on the edge of that burst, and reads 0.4 or 1.6 s by the seed (PR 23)."""

from benchmarks.lib import readers


def read(run):
    return readers.pct(run, readers.ttfts_ms(run), 90, "ttft")
