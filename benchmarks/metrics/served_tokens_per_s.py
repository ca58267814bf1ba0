"""Prompt and output tokens of the requests that finished inside the
window, per second, between the first and the last completion in it."""

from benchmarks.lib import readers


def read(run):
    got = readers.served_rate(run)
    if got is None:
        return None
    run.notes["served_requests_counted"] = got[1]
    run.notes["served_elapsed_s"] = got[2]
    return got[0]
