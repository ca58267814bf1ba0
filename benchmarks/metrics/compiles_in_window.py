"""Harness health: programs compiled, or fetched from the persistent
cache, inside the window. There must be none; one is set-up that leaked
into the measurement."""


def read(run):
    seen = run.record["compiles"]
    return float(seen["compiles"] + seen["cache_hits"])
