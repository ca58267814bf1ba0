"""Parallel: share of the traced window in which a collective runs on a
chip and no compute does."""


def read(run):
    if not run.trace or run.trace["window_s"] <= 0 or run.device["count"] < 2:
        return None
    return 100.0 * run.trace["collective_exposed_s"] / run.trace["window_s"]
