"""Executor: share of the traced window in which the device is idle
under the benchmark's `train.input` span (next_batch, shard_batch and
the dispatch of one step)."""

from benchmarks.lib import readers


def read(run):
    if run.record.get("kind") != "train":
        return None
    return readers.idle_under_share(run, "train.input")
