"""Looped stack: the bytes of weights a step applies (every node's, its
owner's where it applies another node's) over the bytes the server keeps,
from the engine's `weight_walk`: 1.0 for a model whose nodes own their
weights, and for a looped model again if sharing were ever lost."""

from benchmarks.lib import loop_readers


def read(run):
    return loop_readers.weights_applied_over_stored(run)
