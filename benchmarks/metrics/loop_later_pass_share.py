"""Looped stack: device time of the decode program under the nodes of
passes 2 and later over the program's device time, in the traced part: a
count-like control (three of four passes at the cut, less what lies
outside the passes). Each pass's share is in the notes."""

from benchmarks.lib import loop_readers


def read(run):
    return loop_readers.later_pass_share(run)
