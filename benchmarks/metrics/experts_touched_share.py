"""Router: distinct experts with at least one row in a decode step, over
experts x layers x decode steps in the window. A count."""

from benchmarks.lib import moe_readers


def read(run):
    return moe_readers.experts_touched_share(run)
