"""Process start to window start: imports, building the model on the
device, warming every shape, the correctness sample, compilation or
cache reads, and the lead-in."""


def read(run):
    return run.set_up_seconds
