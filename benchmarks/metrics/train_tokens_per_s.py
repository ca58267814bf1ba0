"""Tokens trained per second over whole epochs: epochs completed after
the lead-in, times the tokens of one, over the time that really passed
between the two epoch-end stamps (each taken after fit() blocked on the
parameters). Input path included."""

from benchmarks.lib import readers


def read(run):
    got = readers.train_rate(run)
    if got is None:
        return None
    run.notes["train_epochs_counted"] = got[1]
    run.notes["train_elapsed_s"] = got[2]
    return got[0]
