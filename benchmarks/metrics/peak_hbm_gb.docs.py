"""Device: peak memory on the fullest chip: `peak_bytes_in_use` plus
the larger of `peak_bytes_reserved` and, for the trainer, the step's
temporaries as the compiler counts them (the runtime's counters leave a
running program's temporaries out: PR 22 read 0.76 GB beside 6.5 GB of
them). For the server the temporaries are not added: a lower bound."""

from benchmarks.lib import readers


def read(run):
    return readers.peak_hbm_gb(run)
