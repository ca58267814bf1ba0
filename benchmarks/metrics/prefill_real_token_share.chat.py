"""Engine: prompt tokens over the [slots, bucket] tokens the prefill
programs ran on. A count."""

from benchmarks.lib import readers


def read(run):
    return readers.prefill_real_token_share(run)
