"""Kernels: a prefill's chunked delta-rule recurrence as a share of its
roofline: the larger of its FLOP time and its byte time for the tokens the
programs were given (padding included), from shapes (`lib/kda_counts.py`),
over the device time under `kda.scan` in the prefill programs. The bound
is in the notes."""

from benchmarks.lib import kda_readers


def read(run):
    return kda_readers.scan_roofline(run)
