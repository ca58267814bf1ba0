"""Kernels: a decode step's per-slot recurrent state as a share of its
roofline: the (live slot, layer) rows advanced (the program's counter
`state_rows_decode`), each the state in and out, the convolutions' tails
in and out and the token's rows (`lib/kda_counts.py`), over the chip's
bandwidth (or the FLOPs over its peak, whichever is larger), over the
device time under `kda.step` and `kda.conv` in the decode program. The
bound is in the notes."""

from benchmarks.lib import kda_readers


def read(run):
    return kda_readers.state_roofline(run)
