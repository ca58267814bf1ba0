"""Kernels: the latent paged decode kernel's share of its roofline: the
latent rows attended (the program's counter `mla_rows_read_decode`) at
their own width plus each slot's queries in and outputs back, over the
chip's bandwidth (or the FLOPs over its peak, whichever is larger), over
the device time under `mla.attend` in the decode program. The bound is in
the notes."""

from benchmarks.lib import mla_readers


def read(run):
    return mla_readers.latent_kernel_roofline(run)
