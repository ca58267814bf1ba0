"""Kernels: the paged decode kernel's share of its roofline (bytes from
shapes over the chip's bandwidth, over the kernel's device time). The
bound that sets the floor is in the line's notes."""

from benchmarks.lib import readers


def read(run):
    return readers.decode_kernel_roofline(run)
