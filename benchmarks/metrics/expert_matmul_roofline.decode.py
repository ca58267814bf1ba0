"""Kernels: the expert layer's grouped matmuls in decode, as a share of
their roofline: the bytes of the weights of the experts TOUCHED (the
program's counter) plus the rows in and out, over the chip's bandwidth
(or their FLOPs over its peak, whichever is larger), over the device time
under `moe.experts` in the decode program. The bound is in the notes."""

from benchmarks.lib import moe_readers


def read(run):
    return moe_readers.expert_matmul_roofline(run, "decode")
