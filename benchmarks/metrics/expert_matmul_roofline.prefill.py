"""Kernels: the expert layer's grouped matmuls in prefill, as a share of
their roofline: the larger of the FLOP time and the byte time for the
rows the kernel was given (padding included), over the device time under
`moe.experts` in the prefill programs. The bound is in the notes."""

from benchmarks.lib import moe_readers


def read(run):
    return moe_readers.expert_matmul_roofline(run, "prefill")
