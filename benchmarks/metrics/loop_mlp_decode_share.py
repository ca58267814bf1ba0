"""Looped stack: device time of the decode program under its
`gated_mlp:*` nodes (one a pass and layer), the compiler's weight
prefetches charged to the node that uses them, over the program's device
time, in the traced part. By pass in the notes."""

from benchmarks.lib import loop_readers


def read(run):
    return loop_readers.kind_share(
        run, ("gated_mlp",), "loop_mlp_decode_share_parts"
    )
