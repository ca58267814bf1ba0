"""Looped stack: device time of the decode program under its
`multihead_attention:*` nodes (one a pass and layer: the projections, the
rotation, the cache write and the paged kernel), the compiler's weight
prefetches charged to the node that uses them, over the program's device
time, in the traced part. By pass in the notes."""

from benchmarks.lib import loop_readers


def read(run):
    return loop_readers.kind_share(
        run, ("multihead_attention",), "loop_attn_decode_share_parts"
    )
