"""Looped stack: device time of the decode program under its
`rmsnorm:*` and `ew_add:*` nodes (four norms and two residual adds a pass
and layer, a norm after every pass: small latency-bound ops, 148 a step at
the cut), over the program's device time, in the traced part. By pass in
the notes."""

from benchmarks.lib import loop_readers


def read(run):
    return loop_readers.kind_share(
        run, ("rmsnorm", "ew_add"), "loop_norm_decode_share_parts"
    )
