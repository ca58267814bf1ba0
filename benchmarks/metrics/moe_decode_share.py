"""Expert layer: device time under the program's `moe.*` named scopes
(route, sort, experts, combine) over the decode program's device time,
in the traced part. Each scope's own share is in the notes."""

from benchmarks.lib import moe_readers


def read(run):
    return moe_readers.scope_share(run, "decode_module", "moe_decode_share_parts")
