"""Expert layer: device time under the program's `moe.*` named scopes
over the prefill programs' device time, in the traced part. Each scope's
own share is in the notes."""

from benchmarks.lib import moe_readers


def read(run):
    return moe_readers.scope_share(run, "prefill_module", "moe_prefill_share_parts")
