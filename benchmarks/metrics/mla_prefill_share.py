"""Latent attention: device time under the program's `mla.*` named scopes
(project with the decompression, attend, out) over the prefill programs'
device time, in the traced part. Each scope's own share is in the notes."""

from benchmarks.lib import mla_readers


def read(run):
    return mla_readers.scope_share(run, "prefill_module", "mla_prefill_share_parts")
