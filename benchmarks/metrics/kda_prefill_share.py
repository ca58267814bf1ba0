"""Recurrent layers (Kimi Delta Attention): device self time under the
program's `kda.*` named scopes (project, conv, scan, out) over the
prefill programs' device time, in the traced part. Each scope's own share
is in the notes; `kda.scan` is the chunked recurrence."""

from benchmarks.lib import kda_readers


def read(run):
    return kda_readers.scope_share(run, "prefill_module", "kda_prefill_share_parts")
