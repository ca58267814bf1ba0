"""Recurrent layers (Kimi Delta Attention): device self time under the
program's `kda.*` named scopes (project, conv, step, out) over the decode
program's device time, in the traced part. Each scope's own share is in
the notes. As `mla_decode_share`, a floor for the projections: the
compiler's weight prefetches carry no scope. `kda.step` and `kda.conv`,
which read and write the per-slot state, are held whole."""

from benchmarks.lib import kda_readers


def read(run):
    return kda_readers.scope_share(run, "decode_module", "kda_decode_share_parts")
