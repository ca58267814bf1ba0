"""Latent attention: device time under the program's `mla.*` named scopes
(project, absorb, attend, out) over the decode program's device time, in
the traced part. Each scope's own share is in the notes.

A FLOOR, and the notes say so on every line: XLA reads a decode step's
weights by prefetches of its own making (`slice-done`, `copy-done`), which
carry no scope, so a projection's weight read mostly lands outside
`mla.project` / `mla.absorb` / `mla.out` and the matmul finds its weights
in VMEM. What the share does hold whole is `mla.attend` (the latent
kernel's call) and the compute of the projections. `BENCHMARK.json`'s
entry has no field for prose; `PERF.md` section 3 carries the same note."""

from benchmarks.lib import mla_readers


def read(run):
    share = mla_readers.scope_share(run, "decode_module", "mla_decode_share_parts")
    if share is not None:
        run.notes["mla_decode_share_is"] = (
            "a floor: the compiler's weight prefetches carry no scope"
        )
    return share
