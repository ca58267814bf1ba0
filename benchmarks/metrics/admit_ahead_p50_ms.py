"""Engine: what an admitted request waits before its own prefill program
is even on the device's queue (`ahead`: the admission's host work and the
enqueue held behind what the device still owes), median over the
window's requests, from the program's own record."""

from benchmarks.lib import steplog


def read(run):
    parts = steplog.ttft_parts(run)
    if not parts:
        return None
    return steplog.p50([1e3 * p["ahead"] for p in parts])
