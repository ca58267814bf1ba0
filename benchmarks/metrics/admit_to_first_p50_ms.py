"""Scheduler: a request's admission to its first token, median over the
window's requests, from the program's own record: `ahead` (admit -> its
prefill program is on the device's queue) + `inflight` (-> the prefill's
outputs are the host's) + `emit` (-> first token). The three parts'
medians and 90th percentiles are in the notes, beside the queue's."""

from benchmarks.lib import steplog


def read(run):
    parts = steplog.ttft_parts(run)
    if not parts:
        return None
    run.notes["admit_to_first_parts_ms"] = {
        name: steplog.spread_ms([p[name] for p in parts])
        for name in ("queue", "ahead", "inflight", "emit")
    }
    run.notes["admit_to_first_samples"] = len(parts)
    return steplog.p50(
        [1e3 * (p["ahead"] + p["inflight"] + p["emit"]) for p in parts]
    )
