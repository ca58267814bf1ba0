"""Engine: how long the jitted call of a prefill program holds the host
(`t_enqueued - t_call`), mean over the window's prefill records; by
bucket in the notes."""

from benchmarks.lib import steplog


def read(run):
    by_bucket = {}
    for r in steplog.of_kind(run, steplog.PREFILL):
        by_bucket.setdefault(r.bucket, []).append(r.t_enqueued - r.t_call)
    if not by_bucket:
        return None
    run.notes["prefill_enqueue_block_ms_by_bucket"] = {
        str(b): {"mean": steplog.mean_ms(v), "programs": len(v)}
        for b, v in sorted(by_bucket.items())
    }
    return steplog.mean_ms([s for v in by_bucket.values() for s in v])
