"""Engine: how long the jitted call of a decode step holds the host
(`t_enqueued - t_call`), mean over the window's decode records. In the
notes: the mean blocking read (`t_ready - t_read`) and the longest
interval between two consecutive calls of a step program with a request
running (a whole-process stop shows there and nowhere else)."""

from benchmarks.lib import steplog


def read(run):
    records = steplog.of_kind(run, steplog.DECODE)
    if not records:
        return None
    run.notes["decode_read_block_ms"] = steplog.mean_ms(
        [r.t_ready - r.t_read for r in records]
    )
    # a chained step was called with another in flight: requests ran
    # from the call before it to this one
    calls = steplog.window_of(run).records
    run.notes["decode_longest_call_gap_ms"] = max(
        (1e3 * (b.t_call - a.t_call) for a, b in zip(calls, calls[1:]) if b.chained),
        default=None,
    )
    run.notes["decode_records"] = len(records)
    return steplog.mean_ms([r.t_enqueued - r.t_call for r in records])
