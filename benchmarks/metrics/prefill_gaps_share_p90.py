"""Scheduler: the share of a request's first token -> terminal event
that other requests' prefill programs covered (their `t_call` ->
`t_ready` windows: a prefill and a decode step exclude each other on the
device), 90th percentile over the window's requests, in percent. The
median and the other requests' prefill programs a request are in the
notes."""

from benchmarks.lib import stats, steplog


def read(run):
    gaps = steplog.gap_parts(run)
    if not gaps:
        return None
    shares = [100.0 * g["others_prefill"] / sum(g.values()) for g, _ in gaps]
    run.notes["prefill_gaps_share_p50"] = steplog.p50(shares)
    run.notes["others_prefills_per_request"] = sum(n for _, n in gaps) / len(gaps)
    run.notes["token_gap_parts_share_p50"] = {
        name: steplog.p50([100.0 * g[name] / sum(g.values()) for g, _ in gaps])
        for name in ("others_prefill", "decode", "host")
    }
    return stats.percentile(shares, 90, beyond=0)
