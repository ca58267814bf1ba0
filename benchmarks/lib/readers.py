"""What the metric readers share. A reader is `read(run) -> number or
None`; a reader that finds nothing to read returns None and the harness
leaves the metric out of the line. `run` is a `Run` (below)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from benchmarks.lib import peaks as peaks_lib
from benchmarks.lib import stats


@dataclass
class Run:
    record: dict  # what the family returned
    trace: Optional[dict]  # lib.trace.summarize(...) of a traced run
    device: dict  # platform, kind, count, memory_peak_bytes
    peaks: Optional[dict]  # the peaks of this device kind; None off the chip
    set_up_seconds: float
    notes: dict  # readers may leave a remark here (which bound, sample counts)


# -- trainer -------------------------------------------------------------------


def train_rate(run: Run):
    rec = run.record
    if rec.get("kind") != "train":
        return None
    return stats.whole_unit_rate(rec["epoch_stamps"], rec["tokens_per_epoch"])


def module_mean_ms(run: Run, key: str):
    if not run.trace:
        return None
    mod = run.trace["modules"].get(run.record.get(key, ""))
    if not mod or not mod["count"]:
        return None
    run.notes[f"{key}_count"] = mod["count"]
    return 1e3 * mod["seconds"] / mod["count"]


def idle_share(run: Run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def idle_under_share(run: Run, span: str):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * run.trace["idle_under"].get(span, 0.0) / run.trace["window_s"]


def peak_hbm_gb(run: Run):
    if run.peaks is None:
        return None
    return run.device["memory_peak_bytes"] / 1e9


# -- server --------------------------------------------------------------------


def counted(run: Run):
    rec = run.record
    if rec.get("kind") != "serve":
        return []
    return [r for r in rec["requests"] if r.segment == "window"]


def served_whole(r) -> bool:
    return r.status == "finished" and r.tokens == r.asked


def _left_out(r, finished_only: bool) -> bool:
    # above the knee the backlog the harness cancels at the window's end
    # is not a failure: a tail "of the served" leaves those requests out
    return finished_only and r.cancelled_by_harness


def ttfts_ms(run: Run, finished_only: bool = False):
    """Due time to first token, for every request due in the window; one
    that failed or never produced a token counts as the worst (infinite)."""
    out = []
    for r in counted(run):
        if served_whole(r) and r.first:
            out.append(1e3 * (r.first - r.due))
        elif not _left_out(r, finished_only):
            out.append(float("inf"))
    return out


def tpots_of(records, finished_only: bool = False):
    """A request's mean gap between its tokens, first to last over n - 1:
    a gap is a decode step or a decode step plus a prefill, and the mean
    inside a request comes before any percentile across requests."""
    out = []
    for r in records:
        if served_whole(r):
            if r.tokens >= 2:
                out.append(1e3 * (r.last - r.first) / (r.tokens - 1))
        elif not _left_out(r, finished_only):
            out.append(float("inf"))
    return out


def tpots_ms(run: Run, finished_only: bool = False):
    return tpots_of(counted(run), finished_only)


def pct(run: Run, values, p, name):
    values = list(values)
    run.notes[f"{name}_samples"] = len(values)
    v = stats.percentile(values, p)
    if v is None or v == float("inf"):
        return None
    return v


def steps_in_window(run: Run):
    rec = run.record
    if rec.get("kind") != "serve":
        return []
    lo, hi = rec["window"]
    return [s for s in rec["steps"] if s[0] >= lo and s[1] <= hi]


def occupancy(run: Run):
    steps = steps_in_window(run)
    if len(steps) < 2:
        return None
    busy = steps[-1][4] - steps[0][4]
    slots = steps[-1][5] - steps[0][5]
    return 100.0 * busy / slots if slots else None


def kv_pages_peak_share(run: Run):
    steps = steps_in_window(run)
    if not steps:
        return None
    return 100.0 * max(s[7] for s in steps) / run.record["num_pages"]


def prefill_real_token_share(run: Run):
    """Prompt tokens over the tokens the prefill programs were run on
    ([slots, bucket] per admission batch), for admissions in the window."""
    rec = run.record
    if rec.get("kind") != "serve":
        return None
    w0, w1 = rec["window"]
    groups = {}
    for r in rec["requests"]:
        if r.admit_iter >= 0 and w0 <= r.admit < w1:
            groups.setdefault(r.admit_iter, []).append(r.prompt_len)
    if not groups:
        return None
    real = sum(sum(g) for g in groups.values())
    padded = sum(rec["max_seqs"] * rec["bucket_of"](max(g)) for g in groups.values())
    run.notes["prefill_batches_in_window"] = len(groups)
    return 100.0 * real / padded


def gen_late_ms(run: Run):
    """How late the generator started each request, beyond what the
    front door's own loop imposed: a request due while a scheduler step
    held the loop could not start before that step ended."""
    rec = run.record
    if rec.get("kind") != "serve":
        return []
    steps = rec["steps"]
    out, i = [], 0
    for r in sorted(rec["requests"], key=lambda r: r.due):
        if not r.started:
            continue
        while i < len(steps) and steps[i][1] < r.due:
            i += 1
        free_at = r.due
        if i < len(steps) and steps[i][0] <= r.due:
            free_at = steps[i][1]
        out.append(1e3 * max(0.0, r.started - free_at))
    return out


def served_rate(run: Run):
    rec = run.record
    if rec.get("kind") != "serve":
        return None
    w0, w1 = rec["window"]
    finishes = [
        (r.done, r.prompt_len + r.tokens) for r in rec["requests"]
        if served_whole(r)
    ]
    return stats.completion_rate(finishes, w0, w1)


def decode_kernel_roofline(run: Run):
    """The decode kernel's share of its roofline over the traced part:
    the bytes (and FLOPs) its calls needed, from the context lengths of
    the decode steps the benchmark counted, over the chip's peak, over
    the kernel's device time in the decode program."""
    rec, tr = run.record, run.trace
    if rec.get("kind") != "serve" or not tr or run.peaks is None:
        return None
    kern = tr["kernels"].get(rec["decode_module"])
    mod = tr["modules"].get(rec["decode_module"])
    if not kern or not mod or kern["seconds"] <= 0:
        return None
    lo, hi = rec["trace_window"]
    decode_steps = []
    prev = None
    for s in rec["steps"]:
        if prev is not None and s[0] >= lo and s[1] <= hi and s[2] > prev[2]:
            decode_steps.append(s)
        prev = s
    if not decode_steps:
        return None
    kv = rec["kv"]
    per_step_bytes = per_step_flops = 0.0
    for s in decode_steps:
        # s[6]: sum of the live sequences' context lengths after the
        # step (the new token included); s[8]: how many were live
        n = max(1, s[8])
        lens = [s[6] / n] * n
        per_step_bytes += peaks_lib.paged_decode_attention_bytes(
            lens, kv["heads"], kv["head_dim"], kv["itemsize"]
        )
        per_step_flops += peaks_lib.paged_decode_attention_flops(
            lens, kv["heads"], kv["head_dim"]
        )
    calls = mod["count"]
    scale = kv["layers"] * calls / len(decode_steps)
    floor_s, bound = peaks_lib.roofline_floor_s(
        per_step_flops * scale, per_step_bytes * scale, run.peaks
    )
    run.notes["decode_kernel_bound"] = bound
    run.notes["decode_kernel_calls"] = kern["count"]
    return 100.0 * floor_s / kern["seconds"]
