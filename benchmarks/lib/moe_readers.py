"""Readers of the expert layer's per-layer metrics. They read what the
`olmoe` family leaves in its record under `moe`: the engine's counters
after each scheduler step, and the device time under the program's
`moe.*` named scopes (`lib/scopes.py`). A record without them (another
family, an untraced run, a program with no such scope) reads None."""

from __future__ import annotations

from benchmarks.lib import moe_counts
from benchmarks.lib import peaks as peaks_lib

# moe["steps"] rows, all cumulative: step end, decode steps, rows and
# experts touched in decode, prefill batches, rows and experts touched in
# prefill
_T, _DECODES, _ROWS_D, _TOUCHED_D, _PREFILLS, _ROWS_P, _TOUCHED_P = range(7)


def _moe(run):
    rec = run.record
    return rec.get("moe") if rec.get("kind") == "serve" else None


def _program(run, key):
    moe = _moe(run)
    if not moe or not moe.get("scope_seconds"):
        return None
    prog = moe["scope_seconds"].get(run.record.get(key, ""))
    if not prog or not prog["count"] or prog["seconds"] <= 0 or not prog["scopes"]:
        return None
    return prog


def scope_share(run, key: str, note: str):
    prog = _program(run, key)
    if prog is None:
        return None
    run.notes[note] = {
        k: 100.0 * v / prog["seconds"] for k, v in sorted(prog["scopes"].items())
    }
    return 100.0 * sum(prog["scopes"].values()) / prog["seconds"]


def _between(steps, lo, hi):
    """Counter differences over the steps that ended inside [lo, hi]."""
    inside = [s for s in steps if lo <= s[_T] <= hi]
    if len(inside) < 2:
        return None
    return [b - a for a, b in zip(inside[0], inside[-1])]


def experts_touched_share(run):
    moe = _moe(run)
    if not moe:
        return None
    got = _between(moe["steps"], *run.record["window"])
    if not got or not got[_DECODES]:
        return None
    run.notes["experts_touched_per_layer_step"] = (
        got[_TOUCHED_D] / (moe["layers"] * got[_DECODES])
    )
    return 100.0 * got[_TOUCHED_D] / (
        moe["experts"] * moe["layers"] * got[_DECODES]
    )


def expert_matmul_roofline(run, phase: str):
    """The counters of the traced part, scaled to the executions the
    trace holds whole, against the time under `moe.experts` there."""
    moe = _moe(run)
    prog = _program(run, f"{phase}_module")
    if not moe or prog is None or run.peaks is None:
        return None
    seconds = prog["scopes"].get("moe.experts", 0.0)
    lo, hi = run.record["trace_window"]
    got = _between(moe["steps"], lo, hi) if lo is not None and hi is not None else None
    if seconds <= 0 or not got:
        return None
    executions, rows, touched = (
        (got[_DECODES], got[_ROWS_D], got[_TOUCHED_D]) if phase == "decode"
        else (got[_PREFILLS], got[_ROWS_P], got[_TOUCHED_P])
    )
    if rows <= 0 or executions <= 0:
        return None
    # the counters cover the host's steps inside the traced part, the time
    # the executions the trace holds whole: scale the one to the other
    scale = prog["count"] / executions
    flops = scale * moe_counts.expert_matmul_flops(
        rows, moe["hidden"], moe["expert_hidden"]
    )
    bytes_ = scale * moe_counts.expert_matmul_bytes(
        rows, touched, moe["hidden"], moe["expert_hidden"], moe["itemsize"]
    )
    floor_s, bound = peaks_lib.roofline_floor_s(flops, bytes_, run.peaks)
    run.notes[f"expert_matmul_bound.{phase}"] = bound
    run.notes[f"expert_matmul_executions.{phase}"] = prog["count"]
    return 100.0 * floor_s / seconds
