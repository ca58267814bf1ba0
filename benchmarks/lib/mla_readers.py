"""Readers of the latent-attention and held-share per-layer metrics. They
read what the `deepseek_v3` family leaves in its record under `mla`: the
engine's counters after each scheduler step, and the device time under
the program's `mla.*` named scopes (`lib/scopes.py`). A record without
them (another family, an untraced run, a program with no such scope or
counter, as the parent commit's) reads None and does not raise."""

from __future__ import annotations

from benchmarks.lib import mla_counts, moe_readers
from benchmarks.lib import peaks as peaks_lib

# mla["steps"] rows, all cumulative: step end, decode steps, latent rows
# attended in decode, busy slot-steps, live rows absent in decode, live
# rows absent in prefill, prompt tokens prefilled
_T, _DECODES, _ROWS, _SLOT_STEPS, _ABSENT_D, _ABSENT_P, _PROMPT = range(7)


def _mla(run):
    rec = run.record
    return rec.get("mla") if rec.get("kind") == "serve" else None


def _program(run, key):
    mla = _mla(run)
    if not mla or not mla.get("scope_seconds"):
        return None
    prog = mla["scope_seconds"].get(run.record.get(key, ""))
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
    """`moe_readers._between` (counter differences over the steps that
    ended inside [lo, hi]); None for an untraced run's empty window."""
    if lo is None or hi is None:
        return None
    return moe_readers._between(steps, lo, hi)


def experts_absent_share(run):
    """Live (token, choice) rows routed to experts this chip does not
    hold, over the live rows routed (a busy slot's token in every expert
    layer, k choices each; padding rows are not counted), in the window's
    decode steps; the prefills' beside it."""
    mla = _mla(run)
    got = mla and _between(mla["steps"], *run.record["window"])
    moe = run.record.get("moe") or {}
    per_token = moe.get("k", 0) * moe.get("layers", 0)
    if not got or got[_SLOT_STEPS] * per_token <= 0:
        return None
    if got[_PROMPT] > 0:
        run.notes["experts_absent_share_prefill"] = (
            100.0 * got[_ABSENT_P] / (got[_PROMPT] * per_token)
        )
    return 100.0 * got[_ABSENT_D] / (got[_SLOT_STEPS] * per_token)


def latent_kernel_roofline(run):
    """The counters of the traced part, scaled to the decode programs the
    trace holds whole, against the device time under `mla.attend` there:
    in the decode program that scope holds the latent kernel's call and
    nothing else (the row's write is under `mla.project`)."""
    mla = _mla(run)
    prog = _program(run, "decode_module")
    if not mla or prog is None or run.peaks is None:
        return None
    seconds = prog["scopes"].get("mla.attend", 0.0)
    got = _between(mla["steps"], *run.record["trace_window"])
    if seconds <= 0 or not got or got[_DECODES] <= 0 or got[_ROWS] <= 0:
        return None
    scale = prog["count"] / got[_DECODES]
    flops = scale * mla_counts.latent_decode_flops(
        got[_ROWS], mla["heads"], mla["row"], mla["value_width"]
    )
    bytes_ = scale * mla_counts.latent_decode_bytes(
        got[_ROWS], got[_SLOT_STEPS] * mla["layers"], mla["heads"],
        mla["row"], mla["value_width"], mla["itemsize"],
    )
    floor_s, bound = peaks_lib.roofline_floor_s(flops, bytes_, run.peaks)
    run.notes["latent_kernel_bound"] = bound
    run.notes["latent_kernel_executions"] = prog["count"]
    run.notes["latent_rows_per_decode_step"] = got[_ROWS] / got[_DECODES]
    return 100.0 * floor_s / seconds
