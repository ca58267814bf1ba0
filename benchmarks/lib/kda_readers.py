"""Readers of the recurrent layers' per-layer metrics. They read what the
`kimi_linear` family leaves in its record under `kda`: the engine's
counters after each scheduler step, and the device time under the
program's `kda.*` named scopes (`lib/scopes.py`). A record without them
(another family, an untraced run, a program with no such scope or
counter, as the parent commit's) reads None and does not raise."""

from __future__ import annotations

from benchmarks.lib import kda_counts, moe_readers
from benchmarks.lib import peaks as peaks_lib

# kda["steps"] rows, all cumulative: step end, decode steps, (live slot,
# layer) state rows advanced in decode, prefill programs, the tokens their
# rows held (padding included), (request, layer) rows written by prefills
_T, _DECODES, _ROWS, _PREFILLS, _TOKENS, _RESETS = range(6)


def _kda(run):
    rec = run.record
    return rec.get("kda") if rec.get("kind") == "serve" else None


def _program(run, key):
    kda = _kda(run)
    if not kda or not kda.get("scope_seconds"):
        return None
    prog = kda["scope_seconds"].get(run.record.get(key, ""))
    if not prog or not prog["count"] or prog["seconds"] <= 0 or not prog["scopes"]:
        return None
    return prog


def scope_share(run, key: str, note: str):
    """Device self time under every `kda.*` scope over the program's, in
    per cent; each scope's own share in the notes."""
    prog = _program(run, key)
    if prog is None:
        return None
    run.notes[note] = {
        k: 100.0 * v / prog["seconds"] for k, v in sorted(prog["scopes"].items())
    }
    return 100.0 * sum(prog["scopes"].values()) / prog["seconds"]


def _traced(run):
    """Counter differences over the steps that ended inside the traced
    part; None for an untraced run's empty window."""
    kda = _kda(run)
    lo, hi = run.record.get("trace_window") or (None, None)
    if not kda or lo is None or hi is None:
        return None
    return moe_readers._between(kda["steps"], lo, hi)


def _share(run, flops, bytes_, seconds, executions, name):
    floor_s, bound = peaks_lib.roofline_floor_s(flops, bytes_, run.peaks)
    run.notes[f"{name}_bound"] = bound
    run.notes[f"{name}_executions"] = executions
    return 100.0 * floor_s / seconds


def state_roofline(run):
    """The state rows the traced decode steps advanced (the counter
    `state_rows_decode`), scaled to the decode programs the trace holds
    whole, at `kda_counts.state_step_bytes` a row, against the device time
    under `kda.step` and `kda.conv` there."""
    kda, prog, got = _kda(run), _program(run, "decode_module"), _traced(run)
    if prog is None or run.peaks is None or not got:
        return None
    seconds = prog["scopes"].get("kda.step", 0.0) + prog["scopes"].get("kda.conv", 0.0)
    if seconds <= 0 or got[_DECODES] <= 0 or got[_ROWS] <= 0:
        return None
    rows = got[_ROWS] * prog["count"] / got[_DECODES]
    run.notes["kda_state_rows_per_decode_step"] = got[_ROWS] / got[_DECODES]
    return _share(
        run,
        kda_counts.state_step_flops(rows, kda["heads"], kda["head_dim"]),
        kda_counts.state_step_bytes(
            rows, kda["heads"], kda["head_dim"], kda["kernel"]
        ),
        seconds, prog["count"], "kda_state",
    )


def scan_roofline(run):
    """The tokens the traced prefill programs were given (padding
    included: the programs run it), a recurrent layer each, against the
    device time under `kda.scan` there."""
    kda, prog, got = _kda(run), _program(run, "prefill_module"), _traced(run)
    if prog is None or run.peaks is None or not got:
        return None
    seconds = prog["scopes"].get("kda.scan", 0.0)
    if seconds <= 0 or got[_PREFILLS] <= 0 or got[_TOKENS] <= 0:
        return None
    tokens = got[_TOKENS] * kda["layers"] * prog["count"] / got[_PREFILLS]
    run.notes["kda_tokens_per_prefill_program"] = got[_TOKENS] / got[_PREFILLS]
    shape = (kda["heads"], kda["head_dim"], kda["chunk"])
    return _share(
        run, kda_counts.scan_flops(tokens, *shape),
        kda_counts.scan_bytes(tokens, *shape), seconds, prog["count"], "kda_scan",
    )
