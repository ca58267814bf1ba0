"""Readers of the looped model's per-layer metrics. They read what the
`ouro` family leaves in its record under `loop`: the engine's
`weight_walk` (bytes of weights stored and applied in a step) and, from a
traced run, the decode program's device time by PCG node, a row a scope
`kind:name` with the node's pass and layer in its name (`p2.l5.attn`). A
record without them (another family, an untraced run, a program with no
such walk or scopes, as the parent commit's) reads None and does not
raise."""

from __future__ import annotations

import re

_PASS = re.compile(r"^[a-z0-9_]+:p(\d+)\.")


def _loop(run):
    rec = run.record
    return rec.get("loop") if rec.get("kind") == "serve" else None


def _decode(run):
    loop = _loop(run)
    table = loop and loop.get("decode_by_node")
    if not table or not table.get("rows") or table.get("device_ms", 0) <= 0:
        return None
    return table


def kind_share(run, kinds, note: str):
    """Device time of the decode program under the scopes of `kinds`
    (operator types in lower case, as `Executor.node_scope` writes them),
    compiler-made prefetches charged to the node that uses them, over the
    program's device time, in the traced part. The note: how many nodes,
    the share that was charged, and the share by pass."""
    table = _decode(run)
    if table is None:
        return None
    rows = [r for r in table["rows"] if r[0].split(":", 1)[0] in kinds]
    if not rows:
        return None
    total = table["device_ms"]
    by_pass = {}
    for scope, ms, _ in rows:
        m = _PASS.match(scope)
        key = f"pass_{m.group(1)}" if m else "outside_passes"
        by_pass[key] = by_pass.get(key, 0.0) + 100.0 * ms / total
    run.notes[note] = {
        "nodes": len(rows),
        "charged_share": 100.0 * sum(r[2] for r in rows) / total,
        "by_pass": dict(sorted(by_pass.items())),
        "decode_program_ms": total,
        "executions": table.get("executions"),
    }
    return 100.0 * sum(r[1] for r in rows) / total


def later_pass_share(run):
    """Device time of the decode program under the nodes of passes 2 and
    later over the program's device time: a count-like control, near
    (passes - 1) / passes less what lies outside the passes (embedding,
    head, sampling). The note holds each pass's share and the share over
    the passes' own time."""
    table = _decode(run)
    if table is None:
        return None
    by_pass = {}
    for scope, ms, _ in table["rows"]:
        m = _PASS.match(scope)
        if m:
            by_pass[int(m.group(1))] = by_pass.get(int(m.group(1)), 0.0) + ms
    if len(by_pass) < 2:
        return None
    total, inside = table["device_ms"], sum(by_pass.values())
    later = sum(ms for p, ms in by_pass.items() if p > 1)
    run.notes["loop_later_pass_share_parts"] = {
        "by_pass": {f"pass_{p}": 100.0 * ms / total for p, ms in sorted(by_pass.items())},
        "of_the_passes_own_time": 100.0 * later / inside,
        "accounted": table.get("accounted"),
    }
    return 100.0 * later / total


def weights_applied_over_stored(run):
    loop = _loop(run)
    walk = loop and loop.get("walk")
    if not walk or not walk.get("weights_stored_bytes"):
        return None
    run.notes["weight_walk"] = dict(walk)
    return walk["weights_applied_bytes"] / walk["weights_stored_bytes"]
