"""Device time under `jax.named_scope`s, from a profiler trace.

`lib/trace.py` reduces a trace to programs, kernels and ops by name; it
keeps no scope. Where a named scope is on a TPU v5e's trace (looked at by
hand, PR 26): not in the event's name (the HLO text, printed without
metadata) and not among the event's own stats (timings only), but among
the stats of the event's METADATA record in the device plane, as a string
`jit(f)/.../moe.experts/mul`: the instruction's `op_name`.
`jax.profiler.ProfileData` does not show metadata records, so this file
reads the `.xplane.pb` itself: the few fields of `xplane.proto` it needs,
by their numbers, in protobuf's wire format.

A program that has no such scope (the parent commit's, or a model
without the layer) gives empty sums, never an error.
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional, Sequence, Tuple

from benchmarks.lib import trace as trace_lib

# xplane.proto, by field number
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_METADATA = 2, 3, 4
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_METADATA_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_META_NAME, _META_STATS = 2, 5
_STAT_STR, _STAT_BYTES = 5, 6
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _scope_in(text: str, scopes: Sequence[str], ops: Dict[str, str]) -> Optional[str]:
    for scope in scopes:
        if f"/{scope}/" in text or text.endswith(f"/{scope}"):
            return scope
    if "/" not in text:
        # an instruction the compiler made itself carries its own name as
        # op_name and no path: `ragged-dot-none:`, `ragged-dot-metadata:`
        for prefix, scope in ops.items():
            if text.startswith(prefix):
                return scope
    return None


def _metadata(plane, scopes, ops) -> Dict[int, Tuple[str, Optional[str]]]:
    """metadata id -> (event name, the first of `scopes` on its op_name)."""
    out = {}
    for field, entry in _fields(plane):
        if field != _PLANE_EVENT_METADATA:
            continue
        key, name, scope = 0, "", None
        for f, v in _fields(entry):
            if f == _MAP_KEY:
                key = v
            elif f == _MAP_VALUE:
                for mf, mv in _fields(v):
                    if mf == _META_NAME:
                        name = bytes(mv).decode("utf-8", "replace")
                    elif mf == _META_STATS and scope is None:
                        for sf, sv in _fields(mv):
                            if sf in (_STAT_STR, _STAT_BYTES):
                                scope = _scope_in(
                                    bytes(sv).decode("utf-8", "replace"),
                                    scopes, ops,
                                )
        out[key] = (name, scope)
    return out


def _line(line, wanted=("XLA Ops", "XLA Modules")):
    """(name, [(metadata id, start seconds, end seconds)]); the events of
    a line whose name is not `wanted` are not decoded."""
    name, t0_ns, events = "", 0, []
    for field, value in _fields(line):
        if field == _LINE_NAME:
            name = bytes(value).decode("utf-8", "replace")
        elif field == _LINE_TIMESTAMP_NS:
            t0_ns = value
        elif field == _LINE_EVENTS:
            events.append(value)
    out = []
    if name in wanted:
        for event in events:
            meta = offset_ps = duration_ps = 0
            for f, v in _fields(event):
                if f == _EVENT_METADATA_ID:
                    meta = v
                elif f == _EVENT_OFFSET_PS:
                    offset_ps = v
                elif f == _EVENT_DURATION_PS:
                    duration_ps = v
            start = t0_ns * 1e-9 + offset_ps * 1e-12
            out.append((meta, start, start + duration_ps * 1e-12))
    return name, out


def _plane_name(plane) -> str:
    return next(
        (bytes(v).decode() for f, v in _fields(plane) if f == _PLANE_NAME), ""
    )


def span_window(space, span: str) -> Optional[Tuple[float, float]]:
    """The first host span of that name (a `TraceAnnotation` of the
    Python thread), in seconds on the trace's clock."""
    for field, plane in _fields(space):
        if field != _SPACE_PLANES or _plane_name(plane) != "/host:CPU":
            continue
        meta = _metadata(plane, (), {})
        ids = {k for k, (name, _) in meta.items() if name == span}
        for f, line in _fields(plane):
            if f != _PLANE_LINES:
                continue
            name, events = _line(line, ("python3", "python"))
            hits = sorted((s, e) for m, s, e in events if m in ids)
            if hits:
                return hits[0]
    return None


def scope_seconds(
    path: str, scopes: Sequence[str], modules: Sequence[str],
    span: Optional[str] = None,
    compiler_ops: Optional[Dict[str, str]] = None,
) -> Dict[str, dict]:
    """{program: {"count", "seconds", "scopes": {scope: seconds}}} over
    the host span `span` (the whole trace when None or not found),
    averaged over chips: for each of `modules` (by `lib.trace.module_key`),
    how often it ran whole inside the window, its device time, and the
    self time of its ops under each of `scopes` (a `while`'s body counts,
    the `while` itself only what its body leaves). `compiler_ops` maps
    the name prefix of instructions that the compiler makes itself, and
    that therefore carry no scope, to the scope they belong to: XLA
    expands `jax.lax.ragged_dot` into `ragged-dot-*` Mosaic calls whose
    op_name is their own name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    lo, hi = (span and span_window(space, span)) or (float("-inf"), float("inf"))
    out = {m: {"count": 0, "seconds": 0.0, "scopes": {}} for m in modules}
    ndev = 0
    for field, plane in _fields(space):
        if field != _SPACE_PLANES:
            continue
        if not _plane_name(plane).startswith("/device:TPU:"):
            continue
        ndev += 1
        meta = _metadata(plane, scopes, compiler_ops or {})
        runs, ops = [], []
        for f, line in _fields(plane):
            if f != _PLANE_LINES:
                continue
            line_name, events = _line(line)
            if line_name == "XLA Modules":
                for m, s, e in events:
                    key = trace_lib.module_key(meta.get(m, ("", None))[0])
                    if key in out and s >= lo and e <= hi:
                        runs.append((s, e, key))
            elif line_name == "XLA Ops":
                for m, s, e in events:
                    text, scope = meta.get(m, ("", None))
                    ops.append((trace_lib.Op(s, e, text), scope))
        trace_lib._mark_nesting([op for op, _ in ops])
        runs.sort()
        for s, e, key in runs:
            out[key]["count"] += 1
            out[key]["seconds"] += e - s
        ops.sort(key=lambda pair: pair[0].start)
        i = 0
        for op, scope in ops:
            if scope is None or op.self_s <= 0:
                continue
            while i < len(runs) and runs[i][1] < op.start:
                i += 1
            if i < len(runs) and runs[i][0] <= op.start:
                sums = out[runs[i][2]]["scopes"]
                sums[scope] = sums.get(scope, 0.0) + op.self_s
    for rec in out.values():
        rec["count"] /= max(ndev, 1)
        rec["seconds"] /= max(ndev, 1)
        rec["scopes"] = {k: v / max(ndev, 1) for k, v in rec["scopes"].items()}
    return out
