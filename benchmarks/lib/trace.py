"""From a profiler trace (.xplane.pb) to numbers.

The reduction is the benchmark's: every PR computes device busy time, a
program's device time, a kernel's time and the idle gaps in the same way,
and a reviewer can read how. `tests/test_trace.py` holds it to a small
recorded trace of this program on a TPU v5e.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per executed
program, named `jit_<function>(<hash>)`), `XLA Ops` (one event per HLO
instruction, named by its whole HLO text; the body of a `while` nests
inside the `while` event), and `Async XLA Ops` (copies and collectives
from start to done). The host is `/host:CPU`; `TraceAnnotation` spans of
the Python thread are events of its `python3` line (`python` on the CPU
backend), on the same clock as the device's.
"""

from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds on the trace's clock

_NS = 1e-9
_HLO = re.compile(r"^%([^\s=]+)\s*=\s*(\(?)([a-z0-9]+\[[0-9,]*\])?")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)
KERNEL_TARGET = "tpu_custom_call"


@dataclass
class Op:
    start: float
    end: float
    text: str
    self_s: float = 0.0
    leaf: bool = True

    @property
    def key(self) -> str:
        return op_key(self.text)


@dataclass
class DeviceTrace:
    name: str
    ops: List[Op] = field(default_factory=list)
    async_ops: List[Op] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: Dict[str, List[Interval]]


@functools.lru_cache(maxsize=1 << 16)
def op_key(text: str) -> str:
    """A short stable name for an HLO instruction: its name without the
    trailing number, and the shape of its first result. `%fusion.194 =
    f32[32,1024]{...} fusion(...)` is `fusion f32[32,1024]`."""
    m = _HLO.match(text)
    if not m:
        return text.split("(")[0][:60]
    name = re.sub(r"\.\d+$", "", m.group(1))
    shape = "(tuple)" if m.group(2) else (m.group(3) or "")
    return f"{name} {shape}".strip()


def module_key(name: str) -> str:
    """`jit__decode_impl_paged(3817557165947963370)` -> `jit__decode_impl_paged`."""
    return name.split("(")[0]


def custom_call_target(text: str) -> Optional[str]:
    m = _TARGET.search(text)
    return m.group(1) if m else None


def is_collective(text: str) -> bool:
    """By the instruction's name: `%all-reduce-start.3 = ...`."""
    return text.split("=", 1)[0].lstrip(" %").startswith(COLLECTIVES)


def _mark_nesting(ops: List[Op]) -> None:
    """Self time of each op: its duration less its direct children's (a
    `while` holds its body's ops)."""
    ops.sort(key=lambda o: (o.start, -(o.end - o.start)))
    stack: List[Op] = []
    for op in ops:
        op.self_s = op.end - op.start
        op.leaf = True
        while stack and stack[-1].end <= op.start + 1e-12:
            stack.pop()
        if stack and op.end <= stack[-1].end + 1e-9:
            stack[-1].self_s -= op.end - op.start
            stack[-1].leaf = False
        stack.append(op)
    for op in ops:
        op.self_s = max(op.self_s, 0.0)


def read_xplane(path: str, span_prefixes: Sequence[str] = ()) -> Trace:
    """Read device ops, programs and the host spans whose names start
    with one of `span_prefixes` (all `TraceAnnotation`s of the Python
    thread when empty)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    spans: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                target = {
                    "XLA Ops": dev.ops,
                    "Async XLA Ops": dev.async_ops,
                    "XLA Modules": dev.modules,
                }.get(line.name)
                if target is None:
                    continue
                for e in line.events:
                    s = e.start_ns * _NS
                    target.append(Op(s, s + e.duration_ns * _NS, e.name))
            _mark_nesting(dev.ops)
            dev.modules.sort(key=lambda o: o.start)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name not in ("python3", "python"):
                    continue
                for e in line.events:
                    name = e.name
                    if name.startswith("$") or "(" in name or "::" in name:
                        continue
                    if span_prefixes and not name.startswith(tuple(span_prefixes)):
                        continue
                    s = e.start_ns * _NS
                    spans.setdefault(name, []).append((s, s + e.duration_ns * _NS))
    devices.sort(key=lambda d: d.name)
    for ivs in spans.values():
        ivs.sort()
    return Trace(devices, spans)


# -- interval arithmetic -------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap_total(a: List[Interval], b: List[Interval]) -> float:
    """Seconds that two unions (sorted, disjoint) have in common: one pass
    over both. A serving trace has half a million idle gaps and a thousand
    spans; asking every gap about every span took minutes."""
    got, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            got += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return got


def subtract(ivs: List[Interval], cover: List[Interval]) -> List[Interval]:
    """The parts of `ivs` (a union) that `cover` (a union) does not cover."""
    out = []
    for s, e in ivs:
        out.extend(gaps(clip(cover, s, e), s, e))
    return out


# -- the reduction -------------------------------------------------------------


def window_of(trace: Trace, span: str = "bench.trace") -> Interval:
    """The traced window: the benchmark's own span if it is there, else
    from the first device op to the last."""
    if trace.spans.get(span):
        s, e = trace.spans[span][0]
        return s, e
    events = [o for d in trace.devices for o in d.ops + d.modules]
    starts = [o.start for o in events]
    ends = [o.end for o in events]
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def name_gap(gap: Interval, spans: Dict[str, List[Interval]]) -> str:
    """The host span an idle gap belongs to: the shortest (innermost)
    span that holds the gap's midpoint, and if none does, the span that
    covers most of the gap."""
    mid = 0.5 * (gap[0] + gap[1])
    inner, inner_len = None, float("inf")
    best, best_got = "(no span)", 0.0
    for name, ivs in spans.items():
        if name in ("bench.trace", "bench.window"):
            continue
        got = 0.0
        for s, e in ivs:
            if e <= gap[0]:
                continue
            if s >= gap[1]:
                break
            got += min(gap[1], e) - max(gap[0], s)
            if s <= mid < e and e - s < inner_len:
                inner, inner_len = name, e - s
        if got > best_got:
            best, best_got = name, got
    return inner or best


def summarize(trace: Trace, window: Optional[Interval] = None, top: int = 10) -> dict:
    """Everything the per-layer readers take from a trace, over the
    window: device busy seconds (the union of op intervals, averaged over
    chips), the programs and kernels by time, the ops by self time, the
    longest idle gaps by the host span that covers them, idle seconds
    under each span, and the exposed part of collectives."""
    lo, hi = window or window_of(trace)
    ndev = max(len(trace.devices), 1)
    busy_s = 0.0
    op_time: Dict[str, float] = {}
    modules: Dict[str, dict] = {}
    kernels: Dict[str, dict] = {}
    idle_under: Dict[str, float] = {}
    all_gaps: List[Tuple[float, Interval]] = []
    coll_s = coll_exposed_s = 0.0
    for dev in trace.devices:
        leaf = [o for o in dev.ops if o.leaf and o.end > lo and o.start < hi]
        busy = union(clip(((o.start, o.end) for o in leaf), lo, hi))
        busy_s += total(busy)
        for o in dev.ops:
            if o.end > lo and o.start < hi and o.self_s > 0:
                key = o.key
                op_time[key] = op_time.get(key, 0.0) + o.self_s
        idle = gaps(busy, lo, hi)
        all_gaps.extend((g[1] - g[0], g) for g in idle)
        for name, ivs in trace.spans.items():
            if name in ("bench.trace", "bench.window"):
                continue
            got = overlap_total(idle, union(ivs))
            if got > 0:
                idle_under[name] = idle_under.get(name, 0.0) + got
        whole = [m for m in dev.modules if m.start >= lo and m.end <= hi]
        for m in whole:
            rec = modules.setdefault(module_key(m.text), {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += m.end - m.start
        # kernels: Mosaic custom calls, by the program they ran in
        mi = 0
        for o in leaf:
            if custom_call_target(o.text) != KERNEL_TARGET:
                continue
            while mi < len(whole) and whole[mi].end < o.start:
                mi += 1
            if mi < len(whole) and whole[mi].start <= o.start:
                rec = kernels.setdefault(
                    module_key(whole[mi].text), {"count": 0, "seconds": 0.0}
                )
                rec["count"] += 1
                rec["seconds"] += o.end - o.start
        coll = union(clip(
            ((o.start, o.end) for o in list(dev.ops) + list(dev.async_ops)
             if is_collective(o.text)), lo, hi))
        compute = union(clip(
            ((o.start, o.end) for o in leaf if not is_collective(o.text)), lo, hi))
        coll_s += total(coll)
        coll_exposed_s += total(subtract(coll, compute))
    all_gaps.sort(reverse=True)
    return {
        "window_s": hi - lo,
        "busy_s": busy_s / ndev,
        "devices": len(trace.devices),
        "modules": modules,
        "kernels": kernels,
        "device_ops": [
            [k, v / ndev] for k, v in
            sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[name_gap(g, trace.spans), length] for length, g in all_gaps[:top]],
        "idle_under": {k: v / ndev for k, v in idle_under.items()},
        "collective_s": coll_s / ndev,
        "collective_exposed_s": coll_exposed_s / ndev,
    }
