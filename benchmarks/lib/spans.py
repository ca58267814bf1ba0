"""Idle device time under the program's own host spans, per execution of
the cell's program.

The program marks the phases of its host loop with
`flexflow_tpu.telemetry.trace.span` (a `TraceAnnotation` whose name is
its parent's plus a dotted suffix: `train.input.shard_batch`,
`scheduler.step.decode.wait`). `lib.trace.read_xplane` keeps every span
whose name starts with one of the family's prefixes and `summarize` gives
`idle_under[name]`, the seconds the device was idle while that span was
open, averaged over the chips. The readers here divide by the executions
of the cell's program on one chip in the same window, so that a cell's
phases add up to its idle time per step. A program without these spans
(any commit before PR 24) gives no name to read: the readers return None
and the line leaves the metric out.
"""

from __future__ import annotations

from typing import Iterable, Optional


def executions(run, module_key: str) -> Optional[float]:
    """Executions of the record's `module_key` program in the traced
    window, on one chip (`modules` counts every chip's)."""
    if not run.trace:
        return None
    mod = run.trace["modules"].get(run.record.get(module_key, ""))
    if not mod or not mod["count"]:
        return None
    return mod["count"] / max(1, run.trace.get("devices", 1))


def children(idle_under: dict, parent: str, also: Iterable[str] = ()) -> list:
    """The spans directly inside `parent`: by name (`parent.x`, and not
    `parent.x.y` when `parent.x` is there), and those under the prefixes
    in `also`, for a parent whose children are named after another
    (`scheduler.step.admit` holds `scheduler.step.prefill.*`)."""
    prefixes = (parent + ".",) + tuple(also)
    inside = sorted(n for n in idle_under if n.startswith(prefixes))
    return [
        n for n in inside
        if not any(n.startswith(m + ".") for m in inside if m != n)
    ]


def own_s(idle_under: dict, parent: str, also: Iterable[str] = ()) -> float:
    """A parent's own share: its idle seconds less its children's."""
    got = idle_under.get(parent, 0.0)
    got -= sum(idle_under[c] for c in children(idle_under, parent, also))
    return max(got, 0.0)


def idle_ms_per_execution(run, module_key: str, parts: dict, note: str = ""):
    """`parts` maps a label to idle seconds; the metric is their sum in
    milliseconds per execution of the program. None when the program did
    not run in the window or none of the spans was there. With `note`,
    the parts go to the line's notes under that name."""
    n = executions(run, module_key)
    if n is None or not parts:
        return None
    if note:
        run.notes[note] = {k: 1e3 * v / n for k, v in parts.items()}
    return 1e3 * sum(parts.values()) / n


def named(idle_under: dict, names: Iterable[str]) -> dict:
    """The spans of `names` that the trace holds, by their last word."""
    return {
        n.rsplit(".", 1)[-1]: idle_under[n] for n in names if n in idle_under
    }


def train_phase_idle_ms(run, names: Iterable[str], note: str = ""):
    if run.record.get("kind") != "train" or not run.trace:
        return None
    parts = named(run.trace["idle_under"], names)
    return idle_ms_per_execution(run, "step_module", parts, note)


def decode_host_parts(idle_under: dict) -> dict:
    """Host work of one decode step while the device waits: the
    iteration's begin and end, the decode plan and commit, and what
    `decode.dispatch` does before its program is enqueued (its own
    share: the wait inside it is the engine's sync)."""
    step = "scheduler.step."
    parts = named(idle_under, (
        step + "begin", step + "decode.plan", step + "decode.commit", step + "end",
    ))
    if step + "decode.dispatch" in idle_under:
        parts["dispatch"] = own_s(
            idle_under, step + "decode.dispatch", (step + "decode.wait",)
        )
    return parts


def prefill_host_parts(idle_under: dict) -> dict:
    """Host work of one admission while the device waits: `admit`'s own
    share (queue pop, page claim, emit), the prefill's padding and
    placing, what `prefill.dispatch` does before the program runs, and
    the readback of the admitted prompts' tokens and logits."""
    step = "scheduler.step."
    parts = named(idle_under, (
        step + "prefill.pack", step + "prefill.dispatch", step + "prefill.readback",
    ))
    if step + "admit" in idle_under:
        parts["admit"] = own_s(
            idle_under, step + "admit",
            (step + "prefill.", step + "prefill_suffix"),
        )
    return parts


def prefill_host_idle_ms(run, note: str):
    if run.record.get("kind") != "serve" or not run.trace:
        return None
    parts = prefill_host_parts(run.trace["idle_under"])
    return idle_ms_per_execution(run, "prefill_module", parts, note)
