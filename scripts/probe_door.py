#!/usr/bin/env python3
"""Where a served request's time to first token goes, by hand-off.

    python3 scripts/probe_door.py --workload <serving cell> --seed <n> --seconds 51

Runs one cell of the benchmark exactly as `benchmarks/run.py` runs it
(same arguments, same result line) and prints one more JSON line after
it, from the harness's own stamps of the requests due in the window:

* `gen_late_ms`: due -> the harness's generator woke; `gen_late_reader_ms`
  the same less the step that held the loop when the request fell due
  (`benchmarks/lib/readers.py:gen_late_ms`, what `gen_late_p90_ms.chat`
  reads), over every request of the run;
* `door_ms`: due -> `FrontDoor.submit` accepted (`door_wait_p90_ms`);
* `queue_ms`: accepted -> the scheduler's `admit` event;
* `admit_to_first_ms`: admitted -> first token at the client;
* `ttft_ms`: due -> first token (`ttft_p50_ms`);
* `door_over_period`: the door's wait in iterations (`tpot` median);
* `submits_by_pass`: `FrontDoor.submits_by_pass` (PR 42), the
  submissions by the pass of the event loop since the last `step()`
  in which they crossed the door, and `later_share`, the share in
  passes 2 and later of those that found an iteration running. A tree
  from before PR 42 has no such counter and reads `null`;
* `engine_counters`: the scheduler's final `decode_steps`,
  `prefill_programs`, `kernel_fallbacks` and the counters that say which
  maker the step programs took (`moe_kernel_programs_*`, PR 46;
  `kda_kernel_programs_decode`, PR 48; `kda_kernel_programs_prefill`,
  PR 52; `null` on a tree before them).

* `program_split`: the same time to first token from the PROGRAM's own
  record (`engine.step_log`, `telemetry.trace.request_parts`; PR 54):
  `queue_ms`, `ahead_ms` (admit -> its prefill is on the device's
  queue), `inflight_ms` (-> the prefill's outputs are the host's),
  `emit_ms` (-> `first_token`), their sum `admit_to_first_ms`, and
  `harness_less_program_ms`, the harness's `first - admit` less the
  program's for the same request: the client's wake-up. `gap_*_share`:
  a request's first token -> terminal event by what it waited for.
  `sum_error_us`: the largest distance of a request's parts from its
  own `submit -> first_token` and `first_token -> terminal` (rounding).
  `null` on a tree without the record.

Each as [p50, p90]. With `PROBE_DUMP=<file>` in the environment every
request's stamps (the program's split beside them, under `program`),
every step's tuple and every step record are written there as JSON, so
that two trees can be compared request by request (same seed, same
index) where a percentile is one request's luck (`serve_gpt2m_chat`'s
`ttft_p50_ms`: `PERF.md` section 7, PR 43). Run it in a process that fetches its programs (after
one `benchmarks/run.py` of the same cell in the same checkout): a
process that compiles starts over as `benchmarks/run.py` and the probe's
line is lost.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]


def main(argv=None) -> int:
    import types

    from benchmarks import run
    from benchmarks.lib import readers, stats
    from benchmarks.lib.loading import load_module

    lm = load_module("families", "decoder_lm")
    seen = {}
    drive = lm.drive

    async def spying_drive(ctx, door, backend, plan, traffic, records, vocab):
        seen.update(door=door, records=records, steps=backend.steps, backend=backend)
        return await drive(ctx, door, backend, plan, traffic, records, vocab)

    lm.drive = spying_drive
    rc = run.main(argv)
    if rc or not seen:
        return rc

    def both(values):
        return [stats.percentile(values, p, beyond=0) for p in (50, 90)]

    win = [r for r in seen["records"] if r.segment == "window" and r.first]
    door = [1e3 * (r.accepted - r.due) for r in win]
    period = stats.median(readers.tpots_of(win, finished_only=True))
    by_pass = getattr(seen["door"], "submits_by_pass", None)
    sched = getattr(seen["backend"], "_sched", None)
    later = None
    if by_pass is not None:
        running = sum(n for label, n in by_pass.items() if label != "idle")
        later = (running - by_pass.get("1", 0)) / running if running else 0.0
    split, by_rid, log = program_split(sched, win, both)
    print(json.dumps({
        "door_probe": {
            "requests": len(win),
            "gen_late_ms": both([1e3 * (r.started - r.due) for r in win]),
            "gen_late_reader_ms": both(readers.gen_late_ms(types.SimpleNamespace(
                record={"kind": "serve", "steps": seen["steps"], "requests": seen["records"]}
            ))),
            "door_ms": both(door),
            "queue_ms": both([1e3 * (r.admit - r.accepted) for r in win if r.admit]),
            "admit_to_first_ms": both([1e3 * (r.first - r.admit) for r in win if r.admit]),
            "ttft_ms": both([1e3 * (r.first - r.due) for r in win]),
            "tpot_p50_ms": period,
            "door_over_period": [d / period for d in both(door)],
            "submits_by_pass": by_pass,
            "later_share": later,
            # which makers the step programs took (None on a tree before
            # the counter): equal to `decode_steps` where a kernel engaged
            "engine_counters": {
                name: getattr(sched.stats, name, None) for name in (
                    "decode_steps", "prefill_programs", "kernel_fallbacks",
                    "moe_kernel_programs_prefill", "moe_kernel_programs_decode",
                    "kda_kernel_programs_decode", "kda_kernel_programs_prefill",
                )
            } if sched is not None else None,
            "program_split": split,
        }
    }), flush=True)
    dump = os.environ.get("PROBE_DUMP")
    if dump:
        stamps = ("index", "segment", "prompt_len", "asked", "due", "started",
                  "accepted", "admit", "admit_iter", "first", "last", "done", "tokens")
        fields = ("seq", "kind", "bucket", "iteration", "t_call", "t_enqueued",
                  "t_read", "t_ready", "rows", "rids", "chained")
        with open(dump, "w") as f:
            json.dump({
                "requests": [
                    dict({k: getattr(r, k) for k in stamps},
                         program=by_rid.get(r.rid))
                    for r in seen["records"]
                ],
                "steps": [[float(x) for x in step] for step in seen["steps"]],
                "step_records": [
                    {k: getattr(rec, k) for k in fields} for rec in log
                ],
            }, f)
    return 0


def program_split(sched, win, both):
    """The window requests' time by the program's own record: the
    summary, each request's parts by rid, and the records."""
    log = getattr(sched, "step_log", None)
    if log is None:
        return None, {}, []
    from flexflow_tpu.telemetry.trace import request_parts

    records = list(log.records)
    by_rid, worst = {}, 0.0
    for s in log.requests():
        parts = request_parts(s, records)
        by_rid[s.rid] = {
            "stamps": list(s[1:]), "ttft": parts.ttft, "gap": parts.gap,
            "others_prefills": len(parts.others_at),
        }
        if parts.ttft:
            worst = max(worst, abs(sum(parts.ttft.values()) - (s.first_token - s.submit)))
        if parts.gap:
            worst = max(worst, abs(sum(parts.gap.values()) - (s.terminal - s.first_token)))
    mine = [(r, by_rid[r.rid]) for r in win if by_rid.get(r.rid, {}).get("ttft")]
    split = {"requests": len(mine), "sum_error_us": 1e6 * worst}
    for part in ("queue", "ahead", "inflight", "emit"):
        split[part + "_ms"] = both([1e3 * p["ttft"][part] for _, p in mine])
    first = [
        1e3 * (p["ttft"]["ahead"] + p["ttft"]["inflight"] + p["ttft"]["emit"])
        for _, p in mine
    ]
    split["admit_to_first_ms"] = both(first)
    split["harness_less_program_ms"] = both(
        [1e3 * (r.first - r.admit) - f for (r, _), f in zip(mine, first)]
    )
    gaps = [p["gap"] for _, p in mine if p["gap"] and sum(p["gap"].values()) > 0]
    for part in ("others_prefill", "decode", "host"):
        split[f"gap_{part}_share"] = both(
            [100.0 * g[part] / sum(g.values()) for g in gaps]
        )
    return split, by_rid, records


if __name__ == "__main__":
    sys.exit(main())
