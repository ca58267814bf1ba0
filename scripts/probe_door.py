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

Each as [p50, p90]. With `PROBE_DUMP=<file>` in the environment every
request's stamps and every step's tuple are written there as JSON, so
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
        }
    }), flush=True)
    dump = os.environ.get("PROBE_DUMP")
    if dump:
        stamps = ("index", "segment", "prompt_len", "asked", "due", "started",
                  "accepted", "admit", "admit_iter", "first", "last", "done", "tokens")
        with open(dump, "w") as f:
            json.dump({
                "requests": [{k: getattr(r, k) for k in stamps} for r in seen["records"]],
                "steps": [[float(x) for x in step] for step in seen["steps"]],
            }, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
