"""Scheduler: idle device milliseconds per decode step under the host
work of one iteration: the program's `scheduler.step.begin`,
`.decode.plan`, `.decode.commit` and `.end` spans, and the own share of
`.decode.dispatch` (what the engine does before the program is
enqueued); each part is in the notes."""

from benchmarks.lib import spans


def read(run):
    if run.record.get("kind") != "serve" or not run.trace:
        return None
    parts = spans.decode_host_parts(run.trace["idle_under"])
    return spans.idle_ms_per_execution(
        run, "decode_module", parts, "decode_host_idle_ms_parts"
    )
