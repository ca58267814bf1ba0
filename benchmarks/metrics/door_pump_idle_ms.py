"""Front door: idle device milliseconds per decode step between two
steps, under the benchmark's `door.pump` span (publish, then the event
loop's other tasks). The part under the program's `door.pump.publish`
span goes to the line's notes."""

from benchmarks.lib import spans


def read(run):
    if run.record.get("kind") != "serve" or not run.trace:
        return None
    under = run.trace["idle_under"]
    spans.idle_ms_per_execution(
        run, "decode_module", spans.named(under, ("door.pump.publish",)),
        "door_pump_idle_ms_parts",
    )
    return spans.idle_ms_per_execution(
        run, "decode_module", spans.named(under, ("door.pump",))
    )
