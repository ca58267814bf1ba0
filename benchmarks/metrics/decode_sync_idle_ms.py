"""Engine: idle device milliseconds per decode step while the host
blocks on the device: under the program's `scheduler.step.decode.wait`
(`block_until_ready` of the step's outputs) and
`scheduler.step.decode.readback` (tokens and logits brought to the
host) spans; each part is in the notes."""

from benchmarks.lib import spans


def read(run):
    if run.record.get("kind") != "serve" or not run.trace:
        return None
    parts = spans.named(run.trace["idle_under"], (
        "scheduler.step.decode.wait", "scheduler.step.decode.readback",
    ))
    return spans.idle_ms_per_execution(
        run, "decode_module", parts, "decode_sync_idle_ms_parts"
    )
