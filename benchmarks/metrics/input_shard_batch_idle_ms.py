"""Executor: idle device milliseconds per train step under the
program's `train.input.shard_batch` span (`executor.shard_batch`: one
`device_put` per input). The split by input (`train.input.shard_batch.x`,
`.label`) goes to the line's notes."""

from benchmarks.lib import spans


def read(run):
    value = spans.train_phase_idle_ms(run, ("train.input.shard_batch",))
    if value is not None:
        under = run.trace["idle_under"]
        by_input = spans.named(under, spans.children(under, "train.input.shard_batch"))
        spans.idle_ms_per_execution(
            run, "step_module", by_input, "input_shard_batch_idle_ms_by_input"
        )
    return value
