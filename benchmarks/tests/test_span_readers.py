"""The readers of the program's own spans, on the shape `lib.trace.summarize`
gives them: `idle_under` and `modules` dicts in, the metric out."""

import pytest

from benchmarks.lib import loading, spans
from benchmarks.lib.readers import Run

STEP = "scheduler.step."


def make_run(kind, idle_under, modules, devices=1):
    record = {
        "kind": kind, "step_module": "jit_step",
        "decode_module": "jit__decode_impl_paged",
        "prefill_module": "jit__prefill_impl_paged",
    }
    trace = {
        "window_s": 10.0, "busy_s": 7.0, "devices": devices,
        "idle_under": idle_under, "modules": modules,
    }
    return Run(record, trace, {"platform": "tpu"}, None, 0.0, {})


def read(name, run):
    return loading.load_module("metrics", name).read(run)


TRAIN_IDLE = {
    "train.input": 2.1, "train.epoch_end": 0.7,
    "train.input.next_batch": 0.2,
    "train.input.shard_batch": 1.6,
    "train.input.shard_batch.x": 0.9, "train.input.shard_batch.label": 0.6,
    "train.input.dispatch": 0.1,
    "train.epoch_end.drain": 0.3, "train.epoch_end.losses": 0.1,
    "train.epoch_end.reset": 0.24,
}

SERVE_IDLE = {
    "scheduler.step": 4.0, "door.pump": 0.5, "door.pump.publish": 0.2,
    STEP + "begin": 0.1, STEP + "end": 0.3,
    STEP + "admit": 0.9, STEP + "prefill.pack": 0.2,
    STEP + "prefill.dispatch": 0.1, STEP + "prefill.readback": 0.3,
    STEP + "decode.plan": 0.2, STEP + "decode.dispatch": 1.0,
    STEP + "decode.wait": 0.6, STEP + "decode.readback": 0.8,
    STEP + "decode.commit": 0.4,
}
SERVE_MODULES = {
    "jit__decode_impl_paged": {"count": 500, "seconds": 6.0},
    "jit__prefill_impl_paged": {"count": 20, "seconds": 1.0},
}


@pytest.mark.parametrize(
    "name, seconds",
    [
        ("input_next_batch_idle_ms", 0.2),
        ("input_shard_batch_idle_ms", 1.6),
        ("input_dispatch_idle_ms", 0.1),
        ("epoch_turn_idle_ms", 0.3 + 0.1 + 0.24),
    ],
)
@pytest.mark.parametrize("devices", [1, 4])
def test_trainer_phase_idle_per_step(name, seconds, devices):
    # `modules` counts every chip's executions; `idle_under` is a mean
    # over the chips: 64 steps either way
    modules = {"jit_step": {"count": 64 * devices, "seconds": 8.0 * devices}}
    run = make_run("train", TRAIN_IDLE, modules, devices)
    assert read(name, run) == pytest.approx(1e3 * seconds / 64)


def test_trainer_notes_split_the_parts():
    run = make_run("train", TRAIN_IDLE, {"jit_step": {"count": 64, "seconds": 8.0}})
    read("input_shard_batch_idle_ms", run)
    read("epoch_turn_idle_ms", run)
    by_input = run.notes["input_shard_batch_idle_ms_by_input"]
    assert by_input == {"x": pytest.approx(900 / 64), "label": pytest.approx(600 / 64)}
    parts = run.notes["epoch_turn_idle_ms_parts"]
    assert set(parts) == {"drain", "losses", "reset"}
    assert sum(parts.values()) == pytest.approx(640 / 64)
    # the four phases of a step add up to what the benchmark's own two
    # spans hold, less what no program span covers
    total = sum(read(n, run) for n in (
        "input_next_batch_idle_ms", "input_shard_batch_idle_ms",
        "input_dispatch_idle_ms", "epoch_turn_idle_ms",
    ))
    assert total == pytest.approx(1e3 * (0.2 + 1.6 + 0.1 + 0.64) / 64)


def test_decode_sync_and_host_idle_per_decode_step():
    run = make_run("serve", SERVE_IDLE, SERVE_MODULES)
    assert read("decode_sync_idle_ms", run) == pytest.approx(1e3 * (0.6 + 0.8) / 500)
    # begin + plan + commit + end, and dispatch's own share (1.0 - 0.6)
    assert read("decode_host_idle_ms", run) == pytest.approx(
        1e3 * (0.1 + 0.2 + 0.4 + 0.3 + 0.4) / 500
    )
    assert run.notes["decode_host_idle_ms_parts"]["dispatch"] == pytest.approx(0.8)
    assert set(run.notes["decode_sync_idle_ms_parts"]) == {"wait", "readback"}


def test_door_pump_idle_and_the_publish_part():
    run = make_run("serve", SERVE_IDLE, SERVE_MODULES)
    assert read("door_pump_idle_ms", run) == pytest.approx(1.0)
    assert run.notes["door_pump_idle_ms_parts"] == {"publish": pytest.approx(0.4)}
    # the benchmark's span is there on any commit; the program's is not
    older = {k: v for k, v in SERVE_IDLE.items() if k in ("scheduler.step", "door.pump")}
    run = make_run("serve", older, SERVE_MODULES)
    assert read("door_pump_idle_ms", run) == pytest.approx(1.0)
    assert "door_pump_idle_ms_parts" not in run.notes


@pytest.mark.parametrize("cell", ["chat", "docs"])
def test_prefill_host_idle_per_prefill_with_own_share_of_admit(cell):
    run = make_run("serve", SERVE_IDLE, SERVE_MODULES)
    # admit's own share: 0.9 less pack, dispatch and readback inside it
    own = 0.9 - 0.2 - 0.1 - 0.3
    assert read(f"prefill_host_idle_ms.{cell}", run) == pytest.approx(
        1e3 * (own + 0.2 + 0.1 + 0.3) / 20
    )
    assert run.notes["prefill_host_idle_ms_parts"]["admit"] == pytest.approx(1e3 * own / 20)


def test_own_share_takes_direct_children_once():
    under = {"a": 1.0, "a.b": 0.6, "a.b.c": 0.5, "a.d": 0.1, "ab": 9.0, "z.q": 0.2}
    assert spans.children(under, "a") == ["a.b", "a.d"]
    assert spans.own_s(under, "a") == pytest.approx(0.3)
    assert spans.children(under, "a", ("z.",)) == ["a.b", "a.d", "z.q"]
    assert spans.own_s(under, "a.b") == pytest.approx(0.1)
    assert spans.own_s({"a": 0.1, "a.b": 0.2}, "a") == 0.0  # never negative


NEW = [
    "input_next_batch_idle_ms", "input_shard_batch_idle_ms", "input_dispatch_idle_ms",
    "epoch_turn_idle_ms", "decode_sync_idle_ms", "decode_host_idle_ms",
    "prefill_host_idle_ms.chat", "prefill_host_idle_ms.docs",
]


@pytest.mark.parametrize("name", NEW + ["door_pump_idle_ms"])
def test_none_when_the_program_did_not_run_or_nothing_was_traced(name):
    kind = "train" if name.startswith(("input", "epoch")) else "serve"
    under = TRAIN_IDLE if kind == "train" else SERVE_IDLE
    assert read(name, make_run(kind, under, {})) is None
    run = make_run(kind, under, {})
    run.trace = None  # an untraced run
    assert read(name, run) is None
    other = "serve" if kind == "train" else "train"
    assert read(name, make_run(other, under, {"jit_step": {"count": 4, "seconds": 1}})) is None


@pytest.mark.parametrize("name", NEW)
def test_none_on_a_program_without_the_spans(name):
    """A commit before PR 24 opens none of these spans: the trace holds
    the benchmark's own names only, and the line leaves the metric out."""
    kind = "train" if name.startswith(("input", "epoch")) else "serve"
    under = {"train.input": 2.1, "train.epoch_end": 0.7} if kind == "train" else {
        "scheduler.step": 4.0, "door.pump": 0.5, "door.submit": 0.01,
    }
    modules = dict(SERVE_MODULES, jit_step={"count": 64, "seconds": 8.0})
    assert read(name, make_run(kind, under, modules)) is None
