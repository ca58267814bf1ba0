"""The trainer's callback adds no host sync inside a step, stops only at
an epoch's end, and its stamps give a rate over whole epochs; the
server's backend wrapper only delegates, stamps and counts."""

import time
import types

import numpy as np
import pytest

from benchmarks.families import decoder_lm, ff_transformer
from benchmarks.lib import stats, window


class FakeTracer:
    started = stopped = False


def fake_ctx(trace=False, seconds=1.0):
    ctx = types.SimpleNamespace()
    ctx.trace, ctx.seconds, ctx.tracer = trace, seconds, FakeTracer()
    ctx.compiles = types.SimpleNamespace(reset=lambda: None, snapshot=lambda: {"compiles": 0})
    return ctx


def test_untraced_callback_touches_nothing_inside_a_step(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("the untraced run opened a span or touched JAX in a step")

    monkeypatch.setattr(window, "span", boom)
    import jax

    monkeypatch.setattr(jax, "block_until_ready", boom)
    monkeypatch.setattr(jax, "device_get", boom)
    clock = ff_transformer.EpochClock(fake_ctx(), lead_in=1, trace_epochs=2, batches=3)
    clock.on_epoch_begin(0)
    for it in range(3):
        assert clock.on_batch_begin(it) is None
        assert clock.on_batch_end(it) is None
    assert clock.stamps == []  # a stamp is taken only at an epoch's end


def test_clock_stops_at_the_first_epoch_end_past_the_window(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(time, "perf_counter", lambda: now[0])
    clock = ff_transformer.EpochClock(fake_ctx(seconds=5.0), lead_in=1, trace_epochs=2, batches=3)
    stops = []
    for epoch, t in enumerate([102.0, 104.1, 106.0, 108.3, 110.0]):
        now[0] = t
        stops.append(clock.on_epoch_end(epoch))
        if stops[-1]:
            break
    # lead-in ends at 102.0; 106.0 is 4.0 s in, 108.3 is 6.3 s in: stop there
    assert stops == [False, False, False, True]
    rate, n, elapsed = stats.whole_unit_rate(clock.stamps[0:], 1000)
    assert (n, elapsed) == (3, pytest.approx(6.3))  # whole epochs, real elapsed


class FakeScheduler:
    telemetry = "the scheduler's own"

    def __init__(self):
        self.stats = types.SimpleNamespace(
            decode_steps=0, prefill_batches=0, busy_slot_steps=0, slot_steps=0)
        self.calls = []

    def submit(self, request, strict=True):
        self.calls.append(("submit", request, strict))
        return True

    def cancel(self, rid):
        self.calls.append(("cancel", rid))
        return True

    def work_pending(self):
        return True

    def step(self):
        self.stats.decode_steps += 1
        self.stats.busy_slot_steps += 2
        self.stats.slot_steps += 4


def test_backend_wrapper_delegates_and_counts():
    cache = types.SimpleNamespace(
        active_slots=lambda: [0, 2], lengths=np.array([5, 0, 7, 0]), pages_in_use=3)
    sched = FakeScheduler()
    backend = decoder_lm.SteppedBackend(sched, cache)
    assert backend.telemetry == "the scheduler's own"
    assert backend.submit("r", strict=False) and backend.cancel(7) and backend.work_pending()
    assert sched.calls == [("submit", "r", False), ("cancel", 7)]
    backend.step()
    backend.step()
    backend.close_pump_span()
    (t0, t1, dec, pre, busy, slots, ctx_sum, pages, live) = backend.steps[-1]
    assert t1 >= t0 and (dec, pre, busy, slots) == (2, 0, 4, 8)
    assert (ctx_sum, pages, live) == (12, 3, 2)
