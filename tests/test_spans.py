"""The program's host phases on the profiler's clock (ISSUE 24).

`telemetry.trace.span` is the one way a region of the trainer's loop or
the server's step is marked. Held here:

* a tiny `fit()` and a tiny scheduler run under a `jax.profiler` session
  yield exactly the named spans, properly nested, read back through
  `jax.profiler.ProfileData` (the CPU backend's `python` line carries
  `TraceAnnotation`s as the chip's `python3` line does);
* with no session and no `Telemetry` the primitive records nothing and
  no `Tracer` is built;
* with a `Telemetry` trace attached the Chrome export carries the same
  names and still passes `validate_trace`;
* token streams and trained parameters are bit-identical with the spans
  read (a session running, a tracer attached) and unread;
* the counts taken at the same boundaries (`device_syncs`,
  `readback_bytes`, `prefill_tokens_real/padded`) equal hand counts.
"""

import collections
import glob
import os

import numpy as np
import pytest

import jax

from flexflow_tpu import (
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.serving import Request, ServeConfig, Telemetry, build_scheduler
from flexflow_tpu.telemetry import NullTracer, Tracer, span, validate_trace
from flexflow_tpu.telemetry import trace as trace_mod
from flexflow_tpu.utils import profiling

pytestmark = [pytest.mark.serving, pytest.mark.telemetry]

VOCAB = 50
SLOTS = 4

TRAIN_SPANS = {
    "train.epoch_end.reset",
    "train.input.next_batch",
    "train.input.next_batch.lease_wait",
    "train.input.shard_batch",
    "train.input.shard_batch.x",
    "train.input.shard_batch.label",
    "train.input.dispatch",
    "train.epoch_end.drain",
    "train.epoch_end.losses",
}
STEP = "scheduler.step."
#: the served path: ServeConfig() defaults but for the sizes
SERVE_SPANS = {
    STEP + s for s in (
        "begin", "admit", "prefill.pack", "prefill.dispatch",
        "prefill.readback", "decode.plan", "decode.dispatch", "decode.wait",
        "decode.readback", "decode.commit", "end",
    )
}
#: child -> the span it has to sit inside
PARENTS = {
    "train.input.next_batch.lease_wait": "train.input.next_batch",
    "train.input.shard_batch.x": "train.input.shard_batch",
    "train.input.shard_batch.label": "train.input.shard_batch",
    STEP + "prefill.pack": STEP + "admit",
    STEP + "prefill.dispatch": STEP + "admit",
    STEP + "prefill.readback": STEP + "admit",
    STEP + "decode.wait": STEP + "decode.dispatch",
    STEP + "chunk.wait": STEP + "chunk.dispatch",
    STEP + "verify.wait": STEP + "verify.dispatch",
}


# -- tiny programs -------------------------------------------------------------


def _trainer():
    cfg = FFConfig(batch_size=8)
    model = FFModel(cfg)
    x = model.create_tensor([8, 16], name="x")
    t = model.dense(x, 16, activation=ActiMode.RELU, name="d0")
    model.dense(t, 4, name="head")
    model.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:1],
    )
    return model


def _dataset():
    rng = np.random.RandomState(0)
    return (
        rng.randn(24, 16).astype(np.float32),
        rng.randint(0, 4, size=(24, 1)).astype(np.int32),
    )


def _fit(telemetry=None, epochs=2):
    model = _trainer()
    x, y = _dataset()
    model.fit(x, y, epochs=epochs, verbose=False, telemetry=telemetry)
    return model


@pytest.fixture(scope="module")
def lm():
    cfg = FFConfig(batch_size=SLOTS, seed=0)
    model = FFModel(cfg)
    tok = model.create_tensor([SLOTS, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        model, tok, vocab_size=VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=64,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:1],
    )
    return model


#: three requests, scripted: two admitted together, the third queued
#: behind them (max_seqs=2), so the run has two prefill batches
SCRIPT = ([1, 2, 3], [4, 5, 6, 7, 8], [9, 8])


def _requests(max_new=4):
    return [
        Request(rid=i, prompt=list(p), max_new_tokens=max_new)
        for i, p in enumerate(SCRIPT)
    ]


def _serve(lm, telemetry=None, **kw):
    serve = ServeConfig(max_seqs=2, max_seq_len=32, **kw)
    sched, engine, cache = build_scheduler(lm, serve, telemetry=telemetry)
    done = sched.run(_requests())
    assert all(r.ok for r in done)
    return sched, engine, {r.rid: list(r.generated) for r in done}


# -- reading a profile ---------------------------------------------------------


def _profiled(tmp_path, fn):
    """Run `fn` inside a profiler session; return (fn's result, the
    events of the Python thread's line as (name, start_ns, end_ns))."""
    with profiling.trace(str(tmp_path)):
        out = fn()
    found = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    assert len(found) == 1, found
    data = jax.profiler.ProfileData.from_file(found[0])
    events = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name in ("python", "python3"):
                events += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
    return out, events


def _ours(events, prefixes):
    return [e for e in events if e[0].startswith(prefixes)]


def _assert_nested(events):
    """Any two spans of the thread are disjoint or one holds the other,
    and every child sits inside a span of its parent's name."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    stack = []
    for name, start, end in ordered:
        while stack and start >= stack[-1][2]:
            stack.pop()
        if stack:
            assert end <= stack[-1][2], (name, stack[-1][0])
        parent = PARENTS.get(name)
        if parent is not None:
            assert parent in [s[0] for s in stack], (name, [s[0] for s in stack])
        stack.append((name, start, end))


def test_fit_under_a_profiler_session_yields_exactly_the_named_spans(tmp_path):
    _, events = _profiled(tmp_path, lambda: _fit(epochs=2))
    ours = _ours(events, ("train.",))
    assert {e[0] for e in ours} == TRAIN_SPANS
    _assert_nested(ours)
    count = {n: sum(1 for e in ours if e[0] == n) for n in TRAIN_SPANS}
    # 2 epochs of 3 batches: one of each per step, one of each per epoch
    assert count["train.input.next_batch"] == 6
    # from the third batch on each takes back the slot of the batch two
    # before it, across the epoch's turn too: the ring goes on by itself
    # there and no lease ends under the reset (ISSUE 56)
    assert count["train.input.next_batch.lease_wait"] == 4
    assert count["train.input.shard_batch.label"] == 6
    assert count["train.input.dispatch"] == 6
    assert count["train.epoch_end.drain"] == 2
    assert count["train.epoch_end.losses"] == 2
    assert count["train.epoch_end.reset"] == 2
    # within a step: gather, then place, then dispatch
    firsts = [
        min(e[1] for e in ours if e[0] == n) for n in (
            "train.epoch_end.reset", "train.input.next_batch",
            "train.input.shard_batch", "train.input.dispatch",
            "train.epoch_end.drain", "train.epoch_end.losses",
        )
    ]
    assert firsts == sorted(firsts)


@pytest.mark.parametrize("epochs", [1, 3])
def test_the_spans_the_benchmark_reads_open_once_a_step_or_once_an_epoch(
    tmp_path, epochs
):
    """`input_next_batch_idle_ms`, `input_shard_batch_idle_ms`,
    `input_dispatch_idle_ms` and `epoch_turn_idle_ms` read these six by
    name (benchmarks/metrics/): whether the loader was reset at an
    epoch's turn or had gone on by itself, each opens as often."""
    _, events = _profiled(tmp_path, lambda: _fit(epochs=epochs))
    count = collections.Counter(e[0] for e in _ours(events, ("train.",)))
    for name in (
        "train.input.next_batch", "train.input.shard_batch",
        "train.input.dispatch",
    ):
        assert count[name] == 3 * epochs, name
    for name in (
        "train.epoch_end.drain", "train.epoch_end.losses",
        "train.epoch_end.reset",
    ):
        assert count[name] == epochs, name


@pytest.mark.parametrize(
    "serve_async", [False, True, None], ids=["sync", "async", "default"]
)
def test_scheduler_under_a_profiler_session_yields_exactly_the_named_spans(
    lm, tmp_path, serve_async
):
    # the default loop is the overlapped one, under the same names
    kw = {} if serve_async is None else {"serve_async": serve_async}
    serve_async = serve_async is not False
    (sched, _, _), events = _profiled(tmp_path, lambda: _serve(lm, **kw))
    assert (sched.stats.decode_steps_chained > 0) == serve_async
    ours = _ours(events, ("scheduler.", "door."))
    assert {e[0] for e in ours} == SERVE_SPANS
    _assert_nested(ours)
    count = {n: sum(1 for e in ours if e[0] == n) for n in SERVE_SPANS}
    st = sched.stats
    assert count[STEP + "begin"] == count[STEP + "end"] == st.iterations
    # the overlapped loop opens `admit` again round an admission's
    # read-back, after the decode step it dispatched in between
    assert count[STEP + "admit"] == st.iterations + (2 if serve_async else 0)
    assert count[STEP + "prefill.dispatch"] == st.prefill_batches == 2
    assert count[STEP + "decode.dispatch"] == st.decode_steps
    # a program's first dispatch is forced, and no other: both loops run
    # the ONE decode program, whoever feeds a slot its token
    assert count[STEP + "decode.wait"] == 1
    assert count[STEP + "decode.readback"] == st.decode_steps
    assert count[STEP + "decode.commit"] == st.host_syncs == st.decode_steps


@pytest.mark.parametrize(
    "kw, expected",
    [
        (dict(token_budget=8, chunk_size=8),
         {"chunk.dispatch", "chunk.wait", "chunk.readback", "chunk.commit"}),
        (dict(spec_draft="ngram", spec_k=2),
         {"draft.propose", "verify.dispatch", "verify.wait",
          "verify.readback", "verify.commit"}),
    ],
    ids=["chunked", "spec"],
)
def test_other_step_kinds_follow_the_pattern(lm, tmp_path, kw, expected):
    """`scheduler.step.<kind>.{dispatch,wait,readback,commit}` by
    `step.kind`, in the profile and in the Chrome export alike."""
    tele = Telemetry(trace_enabled=True)
    _, events = _profiled(tmp_path, lambda: _serve(lm, telemetry=tele, **kw))
    ours = _ours(events, ("scheduler.",))
    names = {e[0] for e in ours}
    assert {STEP + s for s in expected} <= names
    _assert_nested(ours)
    chrome = {e["name"] for e in tele.tracer.events if e.get("ph") == "X"}
    assert names <= chrome
    assert validate_trace(tele.tracer.to_json(), errors="list") == []


def test_front_door_publish_span(lm, tmp_path):
    import asyncio

    from flexflow_tpu.serving.frontend.server import FrontDoor

    async def drive():
        sched, _, _ = build_scheduler(lm, ServeConfig(max_seqs=2, max_seq_len=32))
        door = FrontDoor(sched)
        rid = await door.submit([1, 2, 3], max_new_tokens=3)
        return [ev async for ev in door.stream(rid)]

    out, events = _profiled(tmp_path, lambda: asyncio.run(drive()))
    assert [ev.kind for ev in out] == ["token"] * 3 + ["done"]
    assert any(e[0] == "door.pump.publish" for e in events)


# -- nothing read, nothing recorded ---------------------------------------------


def test_no_session_and_no_telemetry_records_nothing_and_builds_no_tracer(
    lm, monkeypatch
):
    built = []
    real_init = Tracer.__init__

    def counting_init(self, *a, **k):
        built.append(self)
        real_init(self, *a, **k)

    monkeypatch.setattr(Tracer, "__init__", counting_init)
    sched, engine, _ = _serve(lm)
    model = _fit(epochs=1)
    assert built == []
    assert sched._tracer is None and engine._tracer is None
    assert sched.telemetry is None and model._telemetry is None
    # the primitive itself: no tracer, or the NullTracer, keeps no event
    null = NullTracer()
    with span("a.b"), span("a.b.c", null, {"k": 1}), null.span("a.b.d"):
        pass
    assert null.events == ()


def test_tracer_span_is_a_thin_call_of_the_primitive():
    tracer = Tracer()
    args = {"iter": 3}
    with tracer.span("outer", args=args) as s:
        assert isinstance(s, trace_mod.span)
        with span("outer.inner", tracer, cat="engine"):
            pass
        args["filled_inside"] = True  # read at exit
    xs = [e for e in tracer.events if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["outer.inner", "outer"]
    assert xs[1]["args"] == {"iter": 3, "filled_inside": True}
    assert xs[0]["cat"] == "engine" and xs[1]["cat"] == "host"
    assert xs[1]["ts"] <= xs[0]["ts"]
    assert xs[0]["ts"] + xs[0]["dur"] <= xs[1]["ts"] + xs[1]["dur"] + 1e-3
    assert validate_trace(tracer.to_json(), errors="list") == []


# -- the Chrome export carries the same names -----------------------------------


def test_chrome_export_carries_the_profile_names_and_validates(lm, tmp_path):
    tele = Telemetry(trace_enabled=True)
    _, events = _profiled(tmp_path, lambda: _serve(lm, telemetry=tele))
    profile = {e[0] for e in _ours(events, ("scheduler.",))}
    assert profile == SERVE_SPANS
    doc = tele.tracer.to_json()
    chrome = {
        e["name"] for e in doc["traceEvents"]
        if e.get("ph") == "X" and e["name"].startswith("scheduler.")
    }
    assert chrome == profile
    # what stays Chrome-only is still there, and the lanes still nest
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"iteration", "inflight:decode", "QUEUED", "RUNNING"} <= names
    assert validate_trace(doc, errors="list") == []


def test_training_chrome_export_carries_fit_phases_and_validates():
    tele = Telemetry(trace_enabled=True)
    _fit(telemetry=tele, epochs=2)
    doc = tele.tracer.to_json()
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    # place_batch is handed fit()'s tracer: its per-input spans are in
    # the Chrome export too, inside `train.input.shard_batch`
    assert names == TRAIN_SPANS | {"iteration", "epoch"}
    assert validate_trace(doc, errors="list") == []


def test_front_door_publish_span_reaches_the_chrome_export(lm):
    import asyncio

    from flexflow_tpu.serving.frontend.server import FrontDoor

    tele = Telemetry(trace_enabled=True)

    async def drive():
        sched, _, _ = build_scheduler(
            lm, ServeConfig(max_seqs=2, max_seq_len=32), telemetry=tele
        )
        door = FrontDoor(sched)
        rid = await door.submit([1, 2, 3], max_new_tokens=3)
        return [ev async for ev in door.stream(rid)]

    out = asyncio.run(drive())
    assert [ev.kind for ev in out] == ["token"] * 3 + ["done"]
    doc = tele.tracer.to_json()
    assert any(e["name"] == "door.pump.publish" for e in doc["traceEvents"])
    assert validate_trace(doc, errors="list") == []


# -- observation does not perturb ------------------------------------------------


def test_token_streams_bit_identical_with_spans_read_and_unread(lm, tmp_path):
    _, _, plain = _serve(lm)
    (_, _, profiled), _ = _profiled(tmp_path, lambda: _serve(lm))
    _, _, traced = _serve(lm, telemetry=Telemetry(trace_enabled=True))
    assert plain == profiled == traced
    assert all(len(toks) == 4 for toks in plain.values())


def test_trained_parameters_bit_identical_with_spans_read_and_unread(tmp_path):
    def leaves(model):
        return [np.asarray(w) for w in jax.tree_util.tree_leaves(model.params)]

    plain = leaves(_fit())
    profiled = leaves(_profiled(tmp_path, _fit)[0])
    traced = leaves(_fit(telemetry=Telemetry(trace_enabled=True)))
    assert len(plain) == len(profiled) == len(traced) > 0
    for a, b, c in zip(plain, profiled, traced):
        assert a.tobytes() == b.tobytes() == c.tobytes()


# -- counts at the same boundaries -----------------------------------------------


def test_sync_and_byte_counts_equal_hand_counts_on_a_scripted_run(lm):
    sched, engine, streams = _serve(lm)
    st = sched.stats
    spec = engine.cache.spec
    # two prefill batches: requests 0 and 1 together, then request 2
    # once a slot is free
    assert st.prefill_batches == 2
    batches = ([SCRIPT[0], SCRIPT[1]], [SCRIPT[2]])
    assert st.prefill_tokens_real == sum(len(p) for b in batches for p in b) == 10
    # each batch is ONE packed row of bucket(total) tokens, one program
    assert st.prefill_tokens_padded == sum(
        spec.bucket(sum(len(p) for p in b)) for b in batches
    )
    assert st.prefill_programs == engine.prefill_programs == 2
    # per prefill: the tokens and the last logits of its n prompts; per
    # decode step: ONE read of one int32 vector, the token and the finite
    # flag of every slot (the logits [max_seqs, V] stay on the device);
    # once, the wait that forces the decode program's first dispatch
    assert st.host_syncs == st.decode_steps
    assert st.device_syncs == 2 * st.prefill_batches + st.decode_steps + 1
    tok, logit = 4, 4 * VOCAB  # int32 token or flag, float32 logits row
    assert st.readback_bytes == (
        (2 + 1) * (tok + logit)
        + st.decode_steps * spec.max_seqs * (tok + tok)
    )
    assert spec.max_seqs * (tok + tok) < 200
    # the stats mirror the engine's ledgers, and every token is there
    assert st.device_syncs == engine.device_syncs
    assert st.readback_bytes == engine.readback_bytes
    assert sum(len(t) for t in streams.values()) == st.tokens_generated == 12
    # every field is a serve_stats_* gauge for the operator
    gauge = st._registry.get("serve_stats_device_syncs")
    assert gauge is not None and gauge.value == st.device_syncs


def test_dense_decode_path_has_no_wait_and_one_sync_fewer_per_step(lm, tmp_path):
    """`decode_kernel="dense"` does not force its outputs in `_dispatch`,
    not even a program's first: no `decode.wait` span at all, one sync
    fewer in the run, and the readback is a step's only blocking read."""
    (sched, engine, _), events = _profiled(
        tmp_path, lambda: _serve(lm, decode_kernel="dense")
    )
    names = {e[0] for e in _ours(events, ("scheduler.",))}
    assert names == SERVE_SPANS - {STEP + "decode.wait"}
    st = sched.stats
    assert st.device_syncs == 2 * st.prefill_batches + st.decode_steps


# -- a request's time, by what it waited for (ISSUE 54) -------------------------


def _rec(seq, kind, t_call, t_enqueued, t_read, t_ready, rids, bucket=None):
    r = trace_mod.StepRecord(kind, rows=len(rids), bucket=bucket)
    r.seq, r.rids = seq, tuple(rids)
    r.t_call, r.t_enqueued, r.t_read, r.t_ready = t_call, t_enqueued, t_read, t_ready
    return r


#: request 7 is admitted at 1.0 with request 8; its prefill is called at
#: 1.5, on the queue at 2.0 and read at 4.0. Between its tokens request 9
#: is admitted: that prefill covers 6.0-7.5, over a decode step of 7's
#: (5.5-6.5) and before the next (7.0-9.0); the last step is read at 11.
HAND = [
    _rec(1, "decode", 0.2, 0.3, 0.8, 0.9, (5,)),
    _rec(2, "prefill", 1.5, 2.0, 3.5, 4.0, (7, 8), bucket=16),
    _rec(3, "decode", 4.5, 4.6, 5.2, 5.4, (7, 8)),
    _rec(4, "decode", 5.5, 5.6, 6.4, 6.5, (7, 8)),
    _rec(5, "prefill", 6.0, 6.2, 7.3, 7.5, (9,), bucket=16),
    _rec(6, "decode", 7.0, 7.1, 8.8, 9.0, (7, 8)),
    _rec(7, "decode", 9.5, 9.6, 10.5, 11.0, (7, 9)),
    _rec(8, "decode", 11.2, 11.3, 11.8, 12.0, (9,)),
]


def test_request_parts_over_a_hand_written_log():
    events = [
        (0.5, "submit", ""), (1.0, "admit", "slot 0"), (4.25, "first_token", ""),
        (11.5, "finished", ""),
    ]
    stamps = trace_mod.lifecycle_stamps(7, events)
    assert stamps == (7, 0.5, 1.0, 4.25, 11.5)
    parts = trace_mod.request_parts(stamps, HAND)
    assert parts.ttft == pytest.approx(
        {"queue": 0.5, "ahead": 1.0, "inflight": 2.0, "emit": 0.25}
    )
    # others' prefill 6.0-7.5; its own steps cover 4.5-5.4, 5.5-6.0 (the
    # prefill's window wins from there), 7.5-9.0 and 9.5-11.0
    assert parts.gap == pytest.approx(
        {"others_prefill": 1.5, "decode": 0.9 + 0.5 + 1.5 + 1.5,
         "host": 7.25 - 1.5 - 4.4}
    )
    assert parts.others_at == (6.0,)
    assert sum(parts.ttft.values()) == pytest.approx(4.25 - 0.5, abs=1e-12)
    assert sum(parts.gap.values()) == pytest.approx(11.5 - 4.25, abs=1e-12)
    # request 9 saw nobody else's prefill; a request still running has no
    # gap parts yet, one never admitted none at all
    nine = trace_mod.request_parts(
        trace_mod.RequestStamps(9, 5.8, 5.9, 7.6, 12.5), HAND
    )
    assert nine.gap["others_prefill"] == 0.0 and nine.others_at == ()
    assert nine.ttft["ahead"] == pytest.approx(0.3)
    running = trace_mod.request_parts(
        trace_mod.RequestStamps(9, 5.8, 5.9, 7.6, None), HAND
    )
    assert running.ttft == nine.ttft and running.gap is None
    queued = trace_mod.request_parts(
        trace_mod.RequestStamps(4, 5.8, None, None, None), HAND
    )
    assert queued.ttft is None and queued.gap is None


def test_lifecycle_stamps_take_the_admission_the_first_token_came_out_of():
    events = [
        (0.0, "submit", ""), (1.0, "admit", ""), (2.0, "preempt", ""),
        (3.0, "admit", ""), (4.0, "first_token", ""), (5.0, "preempt", ""),
        (6.0, "admit", ""), (9.0, "cancelled", ""),
    ]
    assert trace_mod.lifecycle_stamps(3, events) == (3, 0.0, 3.0, 4.0, 9.0)
    assert trace_mod.lifecycle_stamps(3, events[:3]) == (3, 0.0, 1.0, None, None)


@pytest.mark.parametrize("serve_async", [True, False], ids=["async", "sync"])
def test_parts_sum_to_the_whole_on_a_scripted_run(lm, serve_async):
    """Requests 0 and 1 are admitted together; request 2 arrives while
    they decode, so its prefill lands between their tokens."""
    sched, engine, _ = build_scheduler(
        lm, ServeConfig(max_seqs=4, max_seq_len=32, serve_async=serve_async)
    )
    first, second, late = (
        Request(rid=i, prompt=list(p), max_new_tokens=8)
        for i, p in enumerate(SCRIPT)
    )
    sched.submit(first)
    sched.submit(second)
    for _ in range(3):
        sched.step()
    assert len(first.generated) >= 1 and not first.finished
    # the log answers for a request that is still running
    live = {s.rid: s for s in engine.step_log.requests()}
    assert live[0].terminal is None and live[0].first_token is not None
    sched.submit(late)
    done = sched.run()
    assert all(r.ok for r in done)
    log = engine.step_log
    assert first.admit_iter == second.admit_iter < late.admit_iter
    by_rid = {}
    for stamps in log.requests():
        parts = trace_mod.request_parts(stamps, log.records)
        by_rid[stamps.rid] = parts
        assert sum(parts.ttft.values()) == pytest.approx(
            stamps.first_token - stamps.submit, abs=1e-6
        )
        assert sum(parts.gap.values()) == pytest.approx(
            stamps.terminal - stamps.first_token, abs=1e-6
        )
        assert all(v >= 0.0 for v in parts.ttft.values())
        assert all(v >= -1e-9 for v in parts.gap.values())
    assert set(by_rid) == {0, 1, 2}
    # co-admitted: one prefill program, so the same `inflight`
    assert by_rid[0].ttft["inflight"] == by_rid[1].ttft["inflight"]
    # the late admission's prefill is in the others' token gaps, not its own
    for rid in (0, 1):
        assert len(by_rid[rid].others_at) == 1
        assert by_rid[rid].gap["others_prefill"] > 0.0
    assert by_rid[2].others_at == () and by_rid[2].gap["others_prefill"] == 0.0
    # and the program's stamps are the request's own
    for r in (first, second, late):
        assert sum(by_rid[r.rid].ttft.values()) == pytest.approx(r.ttft_s, abs=1e-4)


def test_step_logs_reaches_the_live_engines_without_holding_one(lm):
    import gc

    before = set(map(id, trace_mod.step_logs()))
    sched, engine, _ = build_scheduler(lm, ServeConfig(max_seqs=2, max_seq_len=32))
    assert engine.step_log in trace_mod.step_logs()
    sched.run(_requests())
    mine = [g for g in trace_mod.step_logs() if id(g) not in before]
    assert mine == [engine.step_log] and len(mine[0].retired) == 3
    del sched, engine, mine
    gc.collect()
    assert set(map(id, trace_mod.step_logs())) <= before
