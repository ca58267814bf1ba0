"""flexflow_tpu.telemetry: metrics registry, trace layer, SLO monitor,
and the serving-stack instrumentation (ISSUE 8).

Load-bearing proofs:

* greedy token streams are IDENTICAL with telemetry on vs off, on both
  kv layouts, sync and async — observation must never perturb the
  system it observes;
* the exported async trace SHOWS dispatch N+1 overlapping the
  in-flight window of step N (the double buffer as a picture);
* the rolling-window p95 TTFT agrees EXACTLY with the post-hoc
  `latency_percentiles` on a completed run (one percentile
  implementation, two views);
* KV-pool gauges match truth re-derived from the block tables across
  preemption, in-flight pinning, and truncate-rollback schedules on
  both page geometries — the same ledgers `check_invariants` audits;
* every fault the injector fires surfaces in the exported metrics
  keyed by site — a fault observability can't see is a bug;
* exported artifacts validate against the checked-in schemas
  (trace spans nest, no negative durations; JSONL rows typed; the
  Prometheus text grammar holds, histograms cumulative).
"""

import json
import os

import numpy as np
import pytest

import jax

from flexflow_tpu import (
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.serving import (
    ContinuousBatchingScheduler,
    FaultInjector,
    FaultPlan,
    Request,
    SchedulerStats,
    ServeConfig,
    Telemetry,
    build_scheduler,
    build_telemetry,
    latency_percentiles,
)
from flexflow_tpu.telemetry import (
    MetricsRegistry,
    NullTracer,
    RollingWindow,
    Tracer,
    ValidationError,
    percentiles,
    validate_metrics_jsonl_file,
    validate_metrics_text,
    validate_trace,
    validate_trace_file,
)
from tests.conftest import page_geometry

pytestmark = [pytest.mark.serving, pytest.mark.telemetry]

VOCAB = 50


def _lm(batch=4, seq=32, seed=0):
    cfg = FFConfig(batch_size=batch, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor([batch, seq], dtype=DataType.INT32, name="tokens")
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


@pytest.fixture(scope="module")
def lm():
    return _lm()


_PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [8, 9], [3, 1, 4, 1, 5], [7, 7, 2]]


def _requests(n=6, max_new=8, **kw):
    return [
        Request(rid=i, prompt=list(_PROMPTS[i % len(_PROMPTS)]),
                max_new_tokens=max_new, **kw)
        for i in range(n)
    ]


def _serve(layout="paged", serve_async=False, **kw):
    return ServeConfig(
        max_seqs=4, max_seq_len=32, **page_geometry(layout, 32),
        serve_async=serve_async, **kw,
    )


# -- registry -----------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("c_total", help="a counter")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    c.set_monotonic(5)
    with pytest.raises(ValueError):
        c.set_monotonic(4)
    g = reg.gauge("g")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5
    h = reg.histogram("h_ms", bounds=(1, 10, 100))
    for v in (0.5, 5, 5, 50, 500):
        h.observe(v)
    assert h.count == 5 and h.counts == [1, 2, 1, 1]
    # same (name, labels) returns the same instance; kind conflicts fail
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError):
        reg.gauge("c_total")
    # labelled series are distinct instances under one family
    a = reg.counter("f_total", labels={"site": "a"})
    b = reg.counter("f_total", labels={"site": "b"})
    assert a is not b and reg.counter("f_total", labels={"site": "a"}) is a
    with pytest.raises(ValueError):
        reg.counter("bad name!")


def test_histogram_percentile_interpolates():
    reg = MetricsRegistry()
    h = reg.histogram("h", bounds=(10, 20, 30))
    for _ in range(10):
        h.observe(15)  # all in (10, 20]
    p50 = h.percentile(50)
    assert 10 <= p50 <= 20
    assert h.percentile(100) <= 30
    assert reg.histogram("empty", bounds=(1,)).percentile(95) == 0.0


def test_prometheus_exposition_validates():
    reg = MetricsRegistry()
    reg.counter("x_total", help="things").inc(4)
    reg.gauge("depth").set(2.5)
    h = reg.histogram("lat_ms", bounds=(1, 10))
    h.observe(0.5)
    h.observe(99)
    text = reg.render_prometheus()
    assert validate_metrics_text(text, errors="list") == []
    assert "# TYPE x_total counter" in text
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_count 2" in text
    # a broken exposition is caught: non-cumulative buckets
    bad = text.replace('lat_ms_bucket{le="1"} 1', 'lat_ms_bucket{le="1"} 9')
    errs = validate_metrics_text(bad, errors="list")
    assert any("not cumulative" in e for e in errs)
    with pytest.raises(ValidationError):
        validate_metrics_text("99bad{ 1\n")


# -- rolling windows / percentiles -------------------------------------------


def test_rolling_window_wraps_and_percentiles_exact():
    w = RollingWindow(4)
    for v in (1, 2, 3, 4, 5, 6):
        w.observe(v)
    assert len(w) == 4 and w.total == 6
    assert list(w.values()) == [3, 4, 5, 6]  # oldest first
    got = w.percentiles((50, 95))
    want = {p: float(np.percentile([3, 4, 5, 6], p)) for p in (50, 95)}
    assert got == want
    assert percentiles([], (50,)) == {50: 0.0}


def test_slo_thresholds_count_violations():
    reg = MetricsRegistry()
    from flexflow_tpu.telemetry import SLOMonitor

    slo = SLOMonitor(reg, ttft_ms=10.0, itl_ms=1.0, window=16)
    slo.observe_ttft(0.005)   # 5 ms, under
    slo.observe_ttft(0.050)   # 50 ms, over
    slo.observe_itl(0.0005)   # under
    slo.observe_itl(0.002)    # over
    slo.observe_itl(0.003)    # over
    assert slo.violations() == {"ttft": 1, "itl": 2}
    snap = slo.snapshot()
    assert snap["thresholds_ms"] == {"ttft": 10.0, "itl": 1.0}
    assert snap["ttft_observations"] == 2


# -- trace validation ---------------------------------------------------------


def _span(name, ts, dur, tid=1):
    return {"ph": "X", "name": name, "cat": "t", "pid": 1, "tid": tid,
            "ts": ts, "dur": dur}


def test_trace_validator_accepts_nesting_rejects_overlap():
    ok = {"traceEvents": [
        _span("outer", 0, 100), _span("inner", 10, 20),
        _span("sibling", 40, 10), _span("other-lane", 50, 500, tid=2),
    ]}
    assert validate_trace(ok, errors="list") == []
    partial = {"traceEvents": [_span("a", 0, 100), _span("b", 50, 100)]}
    errs = validate_trace(partial, errors="list")
    assert any("partially overlaps" in e for e in errs)
    bad_schema = {"traceEvents": [{"ph": "X", "name": "x", "pid": 1,
                                   "tid": 1, "ts": 0, "dur": -5}]}
    errs = validate_trace(bad_schema, errors="list")
    assert any("minimum" in e or "negative" in e for e in errs)
    with pytest.raises(ValidationError):
        validate_trace({"traceEvents": [{"ph": "Z", "name": "x", "pid": 1}]})


def test_null_tracer_is_inert():
    t = NullTracer()
    with t.span("x"):
        pass
    t.complete("a", "b", 0, 1)
    t.instant("i", "c")
    t.request_lifecycle(None)
    with pytest.raises(RuntimeError):
        t.save("/tmp/nope.json")


# -- stats façade -------------------------------------------------------------


def test_scheduler_stats_facade_over_registry():
    reg = MetricsRegistry()
    stats = SchedulerStats(registry=reg)
    stats.tokens_generated += 3
    stats.finished_requests = 2
    stats.ttft_sum_s += 0.5
    # reads and the registry gauge are the SAME storage
    assert reg.get("serve_stats_tokens_generated").value == 3
    reg.get("serve_stats_tokens_generated").value = 7
    assert stats.tokens_generated == 7
    # derived properties still work and publish as gauges
    assert stats.mean_ttft_s == 0.25
    stats.publish_derived()
    assert reg.get("serve_stats_mean_ttft_s").value == 0.25
    d = stats.as_dict()
    assert d["tokens_generated"] == 7 and "occupancy" in d
    # standalone (no telemetry): private registry, same surface
    s2 = SchedulerStats()
    s2.decode_steps += 1
    assert s2.decode_steps == 1 and "decode_steps=1" in repr(s2)


# -- serve-path integration ---------------------------------------------------


@pytest.fixture(scope="module")
def reference_streams(lm):
    """Telemetry-off greedy streams per page geometry (the sync loop; the
    async loop is proved token-identical to it elsewhere)."""
    out = {}
    for layout in ("one_page", "paged"):
        sched, _, _ = build_scheduler(lm, _serve(layout))
        done = sched.run(_requests())
        out[layout] = {r.rid: list(r.generated) for r in done}
        assert sched.telemetry is None  # no knobs -> no bundle
    return out


@pytest.mark.parametrize("layout", ["one_page", "paged"])
@pytest.mark.parametrize("serve_async", [False, True])
def test_streams_identical_with_telemetry(lm, reference_streams, layout,
                                          serve_async):
    serve = _serve(layout, serve_async, telemetry=True,
                   slo_ttft_ms=0.01, slo_itl_ms=0.01)
    sched, _, _ = build_scheduler(lm, serve)
    assert sched.telemetry is not None and sched.telemetry.enabled
    done = sched.run(_requests())
    got = {r.rid: list(r.generated) for r in done}
    assert got == reference_streams[layout]
    # the run actually recorded: stats gauges live in the shared
    # registry, SLO windows filled, spans exist
    reg = sched.telemetry.registry
    assert reg.get("serve_stats_tokens_generated").value == sum(
        len(v) for v in got.values()
    )
    assert sched.telemetry.slo.ttft_window.total == len(got)
    assert any(
        e.get("name") == "iteration" for e in sched.telemetry.tracer.events
    )


@pytest.fixture(scope="module")
def async_run(lm, tmp_path_factory):
    """One fully-exported async run: trace + metrics +
    JSONL on disk, scheduler retained — shared by the artifact tests."""
    tmp = tmp_path_factory.mktemp("tele")
    paths = {
        "metrics_out": str(tmp / "metrics.prom"),
        "metrics_jsonl": str(tmp / "metrics.jsonl"),
        "trace": str(tmp / "trace.json"),
    }
    serve = _serve(serve_async=True, slo_ttft_ms=2000.0,
                   slo_itl_ms=500.0, **paths)
    sched, engine, cache = build_scheduler(lm, serve)
    done = sched.run(_requests(n=8, max_new=8))
    return sched, done, paths


def test_exported_artifacts_validate_against_schemas(async_run):
    sched, done, paths = async_run
    for p in paths.values():
        assert os.path.exists(p), p
    validate_metrics_text(open(paths["metrics_out"]).read())
    validate_metrics_jsonl_file(paths["metrics_jsonl"])
    validate_trace_file(paths["trace"])


def test_async_trace_shows_dispatch_overlapping_reconcile(async_run):
    """The acceptance picture: the exported trace for an async run has
    step N+1's in-flight window OPENING (its dispatch) before step N's
    window closes (its reconcile) — the one-step-stale overlap made
    visible."""
    sched, done, paths = async_run
    doc = json.load(open(paths["trace"]))
    windows = {
        e["args"]["step"]: (e["ts"], e["ts"] + e["dur"])
        for e in doc["traceEvents"]
        if e.get("ph") == "X" and e.get("name", "").startswith("inflight:")
    }
    assert len(windows) >= 4
    overlapping = sum(
        1
        for n, (t0, t1) in windows.items()
        if n + 1 in windows and windows[n + 1][0] < t1
    )
    # steady-state pipelining: most consecutive windows overlap
    assert overlapping >= len(windows) // 2, (overlapping, len(windows))
    # and the host dispatch span of the NEXT iteration sits inside an
    # earlier step's open window
    disp = [
        e for e in doc["traceEvents"]
        if e.get("name") == "scheduler.step.decode.dispatch"
    ]
    assert any(
        t0 <= e["ts"] < t1
        for e in disp
        for (t0, t1) in windows.values()
    )


def test_async_trace_holds_the_prefill_windows_and_the_request_parts(lm):
    """Eight requests over four slots: the later admissions' prefills
    are dispatched with a decode step in flight and another chained
    behind them, on a lane of their own; each request's RUNNING span
    holds `ahead`, `inflight` and `emit` end to end from its admission to
    its first token, and the earlier requests show the later prefills as
    instants between their tokens."""
    tele = Telemetry(trace_enabled=True)
    sched, _, _ = build_scheduler(
        lm, _serve(serve_async=True), telemetry=tele
    )
    # staggered ends: a slot frees while the others still decode
    reqs = [
        Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=4 + 3 * (i % 4))
        for i in range(8)
    ]
    done = sched.run(reqs)
    assert all(r.ok for r in done)
    doc = tele.tracer.to_json()
    validate_trace(doc)
    events = doc["traceEvents"]
    windows = [e for e in events if e.get("name", "").startswith("inflight:")]
    prefills = [e for e in windows if e["name"] == "inflight:prefill"]
    assert len(prefills) == sched.engine.prefill_programs >= 2
    assert {e["tid"] for e in prefills} == {12}
    assert all(e["args"]["bucket"] for e in prefills)
    steps = [e for e in windows if e["name"] != "inflight:prefill"]
    assert {e["tid"] for e in steps} == {10, 11}
    # a window's `step` is its record's seq: one number a program
    assert sorted(e["args"]["step"] for e in windows) == [
        r.seq for r in sched.step_log.records
    ]
    # some prefill was in flight together with a decode step
    assert any(
        p["ts"] < s["ts"] + s["dur"] and s["ts"] < p["ts"] + p["dur"]
        for p in prefills for s in steps
    )
    for r in done:
        lane = [e for e in events if e.get("pid") == 2 and e.get("tid") == r.rid]
        spans = {e["name"]: e for e in lane if e.get("ph") == "X"}
        running = spans["RUNNING"]
        first = next(e for e in lane if e["name"] == "first_token")
        at = running["ts"]
        for name in ("ahead", "inflight", "emit"):
            assert spans[name]["ts"] == pytest.approx(at, abs=2e-3)
            at = spans[name]["ts"] + spans[name]["dur"]
        assert at == pytest.approx(first["ts"], abs=2e-3)
    others = [e for e in events if e.get("name") == "others_prefill"]
    assert others and {e["ph"] for e in others} == {"i"}
    assert {e["pid"] for e in others} == {2}


def test_request_part_histograms_are_exported(async_run):
    sched, done, paths = async_run
    reg = sched.telemetry.registry
    for part in ("queue", "ahead", "inflight", "emit"):
        h = reg.get("serve_ttft_part_ms", labels={"part": part})
        assert h is not None and h.count == len(done)
    shares = [
        reg.get("serve_token_gap_part_share", labels={"part": part})
        for part in ("others_prefill", "decode", "host")
    ]
    assert all(h is not None and h.count == len(done) for h in shares)
    # the parts of one request sum to the whole: so do the shares' sums
    assert sum(h.sum for h in shares) == pytest.approx(len(done), abs=1e-6)
    text = open(paths["metrics_out"]).read()
    assert 'serve_ttft_part_ms_bucket{le="0.5",part="ahead"}' in text
    assert 'serve_token_gap_part_share_count{part="others_prefill"}' in text


def test_request_lifecycle_spans_in_trace(async_run):
    sched, done, paths = async_run
    doc = json.load(open(paths["trace"]))
    req_events = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
    names = {e["name"] for e in req_events}
    assert "QUEUED" in names and "RUNNING" in names
    assert any(e["ph"] == "i" and e["name"] == "first_token"
               for e in req_events)
    # every request's closing span carries its terminal status + tokens
    closed = {
        e["args"]["rid"]: e["args"]
        for e in req_events
        if e.get("ph") == "X" and "status" in e.get("args", {})
    }
    for r in done:
        assert closed[r.rid]["status"] == "finished"
        assert closed[r.rid]["tokens"] == len(r.generated)


def test_rolling_p95_ttft_agrees_with_post_hoc(async_run):
    sched, done, paths = async_run
    post = latency_percentiles(done, (50, 95, 99), metric="ttft")
    roll = sched.telemetry.slo.ttft_window.percentiles((50, 95, 99))
    for p in (50, 95, 99):
        assert roll[p] == pytest.approx(post[p] * 1e3, abs=1e-9), p


def test_jsonl_time_series_carries_kv_and_stats(async_run):
    sched, done, paths = async_run
    rows = [json.loads(l) for l in open(paths["metrics_jsonl"])]
    assert len(rows) == sched.stats.iterations
    iters = [r["iteration"] for r in rows]
    assert iters == sorted(iters)
    last = rows[-1]
    assert last["serve_stats_tokens_generated"] == sched.stats.tokens_generated
    assert "kv_slots_active" in last and "serve_slo_ttft_p95_ms" in last
    # all slots drained by the final iteration's sample
    assert rows[-1]["serve_running_requests"] == 0


# -- latency-percentile dedupe ------------------------------------------------


def test_latency_percentiles_shared_math(lm):
    reqs = _requests(n=3)
    for i, r in enumerate(reqs):
        r.status = "finished"
        r.submit_time = 0.0
        r.first_token_time = 0.1 * (i + 1)
        r.finish_time = 1.0
        r.generated = [1, 2]
    got = latency_percentiles(reqs, (50, 95), metric="ttft")
    want = percentiles([r.ttft_s for r in reqs], (50, 95))
    assert got == want
    assert got[95] == pytest.approx(0.29)
    with pytest.raises(ValueError):
        latency_percentiles(reqs, (50,), metric="bogus")


# -- events ring buffer -------------------------------------------------------


def test_request_events_ring_buffer_bounded(lm):
    serve = _serve(telemetry=True)
    sched, _, _ = build_scheduler(lm, serve)
    reqs = [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=12,
                    events_max=3)]
    done = sched.run(reqs)
    r = done[0]
    assert r.ok
    assert len(r.events) <= 3
    assert r.events_dropped > 0
    # the newest events survive (ring drops the OLDEST)
    assert r.events[-1][1] == "finished"
    assert sched.stats.events_dropped == r.events_dropped
    c = sched.telemetry.registry.get("serve_request_events_dropped_total")
    assert c is not None and c.value == r.events_dropped
    # and a truncated log still yields a valid lifecycle trace
    validate_trace(sched.telemetry.tracer.to_json())


# -- KV gauges vs allocator truth --------------------------------------------


def _derive_paged_truth(cache):
    spec = cache.spec
    sentinel = spec.num_pages
    live = sum(
        1
        for s in range(spec.max_seqs)
        for p in cache.block_tables[s]
        if int(p) != sentinel
    )
    return {
        "kv_slots_active": len(cache._active),
        "kv_slots_free": len(cache._free_slots),
        "kv_rows_used": int(cache.lengths.sum()),
        "kv_pages_live": live,
        "kv_pages_pinned": len(cache._limbo),
        "kv_free_heap_depth": len(cache._free_pages),
        "kv_pages_reserved": int(cache._reserved),
    }


def _check_paged_gauges(cache, extra_free=0):
    g = cache.telemetry_gauges()
    truth = _derive_paged_truth(cache)
    for k, v in truth.items():
        assert g[k] == v, (k, g[k], v)
    # conservation: live + pinned + free (+ injector-held) is the pool
    assert (
        g["kv_pages_live"] + g["kv_pages_pinned"] + g["kv_free_heap_depth"]
        + extra_free
        == cache.spec.num_pages
    )
    cache.check_invariants(extra_free=extra_free)


def test_kv_gauges_match_truth_under_preemption(lm):
    # minimum legal pool + optimistic admission forces preemption
    serve = ServeConfig(
        max_seqs=4, max_seq_len=32, kv_page_size=4,
        kv_pages=8, admission="optimistic", max_preemptions=6,
        telemetry=True,
    )
    sched, _, cache = build_scheduler(lm, serve)
    for r in _requests(n=5, max_new=10):
        sched.submit(r)
    seen_preempt = False
    while sched._work_pending():
        sched.step()
        _check_paged_gauges(cache)
        seen_preempt = seen_preempt or sched.stats.preemptions > 0
    assert seen_preempt, "schedule never preempted — pool too generous"
    assert all(r.ok for r in sched.finished)


def test_kv_gauges_match_truth_async_pinning_and_rollback(lm):
    # async + speculation: in-flight windows pin released pages (limbo)
    # and verify rollback returns pages via truncate
    serve = _serve("paged", serve_async=True, telemetry=True,
                   spec_draft="ngram", spec_k=3)
    sched, _, cache = build_scheduler(lm, serve)
    for r in _requests(n=6, max_new=10):
        sched.submit(r)
    saw_pinned = saw_inflight = False
    while sched._work_pending():
        sched.step()
        _check_paged_gauges(cache)
        g = cache.telemetry_gauges()
        saw_pinned = saw_pinned or g["kv_pages_pinned"] > 0
        saw_inflight = saw_inflight or g["kv_inflight_depth"] > 0
    assert saw_inflight, "async run never had a step in flight"
    assert sched.stats.draft_tokens_proposed > 0  # rollback path exercised


def test_kv_gauges_one_page(lm):
    """One page a slot: the page gauges are the slot gauges."""
    serve = _serve("one_page", telemetry=True)
    sched, _, cache = build_scheduler(lm, serve)
    for r in _requests(n=6, max_new=6):
        sched.submit(r)
    while sched._work_pending():
        sched.step()
        g = cache.telemetry_gauges()
        assert g["kv_slots_active"] == len(cache._active)
        assert g["kv_slots_free"] == cache.num_free
        assert g["kv_pages_live"] == g["kv_slots_active"]
        assert g["kv_free_heap_depth"] == g["kv_slots_free"]
        assert g["kv_rows_used"] == int(cache.lengths.sum())
        assert g["kv_occupancy"] == len(cache._active) / cache.spec.max_seqs
        assert g["kv_pages_reserved"] == 0
        cache.check_invariants()


# -- faults surface in metrics ------------------------------------------------


def test_every_injected_fault_surfaces_in_metrics(lm):
    plan = FaultPlan(
        nan_iters={3: [0]},
        cancel_iters={4: [2]},
        steal_iters=(2,),
        steal_pages=1,
        steal_hold=2,
        spike_rate=1.0,
        spike_s=0.0005,
    )
    injector = FaultInjector(plan, seed=0)
    serve = _serve("paged", telemetry=True)
    sched, _, cache = build_scheduler(lm, serve, injector=injector)
    for r in _requests(n=6, max_new=8):
        sched.submit(r)
    while sched._work_pending():
        sched.step()
        cache.check_invariants(extra_free=injector.stolen_pages)
    injector.release_stolen_pages(cache)
    summary = injector.summary()
    assert summary, "no faults fired — plan/seed drifted"
    assert {"nan", "cancel", "page_steal", "spike"} <= set(summary)
    text = sched.telemetry.render_prometheus()
    for site, n in summary.items():
        line = f'serve_fault_injections_total{{site="{site}"}} {n}'
        assert line in text, (line, summary)
    # ... and the injector arrived via build_scheduler's seam
    assert sched.injector is injector


@pytest.mark.parametrize("mode", ["pallas", "dense"])
def test_kernel_block_geometry_is_published_once(lm, mode):
    """The paged kernel's block (a pure function of the cache geometry)
    is a gauge beside the fallback counter where the kernel is taken,
    and absent where it is not."""
    from flexflow_tpu.ops.pallas import decode_kernel as dk

    sched, engine, cache = build_scheduler(
        lm, _serve(telemetry=True, decode_kernel=mode)
    )
    reg = sched.telemetry.registry
    names = [f"serve_decode_kernel_block_{f}" for f in
             ("pages", "rows", "vmem_bytes")]
    if mode == "dense":
        assert engine.kernel_block is None
        assert all(reg.get(n) is None for n in names)
        return
    spec = cache.spec
    want = dk.paged_block(
        1, spec.num_heads, spec.head_dim, spec.page_size,
        spec.max_pages_per_seq, spec.itemsize,
    )
    assert engine.kernel_block == want
    assert [reg.get(n).value for n in names] == [
        want.pages, want.rows, want.vmem_bytes
    ]


def test_kernel_fallback_surfaces_in_metrics_and_trace(lm):
    injector = FaultInjector(FaultPlan(kernel_iters=(1,)), seed=0)
    serve = _serve(telemetry=True, decode_kernel="pallas")
    sched, engine, _ = build_scheduler(lm, serve, injector=injector)
    done = sched.run(_requests(n=4, max_new=4))
    assert all(r.ok for r in done)
    assert engine.kernel_fallbacks == 1 and engine.decode_kernel == "dense"
    reg = sched.telemetry.registry
    assert reg.get("serve_kernel_fallbacks_total").value == 1
    assert sched.stats.kernel_fallbacks == 1
    assert any(
        e.get("name") == "kernel_fallback"
        for e in sched.telemetry.tracer.events
    )


def test_injector_wiring_through_build(lm):
    # injector passed through build_scheduler reaches scheduler + engine
    injector = FaultInjector(FaultPlan(), seed=1)
    sched, engine, _ = build_scheduler(
        lm, _serve(telemetry=True), injector=injector
    )
    assert sched.injector is injector and engine.injector is injector


# -- config / flag wiring -----------------------------------------------------


def test_flag_wiring_to_serveconfig_and_bundle(tmp_path):
    cfg = FFConfig.parse_args([
        "--metrics-out", str(tmp_path / "m.prom"),
        "--metrics-jsonl", str(tmp_path / "m.jsonl"),
        "--trace", str(tmp_path / "t.json"),
        "--slo-ttft-ms", "150",
        "--slo-itl-ms", "20",
    ])
    serve = ServeConfig.from_config(cfg)
    assert serve.metrics_out.endswith("m.prom")
    assert serve.trace.endswith("t.json")
    assert serve.slo_ttft_ms == 150.0 and serve.slo_itl_ms == 20.0
    assert serve.telemetry_requested
    tele = build_telemetry(serve)
    assert tele is not None and tele.enabled and tele.tracing
    assert tele.slo.ttft_ms == 150.0

    cfg2 = FFConfig.parse_args(["--serve-telemetry"])
    serve2 = ServeConfig.from_config(cfg2)
    assert serve2.telemetry and serve2.telemetry_requested
    tele2 = build_telemetry(serve2)
    assert tele2.tracing  # force-enabled bundle gets an in-memory tracer

    assert build_telemetry(ServeConfig()) is None
    with pytest.raises(ValueError):
        ServeConfig(slo_ttft_ms=-1)
    with pytest.raises(ValueError):
        ServeConfig(slo_window=0)


def test_disabled_telemetry_is_fully_absent(lm, monkeypatch):
    from flexflow_tpu.serving import scheduler as scheduler_mod
    from flexflow_tpu.telemetry import registry as registry_mod

    def touched(*a, **k):
        raise AssertionError("no Telemetry attached: nothing to observe")

    # the step log is always on, but what is derived from it per request
    # (the parts, their histograms) is computed only for a Telemetry
    monkeypatch.setattr(scheduler_mod, "request_parts", touched)
    monkeypatch.setattr(registry_mod.Histogram, "observe", touched)
    sched, engine, _ = build_scheduler(lm, _serve(serve_async=True))
    assert sched.telemetry is None and sched._tele is None
    assert engine.telemetry is None
    done = sched.run(_requests(n=2, max_new=4))
    assert all(r.ok for r in done)
    # stats still work on their private registry
    assert sched.stats.tokens_generated == sum(
        len(r.generated) for r in done
    )
    assert len(engine.step_log.records) == (
        sched.stats.dispatch_count + engine.prefill_programs
    )
    assert [s.rid for s in engine.step_log.retired] == [r.rid for r in done]
    assert not any(
        isinstance(m, registry_mod.Histogram)
        for m in sched.stats._registry.metrics()
    )


def test_telemetry_flush_idempotent(tmp_path):
    tele = Telemetry(metrics_out=str(tmp_path / "m.prom"),
                     trace=str(tmp_path / "t.json"))
    tele.registry.counter("x_total").inc()
    tele.flush()
    tele.flush()
    validate_metrics_text(open(tmp_path / "m.prom").read())
    validate_trace_file(str(tmp_path / "t.json"))
