"""The readers of the program's record of its dispatched step programs
(`lib/steplog.py`), over a hand-written log: the hand-computed values,
None for a program without the record, nothing from outside the window.
And that a rehearsal of a serving cell runs all six."""

import types

import pytest

from benchmarks.lib import loading, steplog
from benchmarks.lib.readers import Run
from benchmarks.tests.test_rehearse import last_line, run_cell
from flexflow_tpu.telemetry import trace

NAMES = (
    "admit_to_first_p50_ms", "admit_ahead_p50_ms", "prefill_enqueue_block_ms",
    "decode_enqueue_block_ms", "prefill_gaps_share_p90", "decode_chained_share",
)
WINDOW = (10.0, 20.0)


def rec(seq, kind, t_call, t_enqueued, t_read, t_ready, rids, chained, bucket=None):
    r = trace.StepRecord(kind, rows=len(rids), bucket=bucket)
    r.seq, r.rids, r.chained = seq, tuple(rids), chained
    r.t_call, r.t_enqueued, r.t_read, r.t_ready = t_call, t_enqueued, t_read, t_ready
    return r


def hand_log():
    """Three requests. 1 and 2 are admitted together at 10.10 (prefill
    called 10.102, on the queue 10.105, read 10.120, first tokens 10.121);
    3 is admitted at 10.300 while they decode (prefill 10.301 / 10.304 /
    10.314, first token 10.3145). Request 0 was submitted before the
    window and a decode step runs after it: both are left out. The
    decode steps take 1 ms of the host to enqueue but for one of 3 ms."""
    log = types.SimpleNamespace()
    records = [
        rec(1, "prefill", 9.000, 9.004, 9.010, 9.011, (0,), False, bucket=128),
        rec(2, "prefill", 10.102, 10.105, 10.119, 10.120, (1, 2), False, bucket=128),
        rec(3, "decode", 10.130, 10.131, 10.140, 10.142, (1, 2), False),
        rec(4, "decode", 10.141, 10.142, 10.150, 10.152, (1, 2), True),
        rec(5, "prefill", 10.301, 10.304, 10.313, 10.314, (3,), True, bucket=512),
        rec(6, "decode", 10.305, 10.308, 10.320, 10.322, (1, 2), True),
        rec(7, "decode", 10.330, 10.331, 10.340, 10.342, (1, 2, 3), False),
        rec(8, "decode", 20.500, 20.501, 20.510, 20.512, (3,), False),
        # in flight still: not a record of the window yet
        rec(9, "decode", 19.000, 19.001, None, None, (3,), False),
    ]
    log.records = records
    stamps = [
        trace.RequestStamps(0, 9.0, 9.0, 9.012, 9.5),
        trace.RequestStamps(1, 10.099, 10.100, 10.121, 10.400),
        trace.RequestStamps(2, 10.0995, 10.100, 10.121, 10.350),
        trace.RequestStamps(3, 10.299, 10.300, 10.3145, 10.345),
    ]
    log.requests = lambda: stamps
    return log


@pytest.fixture
def run(monkeypatch):
    log = hand_log()
    monkeypatch.setattr(trace, "step_logs", lambda: [log])
    return Run({"kind": "serve", "window": WINDOW}, None, {"platform": "tpu"},
               None, 0.0, {})


def read(name, run):
    return loading.load_module("metrics", name).read(run)


def test_window_leaves_out_what_is_outside_it(run):
    w = steplog.window_of(run)
    assert [r.seq for r in w.records] == [2, 3, 4, 5, 6, 7]
    assert [p.rid for p in w.parts] == [1, 2, 3]
    assert steplog.window_of(run) is w  # read once a run


def test_admission_to_first_token(run):
    # ahead + inflight + emit = first_token - admit: 21, 21 and 14.5 ms
    assert read("admit_to_first_p50_ms", run) == pytest.approx(21.0)
    parts = run.notes["admit_to_first_parts_ms"]
    assert parts["ahead"]["p50"] == pytest.approx(5.0)  # 5, 5, 4
    assert parts["inflight"]["p50"] == pytest.approx(15.0)  # 15, 15, 10
    assert parts["emit"]["p50"] == pytest.approx(1.0)  # 1, 1, 0.5
    assert parts["queue"]["p50"] == pytest.approx(1.0)  # 1, 0.5, 1
    assert run.notes["admit_to_first_samples"] == 3
    assert read("admit_ahead_p50_ms", run) == pytest.approx(5.0)


def test_enqueue_blocks(run):
    assert read("prefill_enqueue_block_ms", run) == pytest.approx(3.0)
    by_bucket = run.notes["prefill_enqueue_block_ms_by_bucket"]
    assert by_bucket["128"] == {"mean": pytest.approx(3.0), "programs": 1}
    assert by_bucket["512"] == {"mean": pytest.approx(3.0), "programs": 1}
    # 1, 1, 3 and 1 ms
    assert read("decode_enqueue_block_ms", run) == pytest.approx(1.5)
    assert run.notes["decode_read_block_ms"] == pytest.approx(2.0)
    # the longest call-to-call interval that ended with a program in
    # flight: 10.142 -> 10.301 (the step before the admission)
    assert run.notes["decode_longest_call_gap_ms"] == pytest.approx(160.0)
    assert run.notes["decode_records"] == 4


def test_prefill_in_the_token_gaps(run):
    # request 3's prefill (10.301-10.314) lies in the gaps of 1 (279 ms
    # long) and 2 (229 ms); request 3 itself saw nobody else's
    shares = sorted([100 * 13 / 279, 100 * 13 / 229, 0.0])
    assert read("prefill_gaps_share_p90", run) == pytest.approx(shares[2])
    assert run.notes["prefill_gaps_share_p50"] == pytest.approx(shares[1])
    assert run.notes["others_prefills_per_request"] == pytest.approx(2 / 3)
    split = run.notes["token_gap_parts_share_p50"]
    assert set(split) == {"others_prefill", "decode", "host"}


def test_chained_share(run):
    assert read("decode_chained_share", run) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_record_reads_none(name, monkeypatch):
    monkeypatch.delattr(trace, "step_logs")
    run = Run({"kind": "serve", "window": WINDOW}, None, {"platform": "tpu"},
              None, 0.0, {})
    assert read(name, run) is None
    assert not run.notes


@pytest.mark.parametrize("name", NAMES)
def test_a_trainer_and_an_empty_window_read_none(name, monkeypatch):
    monkeypatch.setattr(trace, "step_logs", lambda: [hand_log()])
    train = Run({"kind": "train"}, None, {"platform": "tpu"}, None, 0.0, {})
    assert read(name, train) is None
    empty = Run({"kind": "serve", "window": (30.0, 40.0)}, None,
                {"platform": "tpu"}, None, 0.0, {})
    assert read(name, empty) is None


def test_the_six_are_entries_of_the_five_latency_cells():
    bench = loading.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NAMES)
    latency = next(
        m for m in bench["end_to_end"] if m["name"] == "ttft_p50_ms"
    )["workloads"]
    for name in NAMES:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["workloads"] == latency
        assert entries[name]["moves"] in ("ttft_p50_ms", "tpot_p90_ms")
        assert entries[name]["layer"] in ("scheduler", "engine")


def test_a_rehearsal_of_a_serving_cell_runs_the_six():
    line = last_line(run_cell(loading.ROOT, "serve_gpt2m_chat", 1, seconds="3"))
    ran = set(line["rehearsal"]["readers_that_ran_but_are_not_metrics_on_a_cpu"])
    assert set(NAMES) <= ran
    assert not set(NAMES) & set(line["metrics"])  # a CPU time is never a metric
    parts = line["notes"]["admit_to_first_parts_ms"]
    assert set(parts) == {"queue", "ahead", "inflight", "emit"}
