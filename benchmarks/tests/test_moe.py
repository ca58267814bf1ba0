"""The expert layer's readers: device time under `moe.*` named scopes
from a small trace recorded on a TPU v5e (PR 26: `ops.moe.sparse_moe` at
128 tokens x 256, 8 experts of 128, 2 per token, three executions), the
byte and FLOP counts at hand-worked shapes, and the metric readers on a
hand-made record."""

import os

import pytest

from benchmarks.lib import moe_counts, moe_readers, peaks, scopes
from benchmarks.lib import trace as T
from benchmarks.lib.readers import Run

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "moe_small.xplane.pb")
SCOPES = ("moe.route", "moe.sort", "moe.experts", "moe.combine")


def test_scope_seconds_on_the_recorded_trace():
    got = scopes.scope_seconds(
        SMALL, SCOPES, ("jit__lambda", "jit_absent"),
        compiler_ops={"ragged-dot": "moe.experts"},
    )
    prog = got["jit__lambda"]
    # the programs and their device time are what lib/trace.py reads
    want = T.summarize(T.read_xplane(SMALL))["modules"]["jit__lambda"]
    assert prog["count"] == want["count"] == 3
    assert prog["seconds"] == pytest.approx(want["seconds"], rel=1e-4)
    assert set(prog["scopes"]) == set(SCOPES)
    assert sum(prog["scopes"].values()) <= prog["seconds"]
    # read by hand from the trace (my chip run, PR 26): microseconds
    assert prog["scopes"]["moe.sort"] == pytest.approx(20.72e-6, rel=1e-2)
    assert prog["scopes"]["moe.experts"] == pytest.approx(16.33e-6, rel=1e-2)
    assert got["jit_absent"] == {"count": 0, "seconds": 0.0, "scopes": {}}


def test_the_grouped_matmul_is_in_the_scope_only_by_its_compiler_name():
    """XLA expands `ragged_dot` into `ragged-dot-*` Mosaic calls whose
    op_name is their own name: without the mapping, `moe.experts` holds
    the gate's multiply alone."""
    bare = scopes.scope_seconds(SMALL, SCOPES, ("jit__lambda",))["jit__lambda"]
    assert bare["scopes"]["moe.experts"] < 1e-6


def test_a_trace_without_the_scopes_reads_nothing_and_does_not_raise():
    got = scopes.scope_seconds(
        os.path.join(DATA, "serve_small.xplane.pb"), SCOPES,
        ("jit__decode_impl_paged", "jit__prefill_impl_paged"), span="bench.trace",
        compiler_ops={"ragged-dot": "moe.experts"},
    )
    assert got["jit__decode_impl_paged"]["count"] == 11
    assert got["jit__decode_impl_paged"]["scopes"] == {}
    run = Run(
        record={"kind": "serve", "decode_module": "jit__decode_impl_paged",
                "moe": {"scope_seconds": got}},
        trace=None, device={}, peaks=None, set_up_seconds=0.0, notes={},
    )
    assert moe_readers.scope_share(run, "decode_module", "parts") is None
    other = Run({"kind": "serve"}, None, {}, None, 0.0, {})
    assert moe_readers.experts_touched_share(other) is None
    assert moe_readers.expert_matmul_roofline(other, "decode") is None


def test_counts_by_hand():
    # one expert of hidden 64, width 32, float32: 3 * 64 * 32 * 4 bytes
    assert moe_counts.expert_weight_bytes(64, 32, 4) == 24576
    # 16 rows over 5 touched experts: weights 5 * 24576, rows in and out
    # 2 * 16 * 64 * 4
    assert moe_counts.expert_matmul_bytes(16, 5, 64, 32, 4) == 122880 + 8192
    # three [1, 64] x [64, 32]-sized products a row, 2 m k n each
    assert moe_counts.expert_matmul_flops(16, 64, 32) == 3 * 2 * 16 * 64 * 32
    # the cell's decode step, by shapes: 16 slots x 8 choices x 4 layers,
    # 50 of 64 experts touched a layer: 5.03 GB of weights, 6.1 ms at 819 GB/s
    bytes_ = moe_counts.expert_matmul_bytes(512, 200, 2048, 1024, 4)
    assert bytes_ == 200 * 25165824 + 2 * 512 * 8192
    floor, bound = peaks.roofline_floor_s(
        moe_counts.expert_matmul_flops(512, 2048, 1024), bytes_,
        peaks.peaks_for("TPU v5 lite"),
    )
    assert bound == "memory" and floor == pytest.approx(6.156e-3, rel=1e-3)


def test_readers_on_a_hand_made_record():
    # steps: (end, decode steps, rows d, touched d, prefills, rows p, touched p)
    steps = [
        (1.0, 10, 1000, 100, 2, 4000, 30),
        (2.0, 11, 1064, 110, 2, 4000, 30),
        (3.0, 12, 1128, 122, 3, 6048, 46),
        (9.0, 20, 9999, 999, 9, 99999, 99),
    ]
    prog = {
        "jit_d": {"count": 4.0, "seconds": 0.010,
                  "scopes": {"moe.experts": 0.004, "moe.sort": 0.001}},
        "jit_p": {"count": 1.0, "seconds": 0.020, "scopes": {"moe.experts": 0.010}},
    }
    rec = {
        "kind": "serve", "window": (0.5, 3.5), "trace_window": (0.5, 3.5),
        "decode_module": "jit_d", "prefill_module": "jit_p", "max_seqs": 4,
        "moe": {"layers": 2, "experts": 8, "k": 2, "hidden": 64,
                "expert_hidden": 32, "itemsize": 4, "steps": steps,
                "scope_seconds": prog},
    }
    v5e = peaks.peaks_for("TPU v5 lite")
    run = Run(rec, None, {}, v5e, 0.0, {})
    assert moe_readers.scope_share(run, "decode_module", "parts") == pytest.approx(50.0)
    assert run.notes["parts"] == {"moe.experts": pytest.approx(40.0),
                                  "moe.sort": pytest.approx(10.0)}
    # 22 experts touched over 2 decode steps of 2 layers x 8 experts
    assert moe_readers.experts_touched_share(run) == pytest.approx(100 * 22 / 32)
    # decode: 128 rows and 22 experts over 2 host steps, scaled to the 4
    # executions the trace holds: bytes 2 * (22 * 24576 + 2 * 128 * 256)
    bytes_ = 2 * (22 * 24576 + 2 * 128 * 64 * 4)
    want = 100.0 * (bytes_ / 819e9) / 0.004
    assert moe_readers.expert_matmul_roofline(run, "decode") == pytest.approx(want)
    assert run.notes["expert_matmul_bound.decode"] == "memory"
    # prefill: one batch of 2048 rows, 16 experts, one execution
    bytes_p = 16 * 24576 + 2 * 2048 * 64 * 4
    flops_p = 6 * 2048 * 64 * 32
    floor = max(bytes_p / 819e9, flops_p / 197e12)
    assert moe_readers.expert_matmul_roofline(run, "prefill") == pytest.approx(
        100.0 * floor / 0.010
    )
    run.peaks = None  # off the chip a roofline share is not read
    assert moe_readers.expert_matmul_roofline(run, "decode") is None
