"""The trace reduction, against two small traces recorded on a TPU v5e
(PR 23: 2-layer cuts of the trainer and the server at real widths) and
against hand-made intervals."""

import os

import pytest

from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def op(start, end, text="%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"):
    return T.Op(start, end, text)


def test_op_and_module_keys():
    text = ("%fusion.194 = f32[32,1024,50257]{1,2,0:T(8,128)} fusion(f32[32,1024,1024]"
            "{1,2,0:T(8,128)} %get-tuple-element.23), kind=kOutput")
    assert T.op_key(text) == "fusion f32[32,1024,50257]"
    assert T.op_key("%while.11 = (s32[]{:T(128)}, bf16[16,4]{1,0}) while(...)") == "while (tuple)"
    assert T.module_key("jit__decode_impl_paged(3817557165947963370)") == "jit__decode_impl_paged"
    kernel = ('%_decode_impl_paged.2 = f32[32,16,1,64]{3,2,1,0} custom-call(s32[32]{0} %a), '
              'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert T.custom_call_target(kernel) == "tpu_custom_call"
    assert T.is_collective("%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x)")
    assert not T.is_collective(text)


def test_interval_arithmetic():
    busy = T.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (4.0, 4.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert T.total(busy) == pytest.approx(3.0)
    assert T.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert T.clip(busy, 1.0, 3.5) == [(1.0, 2.0), (3.0, 3.5)]
    assert T.subtract([(0.0, 4.0)], busy) == [(2.0, 3.0)]


def test_self_time_of_nested_ops():
    ops = [op(0.0, 10.0, "%while.1 = (s32[]) while(...)"), op(1.0, 4.0), op(5.0, 9.0)]
    T._mark_nesting(ops)
    assert [o.leaf for o in ops] == [False, True, True]
    assert ops[0].self_s == pytest.approx(3.0)
    assert ops[1].self_s == pytest.approx(3.0)


def test_summary_of_hand_made_trace():
    dev = T.DeviceTrace("/device:TPU:0")
    dev.ops = [op(1.0, 2.0), op(4.0, 5.0),
               op(5.0, 6.0, "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)")]
    dev.async_ops = [op(4.5, 6.0, "%all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %g)")]
    dev.modules = [op(0.9, 6.1, "jit_step(123)")]
    T._mark_nesting(dev.ops)
    spans = {"bench.trace": [(0.0, 8.0)], "train.input": [(2.0, 3.5)],
             "train.epoch_end": [(6.0, 8.0)]}
    s = T.summarize(T.Trace([dev], spans))
    assert s["window_s"] == pytest.approx(8.0)
    assert s["busy_s"] == pytest.approx(3.0)
    # gaps: 0-1 (no span), 2-4 (train.input covers 1.5 of 2), 6-8 (epoch end)
    assert s["idle_gaps"][0][0] in ("train.input", "train.epoch_end")
    assert {g[0] for g in s["idle_gaps"]} == {"(no span)", "train.input", "train.epoch_end"}
    assert s["idle_under"]["train.input"] == pytest.approx(1.5)
    assert s["modules"]["jit_step"] == {"count": 1, "seconds": pytest.approx(5.2)}
    # the collective runs 4.5-6.0; compute covers 4.5-5.0 of it
    assert s["collective_s"] == pytest.approx(1.5)
    assert s["collective_exposed_s"] == pytest.approx(1.0)


def test_recorded_server_trace():
    tr = T.read_xplane(os.path.join(DATA, "serve_small.xplane.pb"), ("door.",))
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert len(tr.spans["door.submit"]) == 6
    s = T.summarize(tr)
    # counted by hand from the raw events (PR 23 probe): 11 decode
    # programs of 52.135 ms together, one prefill of 122.684 ms, and two
    # Mosaic kernel calls per decode program (2 layers), 5.992 ms
    assert s["modules"]["jit__decode_impl_paged"]["count"] == 11
    assert s["modules"]["jit__decode_impl_paged"]["seconds"] == pytest.approx(0.052135, rel=1e-3)
    assert s["modules"]["jit__prefill_impl_paged"]["count"] == 1
    assert s["modules"]["jit__prefill_impl_paged"]["seconds"] == pytest.approx(0.122684, rel=1e-3)
    assert s["kernels"]["jit__decode_impl_paged"]["count"] == 22
    assert s["kernels"]["jit__decode_impl_paged"]["seconds"] == pytest.approx(0.005992, rel=1e-3)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"][0][0] == "copy f32[1024,16,16,64]"  # the undonated pool
    assert len(s["device_ops"]) == 10 and len(s["idle_gaps"]) <= 10


def test_recorded_trainer_trace():
    tr = T.read_xplane(os.path.join(DATA, "train_small.xplane.pb"), ("bench.",))
    lo, hi = T.window_of(tr, "bench.window")
    assert hi - lo == pytest.approx(1.647909, rel=1e-4)
    s = T.summarize(tr, (lo, hi))
    assert s["modules"]["jit_step"]["count"] == 8
    assert s["modules"]["jit_step"]["seconds"] == pytest.approx(0.166785, rel=1e-3)
    # while bodies nest: busy time is the union, never the sum
    assert s["busy_s"] <= s["modules"]["jit_step"]["seconds"]
    assert s["busy_s"] == pytest.approx(0.159, rel=0.02)
    assert s["kernels"] == {}


def test_idle_under_a_span_is_one_pass_and_agrees_with_asking_every_pair():
    """A traced chat run spent four minutes asking each of half a million
    gaps about each of a thousand spans (PR 23 re-check); one pass over two
    sorted unions gives the same seconds."""
    idle = [(0.0, 1.0), (2.0, 3.0), (5.0, 9.0), (9.5, 9.6)]
    span = [(0.5, 2.5), (6.0, 7.0), (8.0, 10.0)]
    pairs = sum(
        max(0.0, min(e1, e2) - max(s1, s2)) for s1, e1 in idle for s2, e2 in span
    )
    assert T.overlap_total(idle, span) == pytest.approx(pairs)
    assert T.overlap_total(span, idle) == pytest.approx(pairs)
    assert T.overlap_total(idle, []) == 0.0

