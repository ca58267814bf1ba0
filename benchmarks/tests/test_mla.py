"""The latent-attention and held-share readers: device time under `mla.*`
named scopes from a small trace recorded on a TPU v5e (PR 30: the decode
step's latent attention alone, `mla_project` -> `mla_absorb_query` ->
the latent Pallas kernel -> `mla_absorb_values` -> `mla_project_out`, 8
slots x 8 heads on rows of 256, three executions), the byte and FLOP
counts at hand-worked shapes, the metric readers on a hand-made record,
and the new cell's files against the catalog row they were cut from."""

import json
import os

import pytest

from benchmarks.lib import loading, mla_counts, mla_readers, peaks, scopes
from benchmarks.lib import trace as T
from benchmarks.lib.readers import Run

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "mla_small.xplane.pb")
SCOPES = ("mla.project", "mla.absorb", "mla.attend", "mla.out")
V5E = peaks.peaks_for("TPU v5 lite")


def test_scope_seconds_on_the_recorded_trace():
    got = scopes.scope_seconds(SMALL, SCOPES, ("jit_step", "jit_absent"))
    prog = got["jit_step"]
    want = T.summarize(T.read_xplane(SMALL))["modules"]["jit_step"]
    assert prog["count"] == want["count"] == 3
    assert prog["seconds"] == pytest.approx(want["seconds"], rel=1e-4)
    assert set(prog["scopes"]) == set(SCOPES)
    assert sum(prog["scopes"].values()) <= prog["seconds"]
    # the latent kernel is a Mosaic call INSIDE its scope (its op_name
    # carries the path, unlike the compiler's own ragged-dot calls): the
    # kernels lib/trace.py counts are the time under `mla.attend`, less
    # the few small ops of the wrapper
    kernels = T.summarize(T.read_xplane(SMALL))["kernels"]["jit_step"]
    assert kernels["count"] == 3
    assert prog["scopes"]["mla.attend"] >= kernels["seconds"] * (1 - 1e-4)
    assert prog["scopes"]["mla.attend"] < 2.0 * kernels["seconds"]
    assert got["jit_absent"] == {"count": 0, "seconds": 0.0, "scopes": {}}


def test_a_trace_without_the_scopes_reads_nothing_and_does_not_raise():
    """The parent commit's programs have no `mla.*` scope and its engine
    no such counter: every new reader returns None there."""
    got = scopes.scope_seconds(
        os.path.join(DATA, "serve_small.xplane.pb"), SCOPES,
        ("jit__decode_impl_paged", "jit__prefill_impl_paged"), span="bench.trace",
    )
    assert got["jit__decode_impl_paged"]["scopes"] == {}
    run = Run(
        record={"kind": "serve", "decode_module": "jit__decode_impl_paged",
                "window": (0.0, 1.0), "trace_window": (0.0, 1.0),
                "mla": {"scope_seconds": got, "steps": []}},
        trace=None, device={}, peaks=V5E, set_up_seconds=0.0, notes={},
    )
    assert mla_readers.scope_share(run, "decode_module", "parts") is None
    assert mla_readers.latent_kernel_roofline(run) is None
    assert mla_readers.experts_absent_share(run) is None
    other = Run({"kind": "serve", "window": (0.0, 1.0)}, None, {}, None, 0.0, {})
    assert mla_readers.experts_absent_share(other) is None
    assert mla_readers.latent_kernel_roofline(other) is None
    assert mla_readers.scope_share(other, "prefill_module", "parts") is None


def test_counts_by_hand():
    # 10 rows of 576 float32 attended, 2 (slot, layer) queries of 32 heads:
    # rows 10 * 576 * 4, queries in 2 * 32 * 576 * 4, outputs 2 * 32 * 512 * 4
    assert mla_counts.latent_decode_bytes(10, 2, 32, 576, 512, 4) == (
        23040 + 147456 + 131072
    )
    # q . k over 576 and p . v over 512, a head and a row, 2 m k n each
    assert mla_counts.latent_decode_flops(10, 32, 576, 512) == 2 * 10 * 32 * 1088
    # the cell's decode step, by shapes: 10 slots at 800 rows, 6 layers:
    # 48,000 rows are 110.6 MB, 0.14 ms at 819 GB/s; the FLOPs are 3.3
    # GFLOP, 0.017 ms at 197 TFLOP/s: bound by memory
    bytes_ = mla_counts.latent_decode_bytes(48000, 60, 32, 576, 512, 4)
    assert bytes_ == 48000 * 2304 + 60 * 32 * 1088 * 4
    floor, bound = peaks.roofline_floor_s(
        mla_counts.latent_decode_flops(48000, 32, 576, 512), bytes_, V5E
    )
    assert bound == "memory" and floor == pytest.approx(bytes_ / 819e9)


def _record():
    # cumulative rows: (t, decode steps, latent rows, busy slot-steps,
    # live rows absent in decode, live rows absent in prefill, prompt
    # tokens prefilled)
    steps = [
        (1.0, 0, 0, 0, 0, 0, 0),
        (2.0, 10, 40000, 100, 1450, 3000, 200),
        (3.0, 20, 90000, 200, 3050, 3000, 200),
    ]
    scope_seconds = {
        "decode": {"count": 10.0, "seconds": 0.05, "scopes": {
            "mla.project": 0.004, "mla.absorb": 0.002, "mla.attend": 0.002,
            "mla.out": 0.002}},
        "prefill": {"count": 2.0, "seconds": 0.2, "scopes": {
            "mla.project": 0.03, "mla.attend": 0.05, "mla.out": 0.01}},
    }
    return {
        "kind": "serve", "decode_module": "decode", "prefill_module": "prefill",
        "window": (0.5, 3.5), "trace_window": (1.5, 3.5),
        "moe": {"layers": 5, "k": 6},
        "mla": {"layers": 6, "heads": 32, "row": 576, "row_cached": 640,
                "value_width": 512, "itemsize": 4, "steps": steps,
                "scope_seconds": scope_seconds},
    }


def test_the_readers_on_a_hand_made_record():
    run = Run(_record(), None, {}, V5E, 0.0, {})
    assert mla_readers.scope_share(run, "decode_module", "d") == pytest.approx(20.0)
    assert run.notes["d"]["mla.attend"] == pytest.approx(4.0)
    assert mla_readers.scope_share(run, "prefill_module", "p") == pytest.approx(45.0)
    # over the window: 200 busy slot-steps route 200 x 6 x 5 live rows in
    # decode, of which 3,050 are absent; 200 prompt tokens as many in prefill
    assert mla_readers.experts_absent_share(run) == pytest.approx(100 * 3050 / 6000)
    assert run.notes["experts_absent_share_prefill"] == pytest.approx(
        100 * 3000 / 6000
    )
    # the traced part holds the steps that ended at 2.0 and 3.0: 10 decode
    # steps, 50,000 rows, 100 slot-steps x 6 layers, and the trace holds
    # 10 programs whole: scale 1
    bytes_ = mla_counts.latent_decode_bytes(50000, 600, 32, 576, 512, 4)
    want = 100.0 * (bytes_ / 819e9) / 0.002
    assert mla_readers.latent_kernel_roofline(run) == pytest.approx(want)
    assert run.notes["latent_kernel_bound"] == "memory"
    assert run.notes["latent_rows_per_decode_step"] == pytest.approx(5000.0)


def test_an_untraced_run_reads_the_counter_and_no_device_metric():
    rec = _record()
    rec["mla"]["scope_seconds"] = None
    run = Run(rec, None, {}, V5E, 0.0, {})
    assert mla_readers.experts_absent_share(run) is not None
    assert mla_readers.latent_kernel_roofline(run) is None
    assert mla_readers.scope_share(run, "decode_module", "d") is None


def test_the_configuration_is_the_catalog_row_but_for_what_reduced_names():
    """Every number of the published config under its own key; the three
    reduced keys with the published value beside them; no width among
    them."""
    bench = loading.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "kanana2_30b_a3b")
    cfg = loading.load_json(os.path.join(loading.ROOT, entry["file"]))
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256,
    }
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert cfg["router_width"] == cfg["published"]["n_routed_experts"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (
        6, 64, 64128)
    # the guide's floors: four expert layers, 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    # the arithmetic of the cut, by shapes
    shapes = cfg["by_shapes"]
    h, e, f = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["intermediate_size"]
    attn = (
        h * cfg["num_attention_heads"] * cfg["qk_head_dim"]
        + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) + cfg["kv_lora_rank"]
        + cfg["kv_lora_rank"] * cfg["num_attention_heads"]
        * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + cfg["num_attention_heads"] * cfg["v_head_dim"] * h
    )
    assert attn == shapes["attention_parameters_per_layer"]
    dense = attn + 2 * h + 3 * h * f
    expert = (
        attn + 2 * h + h * cfg["router_width"] + cfg["router_width"]
        + 3 * h * cfg["n_shared_experts"] * e + cfg["n_routed_experts"] * 3 * h * e
    )
    total = dense + 5 * expert + 2 * cfg["vocab_size"] * h + h
    assert total == shapes["parameters"] and 4 * total == shapes["weight_bytes"]
    assert shapes["kv_bytes_per_token"] == 6 * 640 * 4
    assert shapes["pool_bytes"] == cfg["serve"]["kv_pool_tokens"] * 6 * 640 * 4


def test_the_traffic_is_the_issues():
    traffic = loading.load_traffic("assist_kanana2")
    assert traffic["kind"] == "open_loop" and traffic["mode"] == "latency"
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.7, "clip": [16, 640]}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.7, "clip": [16, 1024]}
    chat = loading.load_traffic("chat_olmoe")
    for key in ("gap", "lead_in_s", "drain_s", "finish_timeout_s", "trace_s"):
        assert traffic[key] == chat[key], key
    # at least 110 counted requests in the 51 s, or tpot_p90_ms reads None
    assert traffic["rate_per_s"] * 51 >= 110
    json.dumps(traffic)
