"""The recurrent layers' counts and readers: the byte and FLOP counts of
`lib/kda_counts.py` at hand-worked shapes, the metric readers on a
hand-made record (and on records that lack what they read), the new
cell's files against the catalog row they were cut from, and the
controls behind its tolerance in the CPU rehearsal."""

import json
import os

import pytest

from benchmarks.lib import kda_counts, kda_readers, loading, peaks
from benchmarks.lib.readers import Run
from benchmarks.tests.test_rehearse import last_line, run_cell

V5E = peaks.peaks_for("TPU v5 lite")
CELL, CONFIG = "serve_kimi_linear_longform", "kimi_linear_48b_a3b"


def test_counts_by_hand():
    # one (slot, layer) row at the served shapes: the state 32 x 128 x 128
    # floats in and out (2 x 2 MiB), the tails 3 x 12,288 in and out, and
    # the token's q, k, v, g, o rows of 4,096 with beta's 32
    row = 4 * (2 * 524288 + 2 * 36864 + 5 * 4096 + 32)
    assert kda_counts.state_step_bytes(1, 32, 128, 4) == row == 4571264
    assert kda_counts.state_step_bytes(64, 32, 128, 4) == 64 * row
    assert kda_counts.state_step_flops(1, 32, 128) == 7 * 524288
    # a decode step of 16 live slots over four layers: 292.6 MB, 0.36 ms
    # at 819 GB/s, against 0.23 GFLOP: bound by memory
    floor, bound = peaks.roofline_floor_s(
        kda_counts.state_step_flops(64, 32, 128),
        kda_counts.state_step_bytes(64, 32, 128, 4), V5E,
    )
    assert bound == "memory" and floor == pytest.approx(64 * row / 819e9)
    # the chunked recurrence, a token and head at C = 64, d = 128:
    # 7 x 64 x 128 + 6 x 128 x 128 = 155,648 FLOPs; bytes: five rows of
    # 128 and beta, and 2 x 16,384 floats of state a chunk of 64
    assert kda_counts.scan_flops(1, 1, 128, 64) == 155648
    assert kda_counts.scan_flops(1600 * 4, 32, 128, 64) == 1600 * 4 * 32 * 155648
    assert kda_counts.scan_bytes(1, 1, 128, 64) == 4 * (5 * 128 + 1 + 512)
    assert kda_counts.scan_bytes(64, 32, 128, 64) == 4 * 64 * (
        5 * 4096 + 32 + 2 * 524288 / 64
    )


def _record():
    # cumulative rows: (t, decode steps, state rows advanced, prefill
    # programs, tokens given to them, rows reset by prefills)
    steps = [
        (1.0, 0, 0, 0, 0, 0),
        (2.0, 10, 640, 2, 1536, 12),
        (3.0, 20, 1440, 5, 3584, 28),
    ]
    scope_seconds = {
        "decode": {"count": 5.0, "seconds": 0.05, "scopes": {
            "kda.project": 0.004, "kda.conv": 0.001, "kda.step": 0.003,
            "kda.out": 0.002}},
        "prefill": {"count": 3.0, "seconds": 0.3, "scopes": {
            "kda.project": 0.03, "kda.conv": 0.01, "kda.scan": 0.05,
            "kda.out": 0.01}},
    }
    return {
        "kind": "serve", "decode_module": "decode", "prefill_module": "prefill",
        "window": (0.5, 3.5), "trace_window": (1.5, 3.5),
        "kda": {"layers": 4, "heads": 32, "head_dim": 128, "kernel": 4,
                "chunk": 64, "steps": steps, "scope_seconds": scope_seconds},
    }


def test_the_readers_on_a_hand_made_record():
    run = Run(_record(), None, {}, V5E, 0.0, {})
    assert kda_readers.scope_share(run, "decode_module", "d") == pytest.approx(20.0)
    assert run.notes["d"]["kda.step"] == pytest.approx(6.0)
    assert kda_readers.scope_share(run, "prefill_module", "p") == pytest.approx(
        100 * 0.1 / 0.3
    )
    # the traced part holds the steps that ended at 2.0 and 3.0: 10 decode
    # steps advanced 800 rows, and the trace holds 5 programs whole: 400
    # rows against the 4 ms under kda.step and kda.conv
    want = 100.0 * (400 * 4571264 / 819e9) / 0.004
    assert kda_readers.state_roofline(run) == pytest.approx(want)
    assert run.notes["kda_state_bound"] == "memory"
    assert run.notes["kda_state_rows_per_decode_step"] == pytest.approx(80.0)
    # 3 prefill programs were given 2,048 tokens, the trace holds 3 whole:
    # 2,048 x 4 (token, layer) pairs against the 50 ms under kda.scan
    tokens = 2048 * 4
    floor, bound = peaks.roofline_floor_s(
        kda_counts.scan_flops(tokens, 32, 128, 64),
        kda_counts.scan_bytes(tokens, 32, 128, 64), V5E,
    )
    assert kda_readers.scan_roofline(run) == pytest.approx(100.0 * floor / 0.05)
    assert run.notes["kda_scan_bound"] == bound == "memory"
    assert run.notes["kda_tokens_per_prefill_program"] == pytest.approx(2048 / 3)


@pytest.mark.parametrize("record", [
    {"kind": "serve", "window": (0.0, 1.0)},  # another family's
    {"kind": "train"},
    dict(_record(), trace_window=(None, None)),  # an untraced run
    dict(_record(), kda=dict(_record()["kda"], scope_seconds=None)),
    dict(_record(), kda=dict(  # the parent's programs: no such scope
        _record()["kda"], scope_seconds={
            "decode": {"count": 5.0, "seconds": 0.05, "scopes": {}},
            "prefill": {"count": 3.0, "seconds": 0.3, "scopes": {}}})),
    dict(_record(), kda=dict(_record()["kda"], steps=[])),  # no counter
], ids=["other", "train", "untraced", "no_times", "no_scopes", "no_steps"])
def test_a_record_without_them_reads_none_and_does_not_raise(record):
    run = Run(record, None, {}, V5E, 0.0, {})
    assert kda_readers.state_roofline(run) is None
    assert kda_readers.scan_roofline(run) is None
    times = record.get("kda", {}).get("scope_seconds")
    if not times or not times["decode"]["scopes"]:
        assert kda_readers.scope_share(run, "decode_module", "d") is None
        assert kda_readers.scope_share(run, "prefill_module", "p") is None


def test_the_metrics_are_their_readers():
    run = Run(_record(), None, {}, V5E, 0.0, {})
    for name, reader in (
        ("kda_decode_share", lambda: kda_readers.scope_share(run, "decode_module", "x")),
        ("kda_prefill_share", lambda: kda_readers.scope_share(run, "prefill_module", "x")),
        ("kda_state_roofline", lambda: kda_readers.state_roofline(run)),
        ("kda_scan_roofline", lambda: kda_readers.scan_roofline(run)),
    ):
        got = loading.load_module("metrics", name).read(run)
        assert got == pytest.approx(reader()) and 0 < got < 105, name


def test_the_configuration_is_the_catalog_row_but_for_what_reduced_names():
    """Every number of the published config under its own key, the nested
    group whole; the three reduced keys with the published value beside
    them; no width among them."""
    bench = loading.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cfg = loading.load_json(os.path.join(loading.ROOT, entry["file"]))
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840,
    }
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert cfg["router_width"] == cfg["published"]["num_experts"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (
        5, 64, 40960)
    # the guide's floors: a whole period (3 KDA : 1 latent) in the four
    # layers after the dense one, 8 experts, an eighth of the rows
    family = loading.load_module("families", cfg["family"])
    kda, full = family.pattern_of(cfg)
    assert kda == (1, 2, 3, 5) and full == (4,)
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert "four chips" in cfg["deployment"].lower()
    # the arithmetic of the cut, by shapes
    shapes, lin = cfg["by_shapes"], cfg["linear_attn_config"]
    e, h, d, k = cfg["hidden_size"], lin["num_heads"], lin["head_dim"], 4
    kda_p = (
        3 * e * h * d + 3 * h * d * k + 2 * (e * d + d * h * d) + h * d + h
        + e * h + d + h * d * e
    )
    assert kda_p == shapes["kda_attention_parameters_per_layer"]
    heads = cfg["num_attention_heads"]
    latent = (
        e * heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
        + e * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) + cfg["kv_lora_rank"]
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + heads * cfg["v_head_dim"] * e
    )
    assert latent == shapes["latent_attention_parameters_per_layer"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    outside = expert + e * cfg["router_width"] + cfg["router_width"] + 2 * e
    dense = kda_p + 2 * e + 3 * e * cfg["intermediate_size"]
    held = cfg["num_experts"] * expert + outside
    total = dense + 3 * (kda_p + held) + latent + held + 2 * cfg["vocab_size"] * e + e
    assert total == shapes["parameters"] and 4 * total == shapes["weight_bytes"]
    uncut = (
        dense + 26 * (256 * expert + outside) + 19 * kda_p + 7 * latent
        + 2 * 163840 * e + e
    )
    assert uncut == shapes["parameters_uncut"] and 49.0e9 < uncut < 49.2e9
    state = 4 * (h * d * d + 3 * 3 * h * d)
    assert state == shapes["state_bytes_per_slot_and_layer"]
    assert 4 * state == shapes["state_bytes_per_slot"]
    assert cfg["serve"]["max_seqs"] * 4 * state == shapes["state_bytes"]
    assert shapes["kv_bytes_per_token"] == 640 * 4
    assert shapes["pool_bytes"] == cfg["serve"]["kv_pool_tokens"] * 640 * 4
    # every bucket a whole number of chunks, the largest holds the longest
    # prompt laid on a chunk boundary
    traffic = loading.load_traffic("longform_kimi")
    longest = -(-traffic["prompt_len"]["clip"][1] // family.KDA_CHUNK) * family.KDA_CHUNK
    buckets = cfg["serve"]["prefill_buckets"]
    assert all(b % family.KDA_CHUNK == 0 for b in buckets) and buckets[-1] >= longest
    assert set(cfg["tolerance"]) == {
        "logits_highest_rel", "routing_highest_share_min", "logits_default_rel",
        "routing_default_share_min", "why"}


def test_the_traffic_is_the_issues():
    traffic = loading.load_traffic("longform_kimi")
    assert traffic["kind"] == "open_loop" and traffic["mode"] == "latency"
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.8, "clip": [32, 1536]}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "clip": [64, 1536]}
    assist = loading.load_traffic("assist_kanana2")
    for key in ("gap", "lead_in_s", "drain_s", "trace_s"):
        assert traffic[key] == assist[key], key
    cfg = loading.load_config(loading.load_benchmark(), CONFIG)
    assert (
        traffic["prompt_len"]["clip"][1] + traffic["output_len"]["clip"][1]
        <= cfg["serve"]["max_seq_len"]
    )
    # at least 110 counted requests in the 51 s, or tpot_p90_ms reads None
    assert traffic["rate_per_s"] * 51 >= 110
    json.dumps(traffic)


def test_the_cell_reports_every_accepted_metric_kananas_cell_does():
    bench = loading.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells and "serve_kanana2_assist" in cells:
            assert cells[-1] == CELL, m["name"]
    mine = {m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert mine == {
        "kda_decode_share", "kda_prefill_share", "kda_state_roofline",
        "kda_scan_roofline"}
    assert loading.find_cell(bench, CELL)["chips"] == 1


def test_the_controls_fail_the_limit_in_the_rehearsal():
    """`--override load_controls=true`: a decode from the zero state, a
    decode without the tails and bfloat16 weights each read far above the
    `highest` limit that the programs themselves keep."""
    line = last_line(run_cell(
        loading.ROOT, CELL, 0, extra=("--override", "load_controls=true")))
    checks = line["checks"]
    limit = loading.load_config(loading.load_benchmark(), CONFIG)["tolerance"][
        "logits_highest_rel"]
    assert line["correct"] is True and checks["logits_rel_gap_at_highest"] < limit
    controls = checks["controls"]
    for name in ("reference_decoding_from_the_zero_state",
                 "reference_decoding_without_the_tails",
                 "reference_with_bfloat16_weights"):
        assert controls[name] > 100 * limit, name
    assert checks["decode_steps_chained_share"] > 0.5
