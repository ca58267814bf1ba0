"""The looped model's readers on a hand-made decode table (a row a PCG
node's scope, as `flexflow_tpu.utils.profiling.fold_step` gives them), on
records that hold nothing to read, and the new cell's files against the
catalog row they were cut from."""

import json
import os

import pytest

from benchmarks.lib import loading, loop_readers
from benchmarks.lib.readers import Run

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = (
    "loop_attn_decode_share", "loop_mlp_decode_share", "loop_norm_decode_share",
    "loop_later_pass_share", "weights_applied_over_stored",
)


def table(passes=4, layers=2):
    """A decode program of 10 ms: a pass is `layers` x (attention 0.5 of
    which 0.2 charged prefetch, MLP 0.4, four norms of 0.01, two adds of
    0.005) and a final norm of 0.01; embedding, head and sampling 1.0."""
    rows = [("embedding:embedding", 0.1, 0.0), ("linear:head", 0.8, 0.5),
            ("step.pick", 0.1, 0.0)]
    for p in range(1, passes + 1):
        for i in range(1, layers + 1):
            at = f"p{p}.l{i}"
            rows.append((f"multihead_attention:{at}.attn", 0.5, 0.2))
            rows.append((f"gated_mlp:{at}.mlp", 0.4, 0.3))
            rows += [(f"rmsnorm:{at}.n{k}", 0.01, 0.0) for k in (1, 2, 3, 4)]
            rows += [(f"ew_add:{at}.add{k}", 0.005, 0.0) for k in (1, 2)]
        rows += [(f"rmsnorm:p{p}.norm", 0.01, 0.0), (f"linear:p{p}.gate", 0.0, 0.0)]
    return {"device_ms": 10.0, "executions": 500, "accounted": 0.9, "rows": rows}


def run_of(loop):
    return Run({"kind": "serve", "loop": loop}, None, {}, None, 0.0, {})


def read(name, run):
    return loading.load_module("metrics", name).read(run)


def test_shares_by_kind_and_by_pass_from_a_decode_table():
    run = run_of({"passes": 4, "walk": None, "decode_by_node": table()})
    assert read("loop_attn_decode_share", run) == pytest.approx(100 * 4.0 / 10.0)
    assert read("loop_mlp_decode_share", run) == pytest.approx(100 * 3.2 / 10.0)
    # 8 x (0.04 + 0.01) + 4 x 0.01
    assert read("loop_norm_decode_share", run) == pytest.approx(100 * 0.44 / 10.0)
    parts = run.notes["loop_attn_decode_share_parts"]
    assert parts["nodes"] == 8 and parts["charged_share"] == pytest.approx(16.0)
    assert parts["by_pass"] == {
        f"pass_{p}": pytest.approx(10.0) for p in (1, 2, 3, 4)}
    assert run.notes["loop_norm_decode_share_parts"]["nodes"] == 8 * 6 + 4
    # passes 2-4: three of four equal passes, of the 76.4% inside the passes
    a_pass = 2 * (0.5 + 0.4 + 0.05) + 0.01
    assert read("loop_later_pass_share", run) == pytest.approx(100 * 3 * a_pass / 10.0)
    notes = run.notes["loop_later_pass_share_parts"]
    assert notes["of_the_passes_own_time"] == pytest.approx(75.0)
    assert sorted(notes["by_pass"]) == ["pass_1", "pass_2", "pass_3", "pass_4"]


def test_the_walk_reads_applied_over_stored():
    walk = {"weights_stored_bytes": 2038644740, "weights_applied_bytes": 5738659856,
            "cache_layers": 24, "weight_layers": 6, "loop_passes": 4}
    run = run_of({"passes": 4, "walk": walk, "decode_by_node": None})
    assert read("weights_applied_over_stored", run) == pytest.approx(2.8149, abs=1e-4)
    assert run.notes["weight_walk"] == walk
    unshared = dict(walk, weights_applied_bytes=walk["weights_stored_bytes"])
    assert read("weights_applied_over_stored", run_of({"walk": unshared})) == 1.0


@pytest.mark.parametrize("name", METRICS)
def test_a_record_with_nothing_to_read_reads_none_and_does_not_raise(name):
    """Another family's record, a training record, an untraced run, a
    program without the walk or the scopes (the parent commit's), a table
    of a model that is not looped."""
    plain = {"device_ms": 5.0, "rows": [("linear:dense", 1.0, 0.0),
                                        ("(unscoped)", 2.0, 0.0)]}
    for record in (
        {"kind": "serve"}, {"kind": "train", "loop": {"walk": {}}},
        {"kind": "serve", "loop": None},
        {"kind": "serve", "loop": {"passes": 4, "walk": None, "decode_by_node": None}},
        {"kind": "serve", "loop": {"walk": {}, "decode_by_node": {"rows": []}}},
        {"kind": "serve", "loop": {"walk": None, "decode_by_node": plain}},
    ):
        assert read(name, Run(record, None, {}, None, 0.0, {})) is None


def test_every_new_metric_is_an_entry_for_the_new_cell_alone():
    bench = loading.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert entries[name]["workloads"] == ["serve_ouro_reason"]
        assert entries[name]["layer"] == "looped stack"
        assert entries[name]["moves"] == "tpot_p90_ms"
    reported = {m["name"] for m in loading.metrics_of(bench, "per_layer", "serve_ouro_reason")}
    assert {"decode_kernel_roofline", "decode_step_ms", "peak_hbm_gb.chat"} <= reported
    assert not any(n.startswith(("moe_", "mla_", "expert")) for n in reported)


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    bench = loading.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    cfg = loading.load_config(bench, "ouro_2_6b")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 6 and cfg["total_ut_steps"] == 4
    # the arithmetic of the cut, by shapes
    shapes, e, f, v = cfg["by_shapes"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    layer = 4 * e * e + 3 * e * f + 4 * e
    stored = 6 * layer + 2 * v * e + e + e + 1
    assert layer == shapes["parameters_per_layer"] == 51388416
    assert stored == shapes["parameters"] and 4 * stored == shapes["weight_bytes"]
    applied = stored + 3 * (6 * layer + e + e + 1)
    assert 4 * applied == shapes["weight_bytes_applied_a_step"]
    assert round(applied / stored, 2) == 2.81
    row_bytes = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 4
    assert shapes["cache_layers"] == 24
    assert shapes["kv_bytes_per_token"] == 24 * row_bytes == 384 * 1024
    assert shapes["pool_bytes"] == cfg["serve"]["kv_pool_tokens"] * 24 * row_bytes
    # a quarter of a v5e's 16 GB, before a single program's temporaries
    assert shapes["weight_bytes"] + shapes["pool_bytes"] > 0.25 * 16e9
    assert cfg["serve"]["max_seq_len"] == 2048 and cfg["serve"]["max_seqs"] == 16
    assert cfg["serve"]["prefill_buckets"] == [128, 256, 640]


def test_the_traffic_is_the_issues():
    traffic = loading.load_traffic("reason_ouro")
    assert traffic["kind"] == "open_loop" and traffic["mode"] == "latency"
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.7, "clip": [16, 512]}
    assert traffic["output_len"]["dist"] == "lognormal"
    assert traffic["output_len"]["sigma"] == 0.7
    assert traffic["output_len"]["clip"] == [32, 1536]
    assert traffic["output_len"]["median"] in (256, 192)  # 192 under 2.2 req/s
    assert traffic["gap"] == {"dist": "exponential", "mean": 1.0}
    assert (traffic["lead_in_s"], traffic["drain_s"], traffic["trace_s"]) == (6.0, 6.0, 12.0)
    # every context fits the positions served, and the longest answer ends
    # inside the time the harness follows it
    cfg = loading.load_config(loading.load_benchmark(), "ouro_2_6b")
    assert traffic["prompt_len"]["clip"][1] + traffic["output_len"]["clip"][1] <= (
        cfg["serve"]["max_seq_len"])
    assert traffic["finish_timeout_s"] >= 0.04 * traffic["output_len"]["clip"][1]
    # at least 110 counted requests in the 51 s, or tpot_p90_ms reads None
    assert traffic["rate_per_s"] * 51 >= 110
    assert os.path.isfile(os.path.join(loading.BENCH_DIR, "reference", "ouro.py"))
