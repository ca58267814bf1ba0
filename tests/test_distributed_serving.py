"""Pod-scale serving (flexflow_tpu/serving/distributed.py +
FFModel.compile_for_serving): the (data, model) serving mesh applied to
attention weights and KV pools, the host-partitioned slot/page
allocator, degenerate 1x1 parity with the pre-placement engine
(token- AND logit-identical across sync/async x spec x chunked x
prefix-cache), multi-device CPU-mesh token parity, per-host telemetry
labels and trace lanes, and the exported serving placement doc's
FX310-FX312 validation. Runs on the conftest-forced 8-virtual-device
CPU platform; all tier 1."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu import (
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.analysis.strategy_check import (
    validate_serving_placement_doc,
    validate_strategy_doc,
)
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.models import build_decoder_lm
from flexflow_tpu.serving import (
    KVCacheSpec,
    PagedKVCache,
    Request,
    ServeConfig,
    build_scheduler,
)
from flexflow_tpu.serving.distributed import (
    ServingPlacement,
    build_placement,
    parse_serve_mesh,
    resolve_num_hosts,
)

pytestmark = pytest.mark.serving

VOCAB = 50

PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 3, 1, 2], [7], [11, 12],
           [3, 3, 3], [8, 1], [2]]


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
    """Un-placed baseline: the pre-existing single-device engine."""
    return _lm()


@pytest.fixture(scope="module")
def deg_lm():
    """Degenerate 1x1x1 placement — must be identical to `lm`."""
    model = _lm()
    model.compile_for_serving(dp=1, tp=1, num_hosts=1)
    return model


@pytest.fixture(scope="module")
def mesh_lm():
    """dp=2, tp=2 over 4 virtual CPU devices, 2 host partitions."""
    model = _lm()
    model.compile_for_serving(dp=2, tp=2, num_hosts=2)
    return model


def _gen(model, **over):
    serve = dict(max_seqs=4, max_seq_len=32)
    serve.update(over)
    return model.generate(
        PROMPTS, max_new_tokens=6, serve_config=ServeConfig(**serve)
    )


def _host_placement(num_hosts=2, num_heads=2):
    """Placement stub for allocator-only tests (no device mesh needed —
    PagedKVCache reads just num_hosts from it)."""
    return ServingPlacement(
        mesh=None, dp=num_hosts, tp=1, num_hosts=num_hosts,
        num_heads=num_heads,
    )


def _host_cache(num_pages=8, max_seqs=4, prefix_cache=False):
    spec = KVCacheSpec(
        layer_guids=(1,), max_seqs=max_seqs, max_len=32, num_heads=2,
        head_dim=4, buckets=(32,), page_size=4, num_pages=num_pages,
    )
    return PagedKVCache(
        spec, jnp.float32, prefix_cache=prefix_cache,
        placement=_host_placement(),
    )


# -- flag parsing / placement units ------------------------------------------


def test_parse_serve_mesh():
    assert parse_serve_mesh("") is None
    assert parse_serve_mesh("2,4") == (2, 4)
    assert parse_serve_mesh(" 1 , 1 ") == (1, 1)
    for bad in ("2", "2,4,8", "a,b", "0,2", "2,-1"):
        with pytest.raises(ValueError):
            parse_serve_mesh(bad)


def test_resolve_num_hosts():
    # explicit flag wins; otherwise one partition per data shard
    assert resolve_num_hosts(4, 2) == 4
    assert resolve_num_hosts(0, 2) == 2
    assert resolve_num_hosts(0, 1) == 1


def test_validate_geometry_rejects_uneven_partitions():
    pl = _host_placement(num_hosts=2, num_heads=4)
    pl.validate_geometry(4, 8)  # clean split
    with pytest.raises(ValueError, match="max_seqs"):
        pl.validate_geometry(3, 8)
    with pytest.raises(ValueError, match="num_pages"):
        pl.validate_geometry(4, 9)
    bad_tp = ServingPlacement(
        mesh=None, dp=1, tp=3, num_hosts=1, num_heads=4
    )
    with pytest.raises(ValueError, match="num_heads"):
        bad_tp.validate_geometry(4, 8)


def test_build_placement_rejects_tp_not_dividing_heads(lm):
    with pytest.raises(ValueError, match="num_heads"):
        build_placement(lm, 1, 3)


def test_serve_config_pod_validation():
    with pytest.raises(ValueError, match="serve-mesh"):
        ServeConfig(serve_mesh="nope")
    with pytest.raises(ValueError, match="serve_hosts"):
        ServeConfig(serve_hosts=-1)
    ServeConfig(serve_mesh="2,2", serve_hosts=2)  # well-formed


def test_pod_flags_parse():
    cfg = FFConfig.parse_args(
        ["--serve-mesh", "2,2", "--serve-hosts", "2",
         "--serve-export-strategy", "out.json"]
    )
    assert cfg.serve_mesh == "2,2"
    assert cfg.serve_hosts == 2
    assert cfg.serve_export_strategy == "out.json"
    sc = ServeConfig.from_config(cfg)
    assert (sc.serve_mesh, sc.serve_hosts) == ("2,2", 2)
    # defaults: no mesh, auto hosts
    sc = ServeConfig.from_config(FFConfig.parse_args([]))
    assert (sc.serve_mesh, sc.serve_hosts) == ("", 0)


# -- mesh application (sharding assertions) ----------------------------------


def test_compile_for_serving_shards_attention_weights(mesh_lm):
    pl = mesh_lm.serving_placement
    assert (pl.dp, pl.tp, pl.num_hosts) == (2, 2, 2)
    saw_attention = False
    for guid, ws in mesh_lm.params.items():
        node = mesh_lm.graph.nodes[guid]
        for w in ws:
            sh = w.sharding
            assert isinstance(sh, NamedSharding)
            assert sh.mesh == pl.mesh
        if node.op_type == OperatorType.MULTIHEAD_ATTENTION:
            saw_attention = True
            # wq/wk/wv: (embed, heads, head_dim) — heads on "model"
            for i in range(3):
                assert ws[i].sharding.spec == PartitionSpec(
                    None, "model", None
                )
            # wo: (heads, head_dim, embed) — heads-major
            assert ws[3].sharding.spec == PartitionSpec(
                "model", None, None
            )
    assert saw_attention


def test_kv_pools_on_serving_mesh(mesh_lm):
    pl = mesh_lm.serving_placement
    _, _, cache = build_scheduler(
        mesh_lm, ServeConfig(max_seqs=4, max_seq_len=32)
    )
    assert cache.num_hosts == 2
    for g in cache.spec.layer_guids:
        for pool in (cache.k[g], cache.v[g]):
            sh = pool.sharding
            assert isinstance(sh, NamedSharding)
            assert sh.mesh == pl.mesh
            # paged pools are [pages, page_size, heads * head_dim]: the
            # model axis cuts the folded dim into whole heads
            assert pool.ndim == 3
            assert sh.spec == PartitionSpec("data", None, "model")


def test_quantized_scale_pools_on_serving_mesh(mesh_lm):
    pl = mesh_lm.serving_placement
    _, _, cache = build_scheduler(
        mesh_lm, ServeConfig(max_seqs=4, max_seq_len=32, kv_dtype="int8")
    )
    for g in cache.spec.layer_guids:
        for pool in (cache.k_scale[g], cache.v_scale[g]):
            assert pool.sharding.spec == PartitionSpec("data", "model")
            assert pool.sharding.mesh == pl.mesh


# -- degenerate 1x1 parity ---------------------------------------------------


_PARITY_VARIANTS = [
    pytest.param(dict(), id="sync"),
    pytest.param(dict(serve_async=True), id="async"),
    pytest.param(dict(token_budget=32, chunk_size=8), id="chunked"),
    pytest.param(
        dict(prefix_cache=True, kv_page_size=4, max_seq_len=64),
        id="prefix-cache",
    ),
    pytest.param(dict(spec_draft="ngram", spec_k=3), id="spec-ngram"),
]


@pytest.mark.parametrize("variant", _PARITY_VARIANTS)
def test_degenerate_mesh_token_identical(lm, deg_lm, variant):
    """The 1x1 serving mesh is the pre-placement engine: token-for-token
    identical across every scheduler mode."""
    assert _gen(deg_lm, **variant) == _gen(lm, **variant)


def test_degenerate_mesh_logits_identical(lm, deg_lm):
    """Bitwise logit agreement, not just argmax: prefill + one decode on
    the 1x1-placed model reproduce the un-placed model exactly (same
    single device, same program)."""
    prompt = [3, 1, 4, 1, 5]
    got = {}
    for name, model in (("base", lm), ("deg", deg_lm)):
        _, engine, cache = build_scheduler(
            model, ServeConfig(max_seqs=2, max_seq_len=32)
        )
        slot = cache.alloc(len(prompt), len(prompt) + 2)
        nxt, last = engine.prefill(model.params, [prompt], [slot])
        tokens = np.zeros(cache.spec.max_seqs, dtype=np.int32)
        active = np.zeros(cache.spec.max_seqs, dtype=bool)
        tokens[slot] = int(nxt[0])
        active[slot] = True
        _, dec = engine.decode(model.params, tokens, active)
        got[name] = (np.asarray(last[0]), np.asarray(dec[slot]))
    np.testing.assert_array_equal(got["deg"][0], got["base"][0])
    np.testing.assert_array_equal(got["deg"][1], got["base"][1])


# -- multi-device mesh parity ------------------------------------------------


@pytest.mark.parametrize(
    "variant",
    [
        pytest.param(dict(), id="sync"),
        pytest.param(dict(serve_async=True), id="async"),
        pytest.param(dict(token_budget=32, chunk_size=8), id="chunked"),
    ],
)
def test_pod_mesh_token_identical(lm, mesh_lm, variant):
    """dp=2/tp=2 over 4 virtual CPU devices with 2 host partitions
    streams the same tokens as the single-device engine (the chunked
    variant exercises the per-host token budgets)."""
    assert _gen(mesh_lm, **variant) == _gen(lm, **variant)


def test_serve_mesh_flag_end_to_end(lm):
    """--serve-mesh/--serve-hosts route through build_scheduler's
    compile_for_serving auto-invocation; tokens match the baseline."""
    model = _lm()
    out = _gen(model, serve_mesh="4,1", serve_hosts=4)
    pl = getattr(model, "serving_placement", None)
    assert pl is not None
    assert (pl.dp, pl.tp, pl.num_hosts) == (4, 1, 4)
    assert pl.mesh_source == "flag"
    assert out == _gen(lm)


def test_pod_capacity_scales_with_hosts():
    """The count the pod CI gate held: at an equal PER-HOST page budget,
    four host partitions run four times the concurrent requests of one
    (the partition is contention-free; the gate asked for 3x). Every
    request reserves two pages, so pages bind, not slots."""
    page, pages_per_host = 16, 8
    peak = {}
    for hosts in (1, 4):
        slots = pages = pages_per_host * hosts
        serve = ServeConfig(
            max_seqs=slots, max_seq_len=32, kv_page_size=page,
            kv_pages=pages, serve_hosts=hosts if hosts > 1 else 0,
            **({"serve_mesh": "4,1"} if hosts > 1 else {}),
        )
        sched, _, cache = build_scheduler(_lm(batch=slots), serve)
        assert cache.num_hosts == hosts
        done = sched.run([
            Request(rid=i, max_new_tokens=2,
                    prompt=[(i * 7 + j) % (VOCAB - 1) + 1 for j in range(page)])
            for i in range(2 * slots)
        ])
        assert len(done) == 2 * slots and all(r.ok for r in done)
        peak[hosts] = sched.stats.peak_in_flight
    assert peak[1] == pages_per_host // 2
    assert peak[4] == 4 * peak[1]


# -- searched mesh: applied vs inherited -------------------------------------


def test_search_result_defaults_to_inherited(lm):
    from flexflow_tpu.search.auto import search_serving_strategy

    sr = search_serving_strategy(lm, batch_size=4)
    assert sr.mesh_execution == "inherited"
    assert "[inherited]" in sr.describe()


def test_searched_mesh_recorded_applied(tmp_path):
    model = _lm()
    out = tmp_path / "serving_strategy.json"
    model.config.serve_export_strategy = str(out)
    pl = model.compile_for_serving()  # no flag, no args -> search
    assert pl.mesh_source == "searched"
    sr = model.serve_search_result
    assert sr.mesh_execution == "applied"
    assert "[applied]" in sr.describe()
    assert (sr.dp, sr.tp) == (pl.dp, pl.tp)
    doc = json.loads(out.read_text())
    assert doc["kind"] == "serving"
    assert doc["mesh_source"] == "searched"
    assert doc["search"]["mesh_execution"] == "applied"
    assert validate_strategy_doc(doc) == []


# -- serving placement doc validation (FX310-FX312) --------------------------


def test_placement_doc_round_trip(mesh_lm):
    doc = mesh_lm.serving_placement.to_doc(max_seqs=4, num_pages=8)
    assert validate_strategy_doc(doc) == []
    assert validate_serving_placement_doc(doc, num_devices=4) == []


def test_placement_doc_rules_fire(mesh_lm):
    good = mesh_lm.serving_placement.to_doc(max_seqs=4, num_pages=8)

    def rules(**over):
        return [
            d.rule_id for d in validate_strategy_doc(dict(good, **over))
        ]

    assert "FX310" in rules(mesh_axes=["x", "y"])
    assert "FX310" in rules(mesh_sizes=[2, 4])
    assert "FX310" in rules(num_hosts=0)
    assert "FX311" in rules(tp=3, mesh_sizes=[2, 3])
    assert "FX312" in rules(num_hosts=3)
    assert "FX312" in rules(
        page_pool={"num_pages": 8, "pages_per_host": 3}
    )
    assert [
        d.rule_id
        for d in validate_serving_placement_doc(good, num_devices=2)
    ] == ["FX305"]


# -- host-partitioned allocator ----------------------------------------------


def test_host_partition_blocks():
    cache = _host_cache()
    assert cache.num_hosts == 2
    assert cache._slots_per_host == 2
    assert cache._pages_per_host == 4
    assert [cache.host_of_slot(s) for s in range(4)] == [0, 0, 1, 1]
    assert cache.free_pages_by_host() == [4, 4]
    cache.check_invariants()


def test_per_host_admission_refuses_fragmented_pool():
    """Admission is per host: a request's pages never straddle hosts, so
    a pod whose free pages are split across partitions refuses a request
    the GLOBAL count would accept."""
    cache = _host_cache()
    r1 = cache.alloc(4, 16)  # 1 page held + 3 reserved on host 0
    r2 = cache.alloc(4, 16)  # balances onto host 1
    assert {cache.host_of_slot(r1), cache.host_of_slot(r2)} == {0, 1}
    assert cache.num_free_pages == 6  # 3 free per host...
    assert not cache.can_admit(4, 16)  # ...but 0 headroom per host
    assert not cache.can_admit(1, 4)
    cache.check_invariants()
    cache.free(r1)
    assert cache.can_admit(4, 16)
    cache.free(r2)
    assert cache.free_pages_by_host() == [4, 4]
    cache.check_invariants()


def test_pages_stay_host_local():
    cache = _host_cache()
    r1 = cache.alloc(16, 16)  # 4 pages, fills one host's shard
    h1 = cache.host_of_slot(r1)
    r2 = cache.alloc(4, 16)
    h2 = cache.host_of_slot(r2)
    assert h1 != h2
    for pos in range(4, 16, 4):  # grow r2 through its reserve
        cache.ensure_position(r2, pos)
    for slot, h in ((r1, h1), (r2, h2)):
        lo, hi = h * 4, (h + 1) * 4
        pages = [
            int(p) for p in cache.block_tables[slot]
            if p != cache.spec.num_pages
        ]
        assert pages and all(lo <= p < hi for p in pages)
    cache.check_invariants()


def test_alloc_shared_truncates_match_at_foreign_pages():
    """Prefix sharing is host-local: a sharer that cannot land on the
    prefix's host maps nothing (full recompute) rather than aliasing
    another host's pages."""
    cache = _host_cache(prefix_cache=True)
    tokens = list(range(1, 9))  # 2 full pages
    # owner holds 2 pages + 2 reserved: its host has ZERO headroom
    a = cache.alloc(8, 16)
    ha = cache.host_of_slot(a)
    cache.lengths[a] = 8
    cache.register_prefix(a, tokens, 8)
    got = cache.alloc_shared(tokens, prompt_len=8, total_len=12)
    assert got is not None
    b, cursor = got
    assert cache.host_of_slot(b) != ha  # owner's host had no headroom
    assert cursor == 0  # match truncated at the first foreign page
    for pi in range(2):
        assert cache._refcounts[int(cache.block_tables[a, pi])] == 1
    cache.check_invariants()
    cache.free(b)

    # with headroom on the owner's host, the sharer lands THERE and maps
    # the full match (locality beats load balance)
    cache.free(a)
    a = cache.alloc(8, 8)  # 2 pages, no reserve: headroom 2 remains
    ha = cache.host_of_slot(a)
    cache.lengths[a] = 8
    cache.register_prefix(a, tokens, 8)
    got = cache.alloc_shared(tokens + [40], prompt_len=9, total_len=12)
    assert got is not None
    c, cursor = got
    assert cache.host_of_slot(c) == ha
    assert cursor == 8  # both full pages shared
    for pi in range(2):
        assert cache._refcounts[int(cache.block_tables[a, pi])] == 2
    assert cache.prefix_hits == 1
    cache.check_invariants()


def test_multihost_invariants_catch_foreign_page():
    cache = _host_cache()
    r = cache.alloc(4, 4)  # 1 page on host 0
    cache.check_invariants()
    # smuggle a host-1 page into the host-0 slot's table
    foreign = cache._free_pages_h[1].pop()
    cache.block_tables[r, 1] = foreign
    cache._refcounts[foreign] = 1
    cache._held[r] += 1
    cache._max_pages[r] += 1
    with pytest.raises(AssertionError):
        cache.check_invariants()


def test_telemetry_gauges_host():
    cache = _host_cache()
    r = cache.alloc(8, 8)  # 2 pages on one host
    h = cache.host_of_slot(r)
    g0 = cache.telemetry_gauges_host(h)
    g1 = cache.telemetry_gauges_host(1 - h)
    assert g0["kv_slots_active"] == 1 and g1["kv_slots_active"] == 0
    assert g0["kv_pages_live"] == 2 and g1["kv_pages_live"] == 0
    assert g0["kv_free_heap_depth"] == 2
    assert g1["kv_free_heap_depth"] == 4


# -- per-host telemetry / trace lanes ----------------------------------------


def test_host_labelled_series_and_trace_lanes(mesh_lm):
    from flexflow_tpu.telemetry.trace import TID_HOST_BASE

    sched, _, cache = build_scheduler(
        mesh_lm, ServeConfig(max_seqs=4, max_seq_len=32, telemetry=True)
    )
    assert cache.num_hosts == 2
    reqs = [
        Request(rid=i, prompt=list(p), max_new_tokens=4)
        for i, p in enumerate(PROMPTS[:6])
    ]
    done = sched.run(reqs)
    assert all(r.status == "finished" for r in done)
    reg = sched.telemetry.registry
    for h in ("0", "1"):
        g = reg.get("kv_slots_free", labels={"host": h})
        assert g is not None
        assert reg.get("kv_free_heap_depth", labels={"host": h}) is not None
        assert (
            reg.get("serve_running_requests", labels={"host": h})
            is not None
        )
    # the unlabelled aggregate series still exist (seed dashboards)
    assert reg.get("kv_slots_free") is not None
    finished_by_host = [
        reg.get(
            "serve_requests_total", labels={"status": "finished", "host": h}
        )
        for h in ("0", "1")
    ]
    total = sum(c.value for c in finished_by_host if c is not None)
    assert total == len(reqs)
    # per-host iteration spans on dedicated lanes, with thread_name metas
    ev = sched.telemetry.tracer.events
    lanes = {
        e["tid"] for e in ev
        if e.get("ph") == "X" and e.get("name") == "iteration"
        and e.get("tid", 0) >= TID_HOST_BASE
    }
    assert lanes == {TID_HOST_BASE, TID_HOST_BASE + 1}
    metas = {
        e["args"]["name"] for e in ev
        if e.get("ph") == "M" and e.get("tid", 0) >= TID_HOST_BASE
    }
    assert metas == {"host 0 partition", "host 1 partition"}


def test_single_host_emits_no_host_labels(lm):
    sched, _, _ = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32, telemetry=True)
    )
    done = sched.run(
        [Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)]
    )
    assert done[0].status == "finished"
    reg = sched.telemetry.registry
    assert reg.get("kv_slots_free") is not None
    assert reg.get("kv_slots_free", labels={"host": "0"}) is None
