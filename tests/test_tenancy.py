"""Multi-tenant serving (flexflow_tpu.serving.tenancy): adapter-pool
ledger discipline (load/unload/attach refcounts, exhaustion,
invariants), the adapter-identity contract (`adapter_id = -1` is
bit-identical to an engine with no pool at all, across every engine
path), mixed-adapter batch isolation (token-identical to isolated
runs), weighted-fair deficit round-robin invariants (deficit
conservation, weighted shares, no starvation, grants within budget),
the class-priced deterministic preemption-victim rule, per-class SLO
labels on the metrics export, and the per-class token-budget
optimizer. All CPU-fast (tier 1)."""

import json

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
    Request,
    RequestStatus,
    ServeConfig,
    build_scheduler,
)
from flexflow_tpu.serving.tenancy import (
    AdapterPool,
    AdapterPoolExhausted,
    DeficitRoundRobin,
    PriorityClass,
    make_lora_weights,
    parse_classes,
)

pytestmark = pytest.mark.serving

VOCAB = 50


def _lm(seed=0, batch=4, seq=32):
    cfg = FFConfig(batch_size=batch, seed=seed)
    model = FFModel(cfg)
    tok = model.create_tensor([batch, seq], dtype=DataType.INT32,
                              name="tokens")
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


def _pool(lm, max_adapters=4, max_rank=8, **kw):
    return AdapterPool.from_model(
        lm, max_seqs=4, max_adapters=max_adapters, max_rank=max_rank, **kw
    )


def _load(pool, aid, rank=None, seed=None):
    rank = rank if rank is not None else pool.spec.max_rank
    w = make_lora_weights(pool.spec, rank, seed=seed if seed is not None
                          else aid)
    pool.load(aid, w)
    return w


# -- adapter pool ledgers ----------------------------------------------------


def test_pool_load_attach_refcounts(lm):
    pool = _pool(lm)
    _load(pool, 0)
    pool.check_invariants()
    assert 0 in pool.loaded
    pool.attach(0, 0)
    pool.check_invariants()
    # loaded (1) + one attached slot (1)
    pages = [int(p) for p in pool.adapter_tables[0]
             if p != pool.spec.num_pages]
    assert pages and all(pool._adapter_refcounts[p] == 2 for p in pages)
    # unload refuses while a slot still gathers from these pages
    with pytest.raises(RuntimeError, match="attached"):
        pool.unload(0)
    pool.detach(0)
    pool.check_invariants()
    assert all(pool._adapter_refcounts[p] == 1 for p in pages)
    pool.unload(0)
    pool.check_invariants()
    assert 0 not in pool.loaded
    assert all(pool._adapter_refcounts[p] == 0 for p in pages)


def test_pool_attach_requires_free_slot_and_detach_is_idempotent(lm):
    pool = _pool(lm)
    _load(pool, 0)
    _load(pool, 1)
    pool.attach(0, 0)
    with pytest.raises(RuntimeError, match="detach first"):
        pool.attach(0, 1)
    pool.detach(0)
    pool.detach(0)  # idempotent: already free
    pool.attach(0, 1)
    pool.detach(0)
    pool.check_invariants()


def test_pool_exhaustion_is_typed_and_harmless(lm):
    # id space for 4 adapters, page heap sized for only 2
    per = _pool(lm).spec.pages_for(8)
    pool = _pool(lm, max_adapters=4, num_pages=2 * per)
    _load(pool, 0)
    _load(pool, 1)
    with pytest.raises(AdapterPoolExhausted):
        _load(pool, 2)
    pool.check_invariants()  # the failed load left no partial pages
    pool.unload(0)
    _load(pool, 2)  # freed pages are reusable
    pool.check_invariants()


# -- weighted-fair deficit round-robin ---------------------------------------


def test_drr_deficit_conservation_under_mixed_costs():
    """Deficits stay within (-eps, quantum + max_cost) through an
    arbitrary grant history — the conservation property that makes the
    scheduler's planner starvation-free."""
    drr = DeficitRoundRobin({"gold": 4.0, "silver": 2.0, "bronze": 1.0},
                            unit=16.0)
    costs = {"gold": 16.0, "silver": 8.0, "bronze": 16.0}
    rng = np.random.RandomState(7)
    for i in range(200):
        backlogged = [c for c in costs if rng.rand() < 0.8] or ["gold"]
        offered = {c: costs[c] for c in backlogged}
        name, rounds = drr.select(offered)
        drr.charge(name, rounds, backlogged, cost=offered[name])
        drr.check_invariants(max_cost=16.0)
        if i % 50 == 0:
            drr.settle(backlogged)
            drr.check_invariants(max_cost=16.0)


def test_drr_grants_track_weights():
    """With every class permanently backlogged at unit cost, landed
    grants converge to the configured weight ratio."""
    drr = DeficitRoundRobin({"gold": 3.0, "bronze": 1.0}, unit=1.0)
    grants = {"gold": 0, "bronze": 0}
    costs = {"gold": 1.0, "bronze": 1.0}
    for _ in range(400):
        name, rounds = drr.select(costs)
        drr.charge(name, rounds, list(costs), cost=1.0)
        grants[name] += 1
    ratio = grants["gold"] / max(1, grants["bronze"])
    assert 2.5 <= ratio <= 3.5, grants


def test_drr_no_starvation_at_extreme_weights():
    """A 100:1 weight split still serves the light class — deficit
    accrual guarantees every backlogged class lands grants at SOME
    bounded interval (weighted fairness, not strict priority)."""
    drr = DeficitRoundRobin({"gold": 100.0, "bronze": 1.0}, unit=1.0)
    costs = {"gold": 1.0, "bronze": 1.0}
    bronze = 0
    for _ in range(500):
        name, rounds = drr.select(costs)
        drr.charge(name, rounds, list(costs), cost=1.0)
        bronze += name == "bronze"
    assert bronze >= 3, bronze


def test_parse_classes_grammar():
    classes = parse_classes("gold:4:200:20,bronze:1")
    assert list(classes) == ["gold", "bronze"]
    assert classes["gold"] == PriorityClass("gold", 4.0, 200.0, 20.0)
    assert classes["bronze"].weight == 1.0
    assert classes["bronze"].slo_ttft_ms == 0.0
    # a bare name is valid (weight defaults to 1)
    assert parse_classes("gold")["gold"].weight == 1.0
    for bad in ("", "gold:0", "a:1,a:2", "a:1:x"):
        with pytest.raises(ValueError):
            parse_classes(bad)


# -- scheduler integration ---------------------------------------------------


_CLASSES = "gold:4:0:0,bronze:1"


def _mixed_requests(n=8, max_new=6):
    reqs = []
    for i in range(n):
        reqs.append(
            Request(
                rid=i,
                prompt=[2 + (i % 5), 3, 5 + (i % 3)],
                max_new_tokens=max_new,
                priority_class="gold" if i % 2 == 0 else "bronze",
                tenant="acme" if i % 2 == 0 else "initech",
            )
        )
    return reqs


def test_multiclass_overload_no_starvation_and_budget(lm):
    """2x+ overload (8 requests, 2 slots) with chunked prefill under a
    token budget: every request in BOTH classes finishes (weighted fair
    != strict priority), grants never exceed the budget, and the ledger
    invariants hold every iteration (debug_invariants audits the DRR
    and the adapter pool in _end_iteration)."""
    sched, engine, cache = build_scheduler(
        lm,
        ServeConfig(
            max_seqs=2, max_seq_len=32, token_budget=10, chunk_size=4,
            decode_kernel="dense", classes=_CLASSES,
            debug_invariants=True, telemetry=True,
        ),
    )
    reqs = _mixed_requests()
    sched.run(reqs)
    assert all(r.status == RequestStatus.FINISHED for r in reqs), [
        (r.rid, r.status) for r in reqs
    ]
    assert all(len(r.generated) == 6 for r in reqs)


def test_multiclass_matches_singleclass_tokens(lm):
    """Fairness reorders WHEN work is granted, never WHAT is computed:
    the same request set produces identical tokens under multiclass
    weighted-fair and under the single-class FIFO planner."""
    out = {}
    for classes in ("", _CLASSES):
        sched, _, _ = build_scheduler(
            lm,
            ServeConfig(max_seqs=2, max_seq_len=32, token_budget=10,
                        chunk_size=4, decode_kernel="dense",
                        classes=classes),
        )
        reqs = _mixed_requests()
        if not classes:
            for r in reqs:
                r.priority_class = ""
        sched.run(reqs)
        out[classes or "fifo"] = {r.rid: list(r.generated) for r in reqs}
    assert out["fifo"] == out[_CLASSES]


def test_victim_tiebreak_is_deterministic_by_admission_order(lm):
    """Equal class-priced cost falls back to youngest-first by
    (admit_iter, rid) — the tie-break that keeps chaos schedules
    replayable under the multiclass victim rule."""
    sched, engine, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=4, max_seq_len=32, classes="gold:2,bronze:2"),
    )
    sched._victim_pricer = None  # token-count pricing: exact ties below
    reqs = [
        Request(rid=i, prompt=[2, 3, 5], max_new_tokens=4,
                priority_class="gold" if i % 2 else "bronze")
        for i in range(4)
    ]
    for i, r in enumerate(reqs):
        r.slot = i
        r.status = RequestStatus.RUNNING
        r.admit_iter = i // 2  # two admission batches of two
        sched.running[i] = r
    # equal weights, equal resident tokens -> all costs tie; min() on
    # (cost, -admit_iter, -rid) must pick the youngest: admit_iter 1,
    # rid 3
    costs = {r.rid: sched._victim_cost(r) for r in reqs}
    assert len(set(costs.values())) == 1, costs
    assert sched._pick_victim().rid == 3
    del sched.running[3]
    assert sched._pick_victim().rid == 2
    # a heavier class breaks the tie on price, not age
    sched.classes["gold"] = PriorityClass("gold", 8.0)
    assert sched._pick_victim().rid == 2  # bronze: cheapest to redo
    sched.running.clear()


# -- adapter identity matrix -------------------------------------------------


_MATRIX = [
    pytest.param({"kv_page_size": 32}, id="one_page-dense-sync"),
    pytest.param({}, id="paged-dense-sync"),
    pytest.param({"kv_dtype": "int8"}, id="paged-int8"),
    pytest.param({"serve_async": True}, id="paged-async"),
    pytest.param({"spec_draft": "ngram", "spec_k": 3}, id="paged-spec"),
    pytest.param({"token_budget": 10, "chunk_size": 4,
                  "decode_kernel": "dense"}, id="paged-chunked"),
    pytest.param({"decode_kernel": "pallas"}, id="paged-pallas"),
    pytest.param({"kv_page_size": 32, "decode_kernel": "pallas"},
                 id="one_page-pallas"),
]


def _run(lm, serve_kw, reqs):
    sched, engine, _ = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32, **serve_kw)
    )
    if engine.adapters is not None:
        for aid in (0, 1):
            _load(engine.adapters, aid)
    sched.run(reqs)
    assert all(r.status == RequestStatus.FINISHED for r in reqs)
    return {r.rid: list(r.generated) for r in reqs}


@pytest.mark.parametrize("serve_kw", _MATRIX)
def test_adapter_identity_matrix(lm, serve_kw):
    """The headline contract: an engine CARRYING a loaded adapter pool,
    serving requests that never reference an adapter (adapter_id = -1,
    the default), emits bit-identical tokens to an engine with no pool
    at all — on every path: {slot, paged} x {fp32, int8} x {sync,
    async} x speculative x chunked x {dense, pallas}."""
    mk = lambda: [  # noqa: E731
        Request(rid=i, prompt=[2 + i, 3, 5], max_new_tokens=5)
        for i in range(3)
    ]
    base = _run(lm, dict(serve_kw), mk())
    pooled = _run(lm, dict(serve_kw, adapters=2, adapter_rank=4), mk())
    assert base == pooled


def test_mixed_adapter_batch_matches_isolated_runs(lm):
    """Tenant isolation: requests on adapters A, B, and no adapter,
    IN ONE BATCH, produce exactly the tokens each would produce running
    alone — the per-slot gather never leaks one slot's delta into
    another's projection. The no-adapter stream also matches a
    pool-free engine (identity inside a mixed batch)."""
    kw = dict(adapters=2, adapter_rank=4)
    mk = lambda aid, rid: Request(  # noqa: E731
        rid=rid, prompt=[7, 3, 5], max_new_tokens=6, adapter_id=aid
    )
    mixed = _run(lm, dict(kw), [mk(0, 0), mk(1, 1), mk(-1, 2)])
    alone = {}
    for aid in (0, 1, -1):
        alone.update(_run(lm, dict(kw), [mk(aid, aid if aid >= 0 else 2)]))
    assert mixed == alone
    base = _run(lm, dict(), [mk(-1, 9)])
    assert mixed[2] == base[9]
    # adapters actually bite: A, B and the base model disagree on the
    # prompt's last logits, each by a tenth of a logit or more. Held on
    # the logits and not on the six greedy tokens: this toy model's base
    # stream is one token repeated, B moves every logit (by 0.58) and
    # still leaves that token first, so `mixed[1] != mixed[2]` failed
    # with nothing wrong in the gather
    last = {}
    for aid in (0, 1, -1):
        _, engine, cache = build_scheduler(
            lm, ServeConfig(max_seqs=2, max_seq_len=32, **kw)
        )
        for loaded in (0, 1):
            _load(engine.adapters, loaded)
        slot = cache.alloc(3, 9)
        engine.adapters.attach(slot, aid)
        last[aid] = engine.prefill(lm.params, [[7, 3, 5]], [slot])[1][0]
    for a, b in ((0, -1), (1, -1), (0, 1)):
        assert np.abs(last[a] - last[b]).max() > 0.1


def test_unknown_class_and_unloaded_adapter_are_rejected(lm):
    sched, engine, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32, classes=_CLASSES,
                    adapters=2),
    )
    with pytest.raises(ValueError, match="unknown priority class"):
        sched.submit(Request(rid=0, prompt=[2], max_new_tokens=1,
                             priority_class="platinum"))
    with pytest.raises(ValueError, match="not loaded"):
        sched.submit(Request(rid=1, prompt=[2], max_new_tokens=1,
                             adapter_id=0))
    sched2, engine2, _ = build_scheduler(
        lm, ServeConfig(max_seqs=2, max_seq_len=32)
    )
    with pytest.raises(ValueError, match="adapter pool"):
        sched2.submit(Request(rid=2, prompt=[2], max_new_tokens=1,
                              adapter_id=0))


# -- per-class telemetry -----------------------------------------------------


def test_per_class_labels_in_metrics_jsonl(lm, tmp_path):
    """The JSONL export carries class- and tenant-labelled series next
    to the fleet-wide ones, every labelled key matches the grammar the
    schema documents, and the file validates."""
    from flexflow_tpu.telemetry import validate_metrics_jsonl_file

    path = tmp_path / "metrics.jsonl"
    sched, _, _ = build_scheduler(
        lm,
        ServeConfig(
            max_seqs=2, max_seq_len=32, classes="gold:4:200:20,bronze:1",
            adapters=2, metrics_jsonl=str(path), telemetry=True,
        ),
    )
    reqs = _mixed_requests(n=6)
    sched.run(reqs)
    assert validate_metrics_jsonl_file(str(path)) == []
    keys = set()
    with open(path) as f:
        for line in f:
            keys.update(json.loads(line))
    assert 'serve_queue_depth{class="gold"}' in keys
    assert 'serve_running_requests{class="bronze"}' in keys
    assert any(k.startswith('serve_requests_total{') and 'tenant="acme"'
               in k for k in keys), sorted(keys)
    # per-class rolling SLO gauges ride the same rows
    assert any(k.startswith("serve_ttft_ms_") and 'class="gold"' in k
               for k in keys), sorted(keys)
    # adapter-pool gauges are exported when a pool is attached
    assert "adapter_pages_free" in keys


def test_labelled_key_grammar_is_enforced():
    from flexflow_tpu.telemetry import validate_metrics_jsonl

    good = json.dumps({"iteration": 0, "t_s": 0.0,
                       'serve_requests_total{class="gold",tenant="a"}': 1})
    assert validate_metrics_jsonl([good]) == []
    bad = json.dumps({"iteration": 0, "t_s": 0.0,
                      'serve_requests_total{class=gold}': 1})
    errs = validate_metrics_jsonl([bad], errors="list")
    assert errs and "labelled grammar" in errs[0]


def test_class_slo_snapshot_rides_monitors(lm):
    from flexflow_tpu.serving.tenancy.slo import class_slo_snapshot

    sched, _, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32,
                    classes="gold:4:10000:10000,bronze:1",
                    telemetry=True),
    )
    reqs = _mixed_requests(n=4)
    sched.run(reqs)
    snap = class_slo_snapshot(sched._class_slo)
    assert set(snap) == {"gold", "bronze"}
    for name in snap:
        assert snap[name]["ttft_observations"] >= 2, snap
    # generous thresholds: nothing violated
    assert snap["gold"]["violations"]["ttft"] == 0


# -- per-class budget optimizer ----------------------------------------------


def test_optimize_token_budget_per_class(lm):
    """One shared iteration budget sized against every class's own
    SLO: the answer is the max over per-class solves and meets_slo
    only when every class's own solve does."""
    from flexflow_tpu.core.machine import MachineSpec
    from flexflow_tpu.search.auto import (
        optimize_token_budget,
        optimize_token_budget_per_class,
    )

    spec = MachineSpec(num_nodes=1, chips_per_node=1, chip="v5e")
    classes = parse_classes("gold:4:200:20,bronze:1:1000:100")
    budget, meets, per = optimize_token_budget_per_class(
        lm.graph, spec, 64, classes, batch=2, chunk_size=8
    )
    assert set(per) == {"gold", "bronze"}
    assert budget == max(r.token_budget for r in per.values())
    assert meets is all(r.meets_slo for r in per.values())
    # each per-class solve equals a direct solve at that class's SLOs
    direct = optimize_token_budget(
        lm.graph, spec, 64, batch=2, chunk_size=8, slo_ttft_ms=200.0,
        slo_itl_ms=20.0,
    )
    assert per["gold"].token_budget == direct.token_budget
    with pytest.raises(ValueError, match="non-empty"):
        optimize_token_budget_per_class(lm.graph, spec, 64, {}, batch=1)
