"""The step programs own the KV pools they rewrite (serving/engine.py
`_step_jit`): every engine program donates `ck`, `cv`, the scale
pools and the recurrent layers' per-slot state, so XLA scatters into the
pool it was handed instead of copying it whole into a fresh output first.

What that asks of the host, held here on the CPU backend (which honours
a donation, so every check is exact): after a dispatch the arrays that
went in are deleted, the only live pools are the ones `cache.commit`
stored, they hold exactly the rows the step wrote, and nothing else
(parameters, adapter pools) is ever consumed. The engine counts how
often the mechanism engages (`pool_steps_donated` / `pool_steps_copied`),
and the counts reach `SchedulerStats` and its `serve_stats_*` gauges.
"""

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
from flexflow_tpu.serving import Request, ServeConfig, build_scheduler
from flexflow_tpu.serving.tenancy import make_lora_weights
from flexflow_tpu.telemetry import Telemetry

pytestmark = pytest.mark.serving

VOCAB = 50
PROMPT = [3, 1, 4, 1, 5]
SLOTS = 2
KINDS = ("prefill", "decode", "verify", "tree", "chunk")
# (ServeConfig keywords of the cache geometry, kv_dtype)
LAYOUTS = {
    "one_page": ({"kv_page_size": 32}, "fp32"),
    "paged": ({}, "fp32"),
    "paged-int8": ({}, "int8"),
}
# a model with recurrent layers is served by these two programs alone,
# and keeps a row a SLOT beside the pages (`cache.state`)
RECURRENT_KINDS = ("prefill", "decode")


def build_lm(hidden=32):
    cfg = FFConfig(batch_size=4, seed=0)
    model = FFModel(cfg)
    tok = model.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(
        model, tok, vocab_size=VOCAB, hidden=hidden, num_heads=4,
        num_layers=2, ff_dim=64,
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
    return build_lm()


@pytest.fixture(scope="module")
def recurrent_lm():
    from tests import test_kimi_linear

    return test_kimi_linear._model()


def _pool_leaves(cache):
    """Every pool array the cache holds right now, by name."""
    groups = {"k": cache.k, "v": cache.v}
    if cache.quantized:
        groups.update(k_scale=cache.k_scale, v_scale=cache.v_scale)
    for g, rows in cache.state.items():
        for name, a in rows.items():
            groups.setdefault("state." + name, {})[g] = a
    return {(n, g): a for n, d in groups.items() for g, a in d.items()}


def _rows_written(cache, slot, positions):
    """Boolean map over a K/V pool's first two dims ([pages, page_size])
    of the cache rows `positions` of `slot`."""
    spec = cache.spec
    rows = np.zeros((spec.num_pages, spec.page_size), dtype=bool)
    for p in positions:
        page = int(cache.block_tables[slot, p // spec.page_size])
        assert page < spec.num_pages, f"position {p} has no page"
        rows[page, p % spec.page_size] = True
    return rows


def _step(kind, eng, cache, params, slot, nxt):
    """Run one step of `kind` on `slot`; the cache positions it writes."""
    at = int(cache.lengths[slot])
    tokens = np.zeros(SLOTS, dtype=np.int32)
    tokens[slot] = nxt
    active = np.zeros(SLOTS, dtype=bool)
    active[slot] = True
    if kind == "decode":
        eng.decode(params, tokens, active)
        return range(at, at + 1)
    w = {"verify": 3, "tree": 4, "chunk": 3}[kind]
    wide = np.zeros((SLOTS, w), dtype=np.int32)
    wide[slot] = [nxt, 7, 2, 9][:w]
    lens = np.zeros(SLOTS, dtype=np.int32)
    lens[slot] = w
    if kind == "verify":
        eng.verify(params, wide, lens)
    elif kind == "tree":
        # root -> {7, 2}, 7 -> 9: two branches, topological
        parents = np.tile(np.arange(-1, w - 1, dtype=np.int32), (SLOTS, 1))
        parents[slot] = [-1, 0, 0, 1]
        eng.verify_tree(params, wide, lens, parents)
    else:
        eng.prefill_chunk(params, wide, lens)
    return range(at, at + w)


@pytest.mark.parametrize(
    "kind,layout",
    [(k, l) for k in KINDS for l in LAYOUTS]
    + [(k, "recurrent") for k in RECURRENT_KINDS],
)
def test_step_consumes_its_pools_and_commits_the_rows(request, kind, layout):
    geometry, kv_dtype = LAYOUTS.get(layout, ({}, "fp32"))
    model = request.getfixturevalue(
        "recurrent_lm" if layout == "recurrent" else "lm"
    )
    sched, eng, cache = build_scheduler(
        model,
        ServeConfig(
            max_seqs=SLOTS, max_seq_len=32, **geometry, kv_dtype=kv_dtype,
        ),
    )
    params = sched.params
    slot = cache.alloc(len(PROMPT), len(PROMPT) + 8)
    programs = 1
    if kind != "prefill":
        nxt, _ = eng.prefill(params, [PROMPT], [slot])
        programs = 2
    went_in = _pool_leaves(cache)
    # np.array: a copy; a zero-copy view would hold the buffer and make
    # the CPU backend decline the donation
    before = {key: np.array(a) for key, a in went_in.items()}
    if kind == "prefill":
        eng.prefill(params, [PROMPT], [slot])
        positions = range(len(PROMPT))
    else:
        positions = _step(kind, eng, cache, params, slot, int(nxt[0]))

    # what the dispatch was handed is gone; what commit stored is alive
    assert all(a.is_deleted() for a in went_in.values()), [
        key for key, a in went_in.items() if not a.is_deleted()
    ]
    live = _pool_leaves(cache)
    assert live.keys() == went_in.keys()
    assert not any(a.is_deleted() for a in live.values())
    assert eng.pool_steps_donated == programs
    assert eng.pool_steps_copied == 0

    # the live pools are the old ones plus the step's rows: every row it
    # wrote differs from the (zero) row that was there, and nothing
    # outside the slot's own rows or pages moved (a prefill also writes
    # the bucket's masked pad rows inside them)
    wrote = _rows_written(cache, slot, positions)
    own = np.zeros_like(wrote)
    table = cache.block_tables[slot]
    own[table[table < cache.spec.num_pages]] = True
    assert not (wrote & ~own).any()
    for (name, g), arr in live.items():
        new, old = np.asarray(arr), before[(name, g)]
        assert new.shape == old.shape and new.dtype == old.dtype
        if name in ("k", "v"):
            # [pages, page_size, heads * head_dim]
            changed = np.any(new != old, axis=2)
            assert changed[wrote].all(), (name, g)
            assert not changed[~own].any(), (name, g)
        elif name.startswith("state."):
            # [max_seqs, ...]: the slot's row moved, and no other
            assert np.any(new[slot] != old[slot]), (name, g)
            others = np.arange(SLOTS) != slot
            assert np.array_equal(new[others], old[others]), (name, g)
        else:  # int8 scale pools [pages, heads]: claimed for written pages
            pages = wrote.any(axis=1)
            assert (new[pages] > 0).all(), (name, g)
            assert np.array_equal(new[~own.any(axis=1)], old[~own.any(axis=1)])


@pytest.mark.parametrize("kernel", ["dense", "pallas"])
def test_counters_reach_scheduler_stats_and_gauges(lm, kernel):
    sched, eng, _ = build_scheduler(
        lm,
        ServeConfig(max_seqs=SLOTS, max_seq_len=32, decode_kernel=kernel),
        telemetry=Telemetry(),
    )
    done = sched.run(
        [Request(rid=i, prompt=[2 + i, 3, 5], max_new_tokens=4)
         for i in range(3)]
    )
    assert all(r.ok for r in done)
    st = sched.stats
    assert st.prefill_batches and st.decode_steps
    assert st.pool_steps_donated == st.prefill_batches + st.decode_steps
    assert st.pool_steps_copied == 0
    assert st.pool_steps_donated == eng.pool_steps_donated
    for name in ("pool_steps_donated", "pool_steps_copied"):
        gauge = st._registry.get("serve_stats_" + name)
        assert gauge is not None and gauge.value == getattr(st, name)


@pytest.mark.parametrize(
    "serve_kw",
    [
        pytest.param({"kv_page_size": 32}, id="one_page"),
        pytest.param({"serve_async": True}, id="paged-async"),
        pytest.param({"spec_draft": "ngram", "spec_k": 3}, id="paged-spec"),
        pytest.param({"token_budget": 10, "chunk_size": 4,
                      "decode_kernel": "dense"}, id="paged-chunked"),
    ],
)
def test_parameters_and_adapter_pools_are_never_consumed(lm, serve_kw):
    sched, eng, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=SLOTS, max_seq_len=32, adapters=2,
                    adapter_rank=4, **serve_kw),
    )
    for aid in (0, 1):
        eng.adapters.load(
            aid, make_lora_weights(eng.adapters.spec, 4, seed=aid)
        )
    kept = jax.tree_util.tree_leaves(
        (sched.params, eng.adapters.device_pools)
    )
    done = sched.run(
        [Request(rid=i, prompt=[2 + i, 3, 5, 7, 1], max_new_tokens=5,
                 adapter_id=i % 3 - 1)
         for i in range(4)]
    )
    assert all(r.ok for r in done)
    assert eng.pool_steps_donated > 0 and eng.pool_steps_copied == 0
    assert not any(leaf.is_deleted() for leaf in kept)
    assert not any(a.is_deleted() for a in _pool_leaves(cache).values())
