"""What a decode step exchanges with the host (ISSUE 31).

The scheduler's reconcile reads ONE small int32 vector (the picked tokens,
a finite flag per slot, the expert layers' counts), whose copy starts at
dispatch; the logits stay on the device for who asks (`engine.decode()`);
`_dispatch` forces a program's first run only. Held here, on the CPU, for
the five served model kinds (a dense decoder, the OLMoE block, the
`deepseek_v3` block, Ouro's looped stack, Kimi-Linear's recurrent layers
with their per-slot state among the donated pools) and both loops:

* a scripted run's token streams equal what stepping `engine.decode()` by
  hand gives;
* a NaN planted in one slot's row fails that request and no other;
* `engine.decode()` returns the logits of `step.device_logits`, and a
  reconcile reads no more than `8 * max_seqs` bytes plus the counts;
* a program's second dispatch does not block, a first-dispatch failure
  still raises `KernelCompileError`, and a fault that surfaces at the
  reconcile's read is `PoolsLostError` for every running request.
"""

import jax
import numpy as np
import pytest

from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.core.types import OperatorType
from flexflow_tpu.models import (
    build_decoder_lm,
    build_deepseek_v3,
    build_kimi_linear,
    build_olmoe,
    build_ouro,
)
from flexflow_tpu.serving import Request, ServeConfig, build_scheduler
from flexflow_tpu.serving.engine import KernelCompileError, PoolsLostError
from flexflow_tpu.serving.faults import FaultInjector, FaultPlan
from flexflow_tpu.serving.scheduler import RequestStatus

pytestmark = pytest.mark.serving

VOCAB, SLOTS, SEQ, MAX_NEW = 97, 4, 32, 6

BUILDERS = {
    "dense": lambda m, tok: build_decoder_lm(
        m, tok, vocab_size=VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=64,
    ),
    "olmoe": lambda m, tok: build_olmoe(
        m, tok, vocab_size=VOCAB, hidden=32, num_heads=4, num_layers=2,
        expert_hidden=16, num_experts=4, experts_per_token=2,
    ),
    "deepseek_v3": lambda m, tok: build_deepseek_v3(
        m, tok, experts_held=(0, 2), vocab_size=VOCAB, hidden=32,
        num_heads=4, num_layers=2, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dense_hidden=48, dense_layers=1,
        expert_hidden=16, num_experts=4, experts_per_token=2,
        shared_experts=1, routed_scale=2.0, rope_theta=1e4, eps=1e-6,
    ),
    # two layers run twice over one set of weights; returns its head
    "ouro": lambda m, tok: build_ouro(
        m, tok, vocab_size=VOCAB, hidden=32, num_heads=4, num_layers=2,
        ff_dim=48, loops=2, rope_theta=1e4, eps=1e-6,
    ),
    # two recurrent layers round one of latent attention
    "kimi_linear": lambda m, tok: build_kimi_linear(
        m, tok, experts_held=(0, 2), vocab_size=VOCAB, hidden=32,
        num_heads=4, num_layers=3, kda_layers=(1, 3), full_attn_layers=(2,),
        kda_head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, dense_hidden=48, dense_layers=1,
        expert_hidden=16, num_experts=4, experts_per_token=2,
        shared_experts=1, routed_scale=2.0, eps=1e-6, kda_chunk=8,
    ),
}
#: int32 counts a decode program returns beside tokens and flags
COUNTS = {"dense": 0, "olmoe": 2, "deepseek_v3": 3, "ouro": 0, "kimi_linear": 3}
#: expert layers of the toy
EXPERT_LAYERS = {"dense": 0, "olmoe": 2, "deepseek_v3": 1, "ouro": 0, "kimi_linear": 2}


@pytest.fixture(scope="module", params=list(BUILDERS))
def served(request):
    cfg = FFConfig(batch_size=SLOTS, seed=3)
    model = FFModel(cfg)
    tok = model.create_tensor([SLOTS, SEQ], dtype=DataType.INT32, name="tokens")
    out = BUILDERS[request.param](model, tok)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
        # Ouro's exit gates are sinks beside its head
        logits=out if request.param == "ouro" else None,
    )
    return request.param, model


#: five requests over four slots: the fifth waits for a slot
SCRIPT = ([1, 2, 3], [4, 5, 6, 7, 8], [9, 8], [11, 12, 13, 14], [21, 22])
LOOPS = pytest.mark.parametrize("serve_async", [False, True], ids=["sync", "async"])


def _requests():
    return [
        Request(rid=i, prompt=list(p), max_new_tokens=MAX_NEW)
        for i, p in enumerate(SCRIPT)
    ]


def _build(model, **kw):
    injector = kw.pop("injector", None)
    serve = ServeConfig(max_seqs=SLOTS, max_seq_len=SEQ, **kw)
    return build_scheduler(model, serve, injector=injector)


def _by_hand(model):
    """Each prompt alone: a prefill, then `engine.decode()` fed its own
    pick, greedy. Also holds the returned logits to the pick."""
    _, engine, cache = _build(model)
    streams = {}
    for rid, prompt in enumerate(SCRIPT):
        slot = cache.alloc(len(prompt), len(prompt) + MAX_NEW)
        nxt, _ = engine.prefill(model.params, [list(prompt)], [slot])
        out = [int(nxt[0])]
        active = np.zeros(SLOTS, dtype=bool)
        active[slot] = True
        while len(out) < MAX_NEW:
            tokens = np.zeros(SLOTS, dtype=np.int32)
            tokens[slot] = out[-1]
            nxt, logits = engine.decode(model.params, tokens, active)
            assert int(np.argmax(logits[slot])) == int(nxt[slot])
            out.append(int(nxt[slot]))
        streams[rid] = out
        cache.free(slot)
    return streams


@LOOPS
def test_scripted_streams_equal_hand_stepped_decode(served, serve_async):
    _, model = served
    sched, engine, _ = _build(model, serve_async=serve_async)
    done = sched.run(_requests())
    assert all(r.ok for r in done)
    assert {r.rid: list(r.generated) for r in done} == _by_hand(model)
    assert engine.kernel_fallbacks == 0


@LOOPS
def test_nan_in_one_slots_row_fails_that_request_only(served, serve_async):
    _, model = served
    clean = _build(model, serve_async=serve_async)[0].run(_requests())
    base = {r.rid: list(r.generated) for r in clean}
    inj = FaultInjector(FaultPlan(nan_iters={3: [1]}))
    sched, _, _ = _build(model, serve_async=serve_async, injector=inj)
    done = {r.rid: r for r in sched.run(_requests())}
    assert inj.summary() == {"nan": 1}
    failed = [rid for rid, r in done.items() if not r.ok]
    assert len(failed) == 1
    assert done[failed[0]].status == RequestStatus.FAILED
    assert "non-finite logits" in done[failed[0]].error
    for rid, r in done.items():
        if rid != failed[0]:
            assert list(r.generated) == base[rid]


def test_a_row_the_model_made_non_finite_fails_its_request_only(served):
    """No injector: the flag is the program's own `isfinite` over the row.
    Slot 1's last token is given an embedding of NaN."""
    _, model = served
    _, engine, cache = _build(model)
    prompts = [[1, 2, 3], [4, 5, 6]]
    slots = [cache.alloc(3, 3 + MAX_NEW) for _ in prompts]
    engine.prefill(model.params, prompts, slots)
    tokens = np.zeros(SLOTS, dtype=np.int32)
    active = np.zeros(SLOTS, dtype=bool)
    tokens[slots[0]], tokens[slots[1]] = 7, 8
    active[slots] = True
    # poison token 8's embedding row, in a copy of the parameters
    (embed,) = [
        n.guid for n in model.graph.nodes.values()
        if n.op_type == OperatorType.EMBEDDING
    ]
    params = dict(model.params)
    table = params[embed][0]
    assert table.shape == (VOCAB, 32)
    params[embed] = [table.at[8].set(np.nan), *params[embed][1:]]
    step = engine.decode_dispatch(params, tokens, active)
    nxt, finite = engine.decode_reconcile(step)
    assert finite[slots[0]] and not finite[slots[1]]
    logits = np.asarray(step.device_logits)
    assert np.isfinite(logits[slots[0]]).all()
    assert not np.isfinite(logits[slots[1]]).all()


def test_decode_returns_the_steps_logits_and_reconcile_reads_bytes(served):
    kind, model = served
    _, engine, cache = _build(model)
    slot = cache.alloc(3, 3 + MAX_NEW)
    nxt, _ = engine.prefill(model.params, [[1, 2, 3]], [slot])
    tokens = np.zeros(SLOTS, dtype=np.int32)
    active = np.zeros(SLOTS, dtype=bool)
    tokens[slot], active[slot] = int(nxt[0]), True
    # the two halves: the reconcile reads one small vector, once
    step = engine.decode_dispatch(model.params, tokens, active)
    syncs, nbytes = engine.device_syncs, engine.readback_bytes
    nxt, finite = engine.decode_reconcile(step)
    assert engine.device_syncs - syncs == 1
    assert engine.readback_bytes - nbytes == 8 * SLOTS + 4 * COUNTS[kind]
    assert engine.readback_bytes - nbytes < 200
    assert np.array_equal(nxt, np.asarray(step.device_next))
    assert finite.all()
    kept = np.asarray(step.device_logits)
    assert kept.shape == (SLOTS, VOCAB)
    # the public call still hands back the logits: it reads the step's
    steps = []
    dispatch = engine.decode_dispatch

    def recorded(*a, **k):
        steps.append(dispatch(*a, **k))
        return steps[-1]

    engine.decode_dispatch = recorded
    tokens[slot] = int(nxt[slot])
    nbytes = engine.readback_bytes
    nxt2, logits = engine.decode(model.params, tokens, active)
    assert logits.shape == (SLOTS, VOCAB) and logits.dtype == np.float32
    assert np.array_equal(logits, np.asarray(steps[-1].device_logits))
    assert int(np.argmax(logits[slot])) == int(nxt2[slot])
    assert not np.array_equal(logits[slot], kept[slot])
    assert engine.readback_bytes - nbytes == (
        8 * SLOTS + 4 * COUNTS[kind] + 4 * SLOTS * VOCAB
    )


def test_counts_ride_the_one_readback(served):
    kind, model = served
    sched, engine, _ = _build(model)
    sched.run(_requests())
    st = sched.stats
    layers = EXPERT_LAYERS[kind]
    if not layers:
        assert st.moe_rows_decode == 0
    else:
        # every busy slot's row goes to two experts in each expert layer;
        # a layer that holds a share leaves some of them to the others
        rows = st.moe_rows_decode + st.moe_rows_absent_decode
        assert rows >= st.busy_slot_steps * 2 * layers
        assert st.moe_experts_touched_decode > 0
    # a prefill reads its tokens, its last logits and its counts; a decode
    # step one vector; once, the decode program's forced first run
    per_prefill = 2 + bool(COUNTS[kind])
    assert st.device_syncs == per_prefill * st.prefill_batches + st.decode_steps + 1


def test_only_a_programs_first_dispatch_blocks(served):
    _, model = served
    _, engine, cache = _build(model)
    slot = cache.alloc(3, 3 + MAX_NEW)
    engine.prefill(model.params, [[1, 2, 3]], [slot])
    tokens = np.zeros(SLOTS, dtype=np.int32)
    active = np.zeros(SLOTS, dtype=bool)
    active[slot] = True
    before = engine.device_syncs
    engine.decode_reconcile(engine.decode_dispatch(model.params, tokens, active))
    assert engine.device_syncs - before == 2  # the forced first run, the read
    before = engine.device_syncs
    step = engine.decode_dispatch(model.params, tokens, active)
    assert engine.device_syncs == before  # enqueued, nothing waited for
    engine.decode_reconcile(step)
    assert engine.device_syncs - before == 1


def test_first_dispatch_failure_is_a_compile_error(served):
    _, model = served
    _, engine, cache = _build(model)
    slot = cache.alloc(3, 3 + MAX_NEW)
    engine.prefill(model.params, [[1, 2, 3]], [slot])

    def refuse(*args):
        raise NotImplementedError("Mosaic failed to compile TPU kernel")

    engine._decode_jit = refuse
    active = np.zeros(SLOTS, dtype=bool)
    active[slot] = True
    with pytest.raises(KernelCompileError, match="Mosaic failed to compile"):
        engine.decode_dispatch(model.params, np.zeros(SLOTS, np.int32), active)
    assert engine.kernel_fallbacks == 0


class _Halted:
    """A device value whose read raises, as a program that failed on the
    device does when its output is brought to the host."""

    def __array__(self, *a, **k):
        raise RuntimeError("device halted")


@LOOPS
def test_fault_at_the_reconciles_read_is_pools_lost_for_all(served, serve_async):
    _, model = served
    sched, engine, _ = _build(model, serve_async=serve_async)
    dispatch = engine.decode_dispatch
    calls = {"n": 0}

    def third_halts(*a, **k):
        step = dispatch(*a, **k)
        calls["n"] += 1
        if calls["n"] == 3:
            step.device_readback = _Halted()
        return step

    engine.decode_dispatch = third_halts
    done = sched.run(_requests())
    assert len(done) == len(SCRIPT)
    lost = [r for r in done if r.status == RequestStatus.FAILED]
    assert lost and all("PoolsLostError" in r.error for r in lost)
    assert all("device halted" in r.error for r in lost)
    assert sched.stats.step_faults >= 1
    # the direct call names it too
    _, engine, cache = _build(model)
    slot = cache.alloc(3, 3 + MAX_NEW)
    engine.prefill(model.params, [[1, 2, 3]], [slot])
    active = np.zeros(SLOTS, dtype=bool)
    active[slot] = True
    step = engine.decode_dispatch(model.params, np.zeros(SLOTS, np.int32), active)
    step.device_readback = _Halted()
    with pytest.raises(PoolsLostError, match="consumed"):
        engine.decode_reconcile(step)
