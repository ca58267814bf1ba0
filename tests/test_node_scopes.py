"""Device time carries the PCG node's name.

(a) Every instruction a step program traced itself sits under a node's
scope (`kind:name`, `Executor.lower_node`), `loss`, `update` or a
`step.*`: the train step on one device and on a 2x2 mesh, the sparse
step, the pipelined executor, and the engine's five step programs of a
dense, an OLMoE-like, a latent, a looped and a recurrent toy model.
(b) The reduction `utils.profiling.fold_step` as a pure function of
(events, HLO text).
(c) A recorded trace of a toy train step on a TPU v5e with its compiled
text (`tests/data/node_scopes_v5e.*`; `python3 tests/test_node_scopes.py`
on the chip records it), folded to known rows: CPU traces have no
`XLA Ops` line, so the chip's shape has to be a fixture.
"""

import os
import re
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.lib.scopes import _scope_in  # noqa: E402
from flexflow_tpu import (  # noqa: E402
    ActiMode,
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.core.types import OperatorType  # noqa: E402
from flexflow_tpu.utils import profiling  # noqa: E402
from flexflow_tpu.utils.profiling import DeviceEvents, fold_step, scope_of  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- (a) every traced instruction has a scope ----------------------------------


def _op_names(text):
    """The `op_name`s the program itself traced: those under `jit(`."""
    return [
        part
        for name in re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text)
        for part in name.split(";")
        if part.startswith("jit(")
    ]


def _feeding(graph, guid):
    """The nodes whose outputs reach node `guid`, itself included."""
    reach, todo = set(), [guid]
    while todo:
        g = todo.pop()
        if g not in reach:
            reach.add(g)
            todo.extend(r.guid for r in graph.nodes[g].inputs)
    return reach


def _check_scoped(text, graph=None, backward=False, only=None):
    """`only`: the guids held to a scope of their own (a serving program
    computes what feeds the logits, and a sink beside them is dead code)."""
    names = _op_names(text)
    assert names
    bare = sorted({n for n in names if scope_of(n) is None})
    assert not bare, bare[:10]
    if graph is None:
        return
    seen = {scope_of(n) for n in names}
    for node in graph.nodes.values():
        # a node without weights (a layout op, a reshape) may leave no
        # instruction of its own in the compiled program
        if not node.inputs or not node.weight_shapes:
            continue
        if only is not None and node.guid not in only:
            continue
        scope = f"{node.op_type.name.lower()}:{node.name}"
        assert (scope, "forward") in seen, scope
        if backward:
            assert (scope, "backward") in seen, scope


def _train_text(model, batch):
    ex = model.executor
    return (
        ex.train_step()
        .lower(
            model.params, model.opt_state, ex.shard_batch(batch),
            jax.random.PRNGKey(0),
        )
        .compile()
        .as_text()
    )


def _transformer(strategy=None, devices=None):
    cfg = FFConfig(batch_size=8, seed=3)
    model = FFModel(cfg)
    t = model.create_tensor([8, 16, 32], name="x")
    for _ in range(2):
        t = model.multihead_attention(t, t, t, 32, 4)
        t = model.dense(t, 32, activation=ActiMode.RELU, use_bias=False)
    model.dense(t, 1, use_bias=False)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[], strategy=strategy, devices=devices,
    )
    rng = np.random.RandomState(0)
    batch = {
        "x": rng.randn(8, 16, 32).astype(np.float32),
        "label": rng.randn(8, 16, 1).astype(np.float32),
    }
    return model, batch


def _mesh_2x2():
    from flexflow_tpu.parallel.strategy import Strategy, annotate_input_batch
    from flexflow_tpu.runtime.executor import MeshConfig
    from flexflow_tpu.search.rewrites import find_tp_sites

    def apply(g):
        annotate_input_batch(g, 2)
        for site in find_tp_sites(g):
            if site.divisible_by(g, 2):
                site.apply(g, 2, 1)

    return Strategy(MeshConfig(("data", "model"), (2, 2)), apply, name="dp2xtp2")


@pytest.mark.parametrize("mesh", ["one_device", "mesh_2x2"])
def test_train_step_is_scoped(mesh):
    if mesh == "one_device":
        model, batch = _transformer(devices=jax.devices()[:1])
    else:
        model, batch = _transformer(strategy=_mesh_2x2())
        assert model.executor.mesh.shape == {"data": 2, "model": 2}
    text = _train_text(model, batch)
    _check_scoped(text, model.graph, backward=True)
    seen = {scope_of(n) for n in _op_names(text)}
    assert ("loss", "forward") in seen and ("loss", "backward") in seen
    assert ("update", "other") in seen


def test_sparse_step_is_scoped():
    from tests.test_sparse_embedding import AggrMode, batch_for, build

    model = build(aggr=AggrMode.SUM, sparse=True)
    assert model.executor._sparse_embedding_guids()
    inputs, y = batch_for(AggrMode.SUM)
    text = _train_text(model, {**inputs, "label": y})
    _check_scoped(text)
    seen = {scope_of(n) for n in _op_names(text)}
    table = next(
        n for n in model.graph.nodes.values()
        if n.op_type == OperatorType.EMBEDDING
    )
    # the lookup outside the grad closure is the node's, the row update
    # `update`'s: the table has no backward of its own
    assert (f"embedding:{table.name}", "forward") in seen
    assert ("update", "other") in seen and ("loss", "backward") in seen


def test_pipelined_step_is_scoped():
    from flexflow_tpu.parallel.strategy import Strategy
    from flexflow_tpu.runtime.executor import MeshConfig
    from tests.test_pipeline_compile import build, mlp_batch, pipe_strategy

    single = build(Strategy(MeshConfig(("data",), (1,)), None))
    piped = build(pipe_strategy(single._prestrategy_graph, dp=2, pp=4))
    x, y = mlp_batch()
    text = _train_text(piped, {"x": x, "label": y})
    _check_scoped(text)
    seen = {scope_of(n) for n in _op_names(text)}
    # the trunk's blocks run under the template block's name, the head
    # outside the pipeline under its own
    assert ("linear:head", "forward") in seen and ("linear:head", "backward") in seen
    trunk = {s for s, _ in seen if s.startswith("linear:d")}
    assert trunk and ("update", "other") in seen


# the engine's five step programs, of five toy models

PROGRAMS = ("prefill", "decode", "verify", "verify_tree", "chunk")
REFUSING_PROGRAMS = ("prefill", "decode")  # the rest are refused
# a model with latent attention or recurrent layers is served by these alone
REFUSING = ("latent", "kimi_linear")


def _dense_model():
    from flexflow_tpu.models import build_decoder_lm

    cfg = FFConfig(batch_size=4, seed=7)
    model = FFModel(cfg)
    tok = model.create_tensor([4, 32], dtype=DataType.INT32, name="tokens")
    build_decoder_lm(model, tok, vocab_size=211)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
    )
    return model


def _drive(model, refusing):
    """{program: compiled text} of every step program the engine has for
    the model, each dispatched once."""
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    def one_hot(values, slot, dtype=np.int32):
        out = np.zeros((4,) + np.shape(values), dtype)
        out[slot] = values
        return out

    _, engine, cache = build_scheduler(model, ServeConfig(max_seqs=4, max_seq_len=32))
    with profiling.step_program_texts(engine) as texts:
        prompt = [(7 * j * j + 3 * j) % 210 + 1 for j in range(9)]
        slot = cache.alloc(len(prompt), len(prompt) + 8)
        nxt, _ = engine.prefill(model.params, [prompt], [slot])
        engine.decode(
            model.params, one_hot(int(nxt[0]), slot), one_hot(True, slot, bool)
        )
        if not refusing:
            draft = [int(nxt[0]), 17, 23, 5]
            engine.verify(
                model.params, one_hot(draft, slot), one_hot(len(draft), slot)
            )
            rows = [int(nxt[0]), 17, 23, 5, 40]
            table = np.tile(np.arange(-1, 4, dtype=np.int32), (4, 1))
            table[slot] = [-1, 0, 0, 1, 2]
            engine.verify_tree(
                model.params, one_hot(rows, slot), one_hot(len(rows), slot), table
            )
            other = cache.alloc(0, 16)
            engine.prefill_chunk(
                model.params, one_hot([3, 4, 5, 6], other), one_hot(4, other)
            )
    return {
        re.fullmatch(r"jit__(\w+?)_impl_paged \(.*", key).group(1): text
        for key, text in texts.items()
    }


@pytest.fixture(scope="module")
def engine_texts():
    from tests import test_deepseek_v3, test_kimi_linear, test_olmoe, test_ouro

    made = {}

    def texts(kind):
        if kind not in made:
            model = {
                "dense": _dense_model,
                "olmoe": test_olmoe._model,
                "latent": test_deepseek_v3._model,
                "ouro": test_ouro._model,
                "kimi_linear": test_kimi_linear._model,
            }[kind]()
            made[kind] = (model, _drive(model, refusing=kind in REFUSING))
        return made[kind]

    return texts


@pytest.mark.parametrize(
    "kind,program",
    [(k, p) for k in ("dense", "olmoe", "ouro") for p in PROGRAMS]
    + [(k, p) for k in REFUSING for p in REFUSING_PROGRAMS],
)
def test_engine_step_programs_are_scoped(engine_texts, kind, program):
    model, texts = engine_texts(kind)
    assert set(texts) == set(REFUSING_PROGRAMS if kind in REFUSING else PROGRAMS)
    _check_scoped(
        texts[program], model.graph,
        only=_feeding(model.graph, model.executor.logits_ref.guid),
    )
    seen = {s for s, _ in map(scope_of, _op_names(texts[program]))}
    assert "step.unpack" in seen
    if program in ("prefill", "decode", "chunk"):
        assert "step.pick" in seen


@pytest.mark.parametrize(
    "kind,scopes",
    [
        ("olmoe", ("moe.route", "moe.sort", "moe.experts", "moe.combine")),
        ("latent", ("moe.experts", "moe.shared", "mla.project", "mla.absorb",
                    "mla.attend", "mla.out")),
    ],
)
def test_the_benchmarks_reader_still_finds_the_inner_scopes(
    engine_texts, kind, scopes
):
    """`benchmarks/lib/scopes.py` looks for `/moe.experts/` in an
    `op_name`: the node's scope in front of it changes nothing."""
    _, texts = engine_texts(kind)
    for program in ("prefill", "decode"):
        names = _op_names(texts[program])
        for scope in scopes:
            if (program, scope) == ("prefill", "mla.absorb"):
                continue  # the prefill attends decompressed
            hits = [n for n in names if _scope_in(n, (scope,), {}) == scope]
            assert hits, (program, scope)
            # and each of them under a node's scope
            assert all(":" in scope_of(n)[0] for n in hits), hits[:3]


def test_the_retired_attention_scopes_are_gone(engine_texts):
    _, texts = engine_texts("olmoe")
    assert not [n for n in _op_names(texts["decode"]) if "/attn." in n]


# -- (b) the reduction, as a pure function -------------------------------------

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8], p2: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = f32[8,8]{1,0} parameter(2)
  %convolution.1 = f32[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(linear:dense_2))/dot_general" stack_frame_id=3}
  %multiply.1 = f32[8,8]{1,0} multiply(%convolution.1, %convolution.1), metadata={op_name="jit(step)/update/mul"}
  ROOT %subtract.1 = f32[8,8]{1,0} subtract(%p2, %multiply.1), metadata={op_name="jit(step)/update/sub"}
}

%fused_computation.2 (q0: f32[8,8]) -> f32[8,8] {
  %q0 = f32[8,8]{1,0} parameter(0)
  ROOT %add.7 = f32[8,8]{1,0} add(%q0, %q0), metadata={op_name="jit(step)/jvp(linear:dense_2)/add"}
}

%body (c: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %c = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte.1 = f32[8,8]{1,0} get-tuple-element(%c), index=1
  %fusion.9 = f32[8,8]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(multihead_attention:mha_1)/while/body/add"}
  ROOT %tuple.2 = (s32[], f32[8,8]{1,0}) tuple(%gte.0, %fusion.9)
}

%cond (c.1: (s32[], f32[8,8])) -> pred[] {
  %c.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt = pred[] compare(%c.1, %c.1), direction=LT
}

ENTRY %main.1 (w: f32[8,8], x: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %w = f32[8,8]{1,0:T(8,128)} parameter(0), metadata={op_name="params[1][0]"}
  %x = f32[8,8]{1,0} parameter(1), metadata={op_name="batch[\\'x\\']"}
  %copy-start.3 = (f32[8,8]{1,0:T(8,128)S(1)}, f32[8,8]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%w)
  %copy-done.3 = f32[8,8]{1,0:T(8,128)S(1)} copy-done(%copy-start.3)
  %fusion.2 = f32[8,8]{1,0} fusion(%x, %copy-done.3), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(linear:dense_2)/add"}
  %while.1 = (s32[], f32[8,8]{1,0}) while(%fusion.2), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(multihead_attention:mha_1)/while"}
  %all-reduce-start.1 = f32[8,8]{1,0} all-reduce-start(%fusion.2), to_apply=%cond, metadata={op_name="jit(step)/transpose(jvp(linear:dense_2))/dot_general"}
  %all-reduce-done.1 = f32[8,8]{1,0} all-reduce-done(%all-reduce-start.1)
  %fusion.1 = f32[8,8]{1,0} fusion(%x, %all-reduce-done.1, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/update/sub"}
  %square.1 = f32[8,8]{1,0} multiply(%fusion.2, %fusion.2), metadata={op_name="jit(step)/jvp(loss)/square"}
  %copy.9 = f32[8,8]{0,1} copy(%x)
  ROOT %tuple.1 = (f32[8,8]{1,0}, f32[8,8]{0,1}) tuple(%fusion.1, %copy.9)
}
"""


def _events(*spans, module="jit_step(123)"):
    """One chip's events from (instruction, start, end) in nanoseconds;
    the program runs from 0 to 1,000."""
    return DeviceEvents(
        modules=[(module, 0.0, 1000.0), ("jit_other(9)", 2000.0, 3000.0)],
        ops=[(f"%{name} = f32[8,8]{{1,0}} op(...)", s, e) for name, s, e in spans]
        + [("%fusion.1 = f32[] fusion()", 2000.0, 2500.0)],  # another program's
    )


def _rows(profile):
    return {r.scope: r for r in profile.rows}


def test_scope_of_reads_the_phase_from_the_wrapper():
    assert scope_of("jit(step)/jvp(linear:dense_2)/dot_general") == (
        "linear:dense_2", "forward")
    assert scope_of("jit(step)/transpose(jvp(linear:dense_2))/dot_general") == (
        "linear:dense_2", "backward")
    assert scope_of("jit(_decode)/sparse_moe:moe_1/moe.experts/mul") == (
        "sparse_moe:moe_1", "forward")
    assert scope_of("jit(step)/transpose(jvp(while))/body/linear:d0/mul") == (
        "linear:d0", "backward")
    assert scope_of("jit(step)/update/sub") == ("update", "other")
    assert scope_of("jit(step)/transpose(jvp(loss))/mul;jit(step)/x") == (
        "loss", "backward")
    # a node's scope wins over the loop's it sits in
    assert scope_of("jit(f)/step.pipeline/while/body/closed_call/linear:d/add") == (
        "linear:d", "forward")
    assert scope_of("jit(f)/step.pipeline/while/body/add") == (
        "step.pipeline", "other")
    assert scope_of("jit(step)/jvp()/sharding_constraint") is None
    assert scope_of("params[101][0]") is None


def test_a_fusion_is_charged_to_its_heaviest_instruction_not_its_root():
    """The weight gradient with the SGD update fused in as its epilogue:
    the root is `update`'s subtract, the time is the contraction's."""
    rows = _rows(fold_step([_events(("fusion.1", 100, 400))], HLO))
    assert set(rows) == {"linear:dense_2"}
    row = rows["linear:dense_2"]
    assert row.backward_ms == pytest.approx(300e-6)
    assert row.forward_ms == 0 and row.other_ms == 0
    assert row.mixed_ms == pytest.approx(300e-6)  # the body holds `update` too
    assert (row.kind, row.family) == ("linear", "dense")


def test_a_compiler_made_copy_is_charged_to_its_first_scoped_user():
    """Two hops: copy-start -> copy-done -> the fusion that reads it."""
    profile = fold_step(
        [_events(("copy-start.3", 0, 10), ("copy-done.3", 10, 110),
                 ("fusion.2", 110, 160))],
        HLO,
    )
    row = _rows(profile)["linear:dense_2"]
    assert row.forward_ms == pytest.approx(160e-6)
    assert row.charged_ms == pytest.approx(110e-6)
    assert row.mixed_ms == 0
    assert profile.heaviest[0] == (
        "copy-done.3", "linear:dense_2", "forward", pytest.approx(100e-6))


def test_what_has_neither_scope_nor_scoped_neighbour_is_one_row():
    rows = _rows(fold_step(
        [_events(("copy.9", 0, 40), ("not-in-the-text.4", 50, 60))], HLO
    ))
    assert set(rows) == {profiling.UNSCOPED}
    assert rows[profiling.UNSCOPED].other_ms == pytest.approx(50e-6)
    assert rows[profiling.UNSCOPED].charged_ms == 0


def test_a_while_counts_what_its_body_leaves():
    rows = _rows(fold_step(
        [_events(("while.1", 100, 500), ("fusion.9", 120, 220),
                 ("fusion.9", 300, 400))],
        HLO,
    ))
    # the body's fusion is the attention node's by its own op_name, and
    # so is the loop: 400 in all, of which the loop itself kept 200
    row = rows["multihead_attention:mha_1"]
    assert row.forward_ms == pytest.approx(400e-6)
    assert row.family == "attention"


def test_collectives_keep_their_scope_and_a_column():
    rows = _rows(fold_step(
        [_events(("all-reduce-start.1", 0, 30), ("all-reduce-done.1", 30, 100))],
        HLO,
    ))
    row = rows["linear:dense_2"]
    assert row.backward_ms == pytest.approx(100e-6)
    assert row.collective_ms == pytest.approx(100e-6)
    assert row.charged_ms == pytest.approx(70e-6)  # the done, through its start


def test_the_accounting_line_and_the_mean_over_chips_and_executions():
    one = _events(("fusion.2", 0, 500), ("square.1", 500, 990))
    two = DeviceEvents(
        modules=[("jit_step(123)", 0.0, 1000.0), ("jit_step(123)", 5000.0, 6000.0)],
        ops=[("%fusion.2 = f32[] fusion()", 0.0, 250.0),
             ("%fusion.2 = f32[] fusion()", 5000.0, 5250.0),
             ("%square.1 = f32[] multiply()", 5500.0, 5990.0),
             ("%square.1 = f32[] multiply()", 7000.0, 7990.0)],  # outside
    )
    profile = fold_step([one], HLO)
    assert (profile.program, profile.chips, profile.executions) == ("jit_step", 1, 1)
    assert profile.device_ms == pytest.approx(1000e-6)
    assert profile.accounted == pytest.approx(0.99)
    assert _rows(profile)["loss"].forward_ms == pytest.approx(490e-6)
    both = fold_step([one, two], HLO)
    assert (both.chips, both.executions) == (2, 1)  # 3 executions on 2 chips
    assert both.device_ms == pytest.approx(1000e-6)
    # (500 + 250 + 250) / 3 executions
    assert _rows(both)["linear:dense_2"].forward_ms == pytest.approx(1000e-6 / 3)
    families = {r.scope: r for r in both.by_family()}
    assert set(families) == {"dense", "loss"}
    assert "rows hold" in both.table() and "dense" in both.table(by_family=True)


def test_one_executable_has_to_be_named_among_several_of_a_name():
    events = DeviceEvents(
        modules=[("jit_step(1)", 0.0, 100.0), ("jit_step(2)", 200.0, 300.0)],
        ops=[("%fusion.2 = f32[] fusion()", 0.0, 50.0),
             ("%fusion.2 = f32[] fusion()", 200.0, 280.0)],
    )
    with pytest.raises(ValueError, match="several executables"):
        fold_step([events], HLO)
    second = fold_step([events], HLO, program="jit_step(2)")
    assert _rows(second)["linear:dense_2"].forward_ms == pytest.approx(80e-6)
    with pytest.raises(profiling.NoDeviceOps, match="no device ops"):
        fold_step([events], HLO, program="jit_absent")


# -- (c) the chip's shape: a recorded v5e trace --------------------------------


def test_a_recorded_v5e_train_step_folds_to_known_rows():
    trace = os.path.join(DATA, "node_scopes_v5e.xplane.pb")
    with open(os.path.join(DATA, "node_scopes_v5e.hlo.txt")) as f:
        text = f.read()
    assert os.path.getsize(trace) + len(text) < 1_000_000
    profile = fold_step(profiling.read_device_events(trace), text)
    assert (profile.program, profile.chips, profile.executions) == ("jit_step", 1, 3)
    rows = _rows(profile)
    assert profile.device_ms == pytest.approx(0.61435, rel=1e-4)
    assert profile.accounted == pytest.approx(0.9941, abs=1e-4)
    assert profiling.UNSCOPED not in rows
    for scope, forward, backward in RECORDED:
        assert rows[scope].forward_ms == pytest.approx(forward, rel=1e-3), scope
        assert rows[scope].backward_ms == pytest.approx(backward, rel=1e-3), scope
    assert rows["update"].other_ms == pytest.approx(RECORDED_UPDATE, rel=1e-3)
    # every weight gradient has its SGD update fused in as the epilogue
    assert rows["linear:dense_2"].mixed_ms == pytest.approx(0.023394, rel=1e-3)
    assert rows["linear:dense_2"].charged_ms < 1e-4
    name, scope, phase, ms = profile.heaviest[0]
    assert (scope, phase) == ("multihead_attention:multihead_attention_1", "backward")
    assert {r.scope for r in profile.by_family()} >= {
        "attention", "dense", "loss", "update"}


#: (scope, forward ms, backward ms) a step of the recorded trace: a
#: 2-layer encoder of 512 x 8 heads on 16 sequences of 256 (my chip run,
#: PR 35, "TPU v5 lite")
RECORDED = (
    ("multihead_attention:multihead_attention_1", 0.080752, 0.185345),
    ("multihead_attention:multihead_attention", 0.078401, 0.114082),
    ("linear:dense", 0.017873, 0.023267),
    ("linear:dense_2", 0.014248, 0.02342),
    ("linear:dense_3", 0.011181, 0.023901),
    ("linear:dense_1", 0.011522, 0.023198),
    ("linear:dense_4", 0.0, 0.001585),
    ("loss", 0.000377, 0.0),
)
RECORDED_UPDATE = 0.00157


def _record(out_dir):
    """On the chip: a 2-layer toy through `profile_step`, the profile
    and the compiled text (less its `backend_config`s) into `out_dir`."""
    import glob
    import shutil

    from examples.transformer import build_transformer, synthetic_batch

    model, _ = build_transformer(
        batch_size=16, seq_len=256, hidden=512, num_heads=8, num_layers=2,
        devices=jax.devices()[:1],
    )
    log_dir = os.path.join(out_dir, "node_scopes_trace")
    profile = profiling.profile_step(
        model, synthetic_batch(16, 256, 512), steps=3, log_dir=log_dir
    )
    (trace,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(trace, os.path.join(out_dir, "node_scopes_v5e.xplane.pb"))
    with open(os.path.join(log_dir, "jit_step.hlo.txt")) as f:
        text = re.sub(r", backend_config=\{.*\}$", "", f.read(), flags=re.M)
    with open(os.path.join(out_dir, "node_scopes_v5e.hlo.txt"), "w") as f:
        f.write(text)
    for r in profile.rows:
        print((r.scope, round(r.forward_ms, 6), round(r.backward_ms, 6), round(r.other_ms, 6)))


if __name__ == "__main__":
    _record(os.path.join(os.path.dirname(DATA), "..", "chiprun_out"))
