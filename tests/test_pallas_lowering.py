"""Every Pallas entry point lowers — and, where the TPU compiler is
loadable, compiles — for TPU from the CPU sandbox.

Interpret-mode parity (tests/test_decode_kernel.py and friends) runs the
kernel bodies but never the TPU lowering's block-shape rules, so a block
the chip refuses used to pass every test here and fail (or silently fall
back to dense) only on the chip. Two layers, both without a chip:

  * `.trace(...).lower(lowering_platforms=("tpu",))` applies the Pallas
    TPU lowering's own checks (last-two-dims tiling, supported
    primitives);
  * an ahead-of-time compile against a v5e topology description
    (libtpu's compiler, no device) runs Mosaic itself: vector layouts,
    VMEM limits, matmul tiles. Skipped when libtpu cannot describe a
    topology here.

Geometries: "smoke" is what the CPU serving tests build (2 layers,
hidden 32, 4 heads), "flagship" the 1024-wide, 16-head, 512-token
decoder chip_smoke.py serves, "gpt2_medium" and "olmoe" the two serving
cells of BENCHMARK.json as they run (16 slots, 1024 positions in pages
of 16, pools of 8,192 and 16,384 tokens; paged entry points only: no
cell serves the contiguous cache)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

from flexflow_tpu.ops.pallas import decode_kernel as dk
from flexflow_tpu.ops.pallas import flash_kernel as fk
from flexflow_tpu.ops.pallas.ring_attention import ring_attention
from tests.conftest import page_geometry

# name: (batch, heads, head_dim, max_len, page sizes, query widths)
GEOMETRIES = {
    "smoke": (4, 4, 8, 32, (8, 16, 32), (1, 4)),
    "flagship": (8, 16, 64, 512, (16, 32, 128), (1, 5, 13)),
    "gpt2_medium": (16, 16, 64, 1024, (16, 32), (1, 4)),
    "olmoe": (16, 16, 128, 1024, (16, 32), (1, 4)),
}
# the serving cells' pools, in tokens (elsewhere: every slot's max_len)
POOL_TOKENS = {"gpt2_medium": 8192, "olmoe": 16384}
# (batch, seq, heads, head_dim) the training path hands the tiled kernel:
# "flagship" is the grid form's band, "train_cells" what a chip of
# `train_ff_b64` and of `train_ff_b256_x4` holds, the whole-sequence form
# (forward and both bodies through the VJP; with the log-sum-exp asked
# for, the grid form at that shape)
FLASH_SHAPES = {
    "smoke": (1, 256, 2, 8),
    "flagship": (8, 2048, 16, 64),
    "train_cells": (64, 512, 16, 64),
}


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _decode_cases(geom):
    """(id, fn, arg shapes) for the nine decode/verify entry points."""
    b, h, d, max_len, page_sizes, widths = GEOMETRIES[geom]
    lens = _sds((b,), jnp.int32)
    cache = _sds((b, max_len, h, d))
    run = dict(interpret=False)
    for w in widths:
        q = _sds((b, w, h, d))
        allowed = _sds((b, w, max_len))
        entry = dk.flash_decode if w == 1 else dk.flash_verify
        if geom not in POOL_TOKENS:
            yield (
                f"{geom}-{entry.__name__}-w{w}",
                functools.partial(entry, **run),
                (q, cache, cache, lens),
            )
            yield (
                f"{geom}-flash_verify_tree-w{w}",
                functools.partial(dk.flash_verify_tree, **run),
                (q, cache, cache, lens, allowed),
            )
        for ps in page_sizes:
            pages = POOL_TOKENS.get(geom, b * max_len) // ps
            tables = _sds((b, max_len // ps), jnp.int32)
            pool = _sds((pages, ps, h, d))
            entry = dk.paged_flash_decode if w == 1 else dk.paged_flash_verify
            yield (
                f"{geom}-{entry.__name__}-ps{ps}-w{w}",
                functools.partial(entry, **run),
                (q, pool, pool, tables, lens),
            )
            yield (
                f"{geom}-paged_flash_verify_tree-ps{ps}-w{w}",
                functools.partial(dk.paged_flash_verify_tree, **run),
                (q, pool, pool, tables, lens, allowed),
            )
            if not dk.supports(w, 0, d, page_size=ps, kv_dtype="int8"):
                continue
            pool8 = _sds((pages, ps, h, d), jnp.int8)
            scale = _sds((pages, h))
            entry = (
                dk.paged_flash_decode_quant
                if w == 1
                else dk.paged_flash_verify_quant
            )
            yield (
                f"{geom}-{entry.__name__}-ps{ps}-w{w}",
                functools.partial(entry, **run),
                (q, pool8, pool8, scale, scale, tables, lens),
            )
            yield (
                f"{geom}-paged_flash_verify_tree_quant-ps{ps}-w{w}",
                functools.partial(dk.paged_flash_verify_tree_quant, **run),
                (q, pool8, pool8, scale, scale, tables, lens, allowed),
            )


def _flash_cases(geom):
    """Training flash kernel: forward, forward with LSE (the ring's
    residual), and forward+backward through the custom VJP."""
    shape = FLASH_SHAPES[geom]
    for dtype in (jnp.float32, jnp.bfloat16):
        x = _sds(shape, dtype)
        name = f"{geom}-flash-{jnp.dtype(dtype).name}"

        def fwd(q, k, v):
            return fk.flash_attention_tpu(
                q, k, v, causal=True, interpret=False
            )

        def fwd_lse(q, k, v):
            return fk.flash_attention_tpu(
                q, k, v, return_lse=True, interpret=False
            )

        def loss(q, k, v):
            return fwd(q, k, v).astype(jnp.float32).sum()

        yield (f"{name}-fwd", fwd, (x, x, x))
        yield (f"{name}-fwd-lse", fwd_lse, (x, x, x))
        yield (f"{name}-fwd-bwd", jax.grad(loss, argnums=(0, 1, 2)), (x, x, x))


CASES = [
    *(c for geom in GEOMETRIES for c in _decode_cases(geom)),
    *(c for geom in FLASH_SHAPES for c in _flash_cases(geom)),
]


def _ids(cases):
    return [c[0] for c in cases]


@pytest.mark.parametrize("case", CASES, ids=_ids(CASES))
def test_lowers_for_tpu(case):
    _, fn, shapes = case
    jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the repo's own backend probes (`jax.default_backend()`, which
    JAX itself never calls through the module attribute) answer as they
    do on the chip, so `interpret=None` and the "on TPU" gates resolve
    the way they will there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _ring(mesh, causal):
    def fn(q, k, v):
        return ring_attention(
            q, k, v, mesh, "seq", causal=causal, use_pallas=True
        )

    return fn


@pytest.mark.parametrize("causal", [False, True])
def test_ring_body_lowers_for_tpu(as_tpu, causal):
    mesh = Mesh(jax.devices()[:4], ("seq",))
    x = _sds((2, 4 * 256, 4, 64), jnp.bfloat16)
    jax.jit(_ring(mesh, causal)).trace(x, x, x).lower(
        lowering_platforms=("tpu",)
    )


# -- Mosaic itself, ahead of time ---------------------------------------------


@functools.lru_cache(maxsize=None)
def _v5e_devices():
    """Four compile-only v5e devices (a 2x2 host), or None when libtpu
    cannot describe a topology on this machine."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception:  # no libtpu here: nothing to compile against
        return None
    return tuple(topo.devices)


def _compile_for(devices, fn, shapes, spec=None):
    if spec is None:
        sharding = SingleDeviceSharding(devices[0])
    else:
        sharding = jax.sharding.NamedSharding(*spec)
    placed = [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
        for s in shapes
    ]
    return (
        jax.jit(fn)
        .trace(*placed)
        .lower(lowering_platforms=("tpu",))
        .compile()
    )


FLAGSHIP = [c for c in CASES if not c[0].startswith("smoke")]


@pytest.mark.parametrize("case", FLAGSHIP, ids=_ids(FLAGSHIP))
def test_mosaic_compiles_flagship(case):
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    _, fn, shapes = case
    _compile_for(devices, fn, shapes)


PAGED_BLOCKS = [
    pytest.param(w, h, d, ps, max_len // ps, itemsize,
                 id=f"{geom}-ps{ps}-w{w}-{itemsize}B")
    for geom, (_, h, d, max_len, page_sizes, widths) in GEOMETRIES.items()
    for ps in page_sizes
    for w in (*widths, dk._MAX_TREE_W, dk._MAX_W)
    for itemsize in ((4, 1) if ps % dk._INT8_SUBLANES == 0 else (4,))
]


@pytest.mark.parametrize("w, h, d, ps, np_seq, itemsize", PAGED_BLOCKS)
def test_paged_block_fits_the_stated_vmem_budget(w, h, d, ps, np_seq, itemsize):
    """The pages a grid step takes follow from shapes; what they need in
    VMEM, by the kernel's own count, is under the budget the code states
    (the compiles above hold Mosaic's count under the chip's limit)."""
    blk = dk.paged_block(w, h, d, ps, np_seq, itemsize)
    assert 1 <= blk.pages <= np_seq and blk.rows == blk.pages * ps
    assert blk.vmem_bytes <= dk._VMEM_BUDGET
    assert h % blk.heads == 0 and w * blk.heads <= max(w, dk._MAX_Q_ROWS)


def test_mosaic_compiles_ring_body(as_tpu):
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    mesh = Mesh(devices, ("seq",))
    x = _sds((2, 4 * 256, 4, 64), jnp.bfloat16)
    _compile_for(
        devices, _ring(mesh, True), (x, x, x),
        spec=(mesh, P(None, "seq", None, None)),
    )


# -- the kernels on a serving mesh --------------------------------------------


def _paged_decode_on_mesh(mesh, head_shard):
    """(fn, arg shapes) for one flagship paged decode attention with the
    pools sharded as ServingPlacement.kv_sharding() shards them."""
    from flexflow_tpu.ops.attention import paged_decode_attention

    b, h, d, max_len, ps = 8, 16, 64, 512, 16

    def placed(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=jax.sharding.NamedSharding(mesh, P(*spec))
        )

    pool = placed(
        (b * max_len // ps, ps, h, d), jnp.float32,
        "data", None, "model", None,
    )
    shapes = (
        placed((b, 1, h, d), jnp.float32, None, None, "model", None),
        pool,
        pool,
        placed((b, max_len // ps), jnp.int32),
        placed((b,), jnp.int32),
    )

    def fn(q, k, v, tables, lens):
        return paged_decode_attention(
            q, k, v, tables, lens, kernel="auto", head_shard=head_shard
        )

    return fn, shapes


def test_kernel_on_head_sharded_mesh_needs_shard_map(as_tpu):
    """Under plain jit JAX refuses to partition a Mosaic call — it never
    replicates the pool behind the caller's back — and the head-shard
    wrapper is what makes the same call lower."""
    import numpy as np

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    fn, shapes = _paged_decode_on_mesh(mesh, None)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))
    fn, shapes = _paged_decode_on_mesh(mesh, (mesh, "model"))
    jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",))


def test_mosaic_compiles_head_sharded_kernel(as_tpu):
    """Four v5e chips, heads over the model axis: compiles, and the
    program moves no pool between chips."""
    import numpy as np

    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    mesh = Mesh(np.array(devices).reshape(1, 4), ("data", "model"))
    fn, shapes = _paged_decode_on_mesh(mesh, (mesh, "model"))
    compiled = (
        jax.jit(fn)
        .trace(*shapes)
        .lower(lowering_platforms=("tpu",))
        .compile()
    )
    hlo = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all"):
        assert collective not in hlo, collective


# -- the engine's step programs own the pools they rewrite --------------------

from test_pool_donation import (  # noqa: E402,F401
    KINDS, PROMPT, RECURRENT_KINDS, _step, build_lm, lm, recurrent_lm,
)


@pytest.fixture
def step_programs(as_tpu, monkeypatch):
    """Every step program an engine builds, recorded as (jitted program,
    argument shapes) at its first call and never run: lowering for the TPU
    as the chip would (`as_tpu`: the decode kernel is the Mosaic one, which
    the CPU cannot execute), the call answers with zeros of the program's
    output shapes."""
    from flexflow_tpu.serving.engine import GenerationEngine

    programs = []
    build = GenerationEngine._step_jit

    def recording(self, *impls):
        jitted = build(self, *impls)

        def call(*args):
            shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
            )
            programs.append((jitted, shapes))
            return jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(jitted, *shapes),
            )

        return call

    monkeypatch.setattr(GenerationEngine, "_step_jit", recording)
    return programs


def _step_program(lm, programs, kind, layout, dtype="fp32"):
    """The lowered-for-TPU program of step `kind`, its cache, and where
    the pools sit among its arguments."""
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    sched, eng, cache = build_scheduler(
        lm,
        ServeConfig(
            max_seqs=2, max_seq_len=32, **page_geometry(layout, 32),
            kv_dtype=dtype,
            decode_kernel="pallas",
        ),
    )
    slot = cache.alloc(len(PROMPT), len(PROMPT) + 8)
    eng.prefill(sched.params, [PROMPT], [slot])
    if kind != "prefill":
        _step(kind, eng, cache, sched.params, slot, 7)
    jitted, shapes = programs[-1]
    return jitted.trace(*shapes).lower(lowering_platforms=("tpu",)), cache


@pytest.mark.parametrize(
    "kind,layout",
    [(k, l) for k in KINDS for l in ("one_page", "paged")]
    + [(k, "recurrent") for k in RECURRENT_KINDS],
)
def test_step_program_donates_exactly_its_pools(
    request, step_programs, kind, layout
):
    """Each of the five programs, lowered for the TPU, marks every pool it
    rewrites as donated (the lowered text carries one aliasing attribute a
    pool) and nothing else: an edit that drops the donation at one site,
    or extends it to the parameters, fails here without a chip. A model
    with recurrent layers has its per-slot state among them."""
    recurrent = layout == "recurrent"
    lowered, cache = _step_program(
        request.getfixturevalue("recurrent_lm" if recurrent else "lm"),
        step_programs, kind, "paged" if recurrent else layout,
    )
    pools = {
        (s.shape, s.dtype)
        for s in jax.tree.leaves((cache.k, cache.v, cache.state))
    }
    spec = cache.spec
    # K and V a layer, or the one latent pool; S and conv a recurrent layer
    n_pools = spec.kv_pools * len(spec.layer_guids) + 2 * len(spec.state_guids)
    assert bool(spec.state_guids) == recurrent
    args = jax.tree.leaves(lowered.args_info)
    donated = [a for a in args if a.donated]
    assert len(donated) == n_pools
    assert {(a.shape, a.dtype) for a in donated} == pools
    text = lowered.as_text()
    assert (
        text.count("tf.aliasing_output") + text.count("jax.buffer_donor")
        == n_pools
    )


def test_int8_step_program_donates_its_scale_pools_too(lm, step_programs):
    lowered, cache = _step_program(
        lm, step_programs, "decode", "paged", "int8"
    )
    n_pools = 4 * len(cache.spec.layer_guids)
    donated = [a for a in jax.tree.leaves(lowered.args_info) if a.donated]
    assert len(donated) == n_pools
    assert sorted({str(a.dtype) for a in donated}) == ["float32", "int8"]


@pytest.fixture(scope="module")
def lm_lane_rows():
    """`lm` with cache rows of one whole lane tile (4 heads of 32): the
    narrowest row the COMPILED paged kernel copies out of a pool
    (decode_kernel.use_kernel), so the decode program below holds the
    Mosaic kernel as it does on the chip."""
    return build_lm(hidden=128)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_compiled_step_program_aliases_its_pools(
    lm_lane_rows, step_programs, kind
):
    """Compiled for a v5e, the program's outputs alias at least the pools'
    bytes, and it converts no pool to another layout (`copy`; a
    `copy-start` prefetch of these tiny pools into fast memory is not
    one): the scatter writes into the buffer it was handed. Parameters
    and results take the layouts the device gives their shapes, as on
    the chip."""
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    lm = lm_lane_rows

    sched, eng, cache = build_scheduler(
        lm,
        ServeConfig(max_seqs=2, max_seq_len=32,
                    decode_kernel="pallas"),
    )
    slot = cache.alloc(len(PROMPT), len(PROMPT) + 8)
    eng.prefill(sched.params, [PROMPT], [slot])
    if kind == "decode":
        _step(kind, eng, cache, sched.params, slot, 7)
    jitted, shapes = step_programs[-1]
    one_chip = SingleDeviceSharding(devices[0])
    placed = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    compiled = (
        jitted.trace(*placed).lower(lowering_platforms=("tpu",)).compile()
    )
    assert ("tpu_custom_call" in compiled.as_text()) == (kind == "decode")
    pool = next(iter(cache.k.values()))
    pool_bytes = sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves((cache.k, cache.v))
    )
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    shape = "f32[%s]" % ",".join(map(str, pool.shape))
    copies = [
        line for line in compiled.as_text().splitlines()
        if " copy(" in line and shape in line.split("=", 1)[-1][:80]
    ]
    assert not copies, copies[:2]


# -- the grouped-matmul kernel of an expert layer's prefill (PR 46) -----------

from flexflow_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

# hidden, expert width, the router's experts, and the rows of the three
# expert cells' programs as they run (slots x k of the decode step first,
# then bucket x k of the prefill programs), 64 experts stacked
EXPERT_CELLS = {
    "olmoe": (2048, 1024, 64, (128, 1024, 2048, 5120)),
    "kanana": (2048, 768, 128, (96, 768, 1536, 3840)),
    "kimi": (2304, 1024, 256, (256, 2048, 4096, 8192, 12800)),
}
EXPERT_SHAPES = [
    pytest.param(d, f, experts, rows, id=f"{cell}-rows{rows}")
    for cell, (d, f, experts, buckets) in EXPERT_CELLS.items()
    for rows in buckets
]


def _expert_mlp(experts, rows):
    def fn(x, w_gate, w_up, w_down, sizes):
        return gm.expert_mlp(
            x, w_gate, w_up, w_down, sizes, rows_per_group=rows / experts,
            interpret=False,
        )

    return fn


def _expert_operands(d, f, rows):
    return (
        _sds((rows, d)), _sds((64, d, f)), _sds((64, d, f)), _sds((64, f, d)),
        _sds((64,), jnp.int32),
    )


@pytest.mark.parametrize("d, f, experts, rows", EXPERT_SHAPES)
def test_grouped_matmul_lowers_and_compiles_at_the_cells_shapes(
    d, f, experts, rows
):
    """Both calls of `expert_mlp` (gate + up + silu, then down) at the
    precision the cells serve at: the Pallas TPU lowering here, Mosaic
    itself where libtpu describes a v5e."""
    assert gm.use_kernel(rows, d, f, jnp.float32, grad=False) is False  # a CPU
    fn, shapes = _expert_mlp(experts, rows), _expert_operands(d, f, rows)
    text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert "grouped_gate_up" in text and "grouped_matmul" in text
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    _compile_for(devices, fn, shapes)


def test_grouped_matmul_compiles_at_highest_too():
    """The cells' first correctness pass traces the step programs under
    `jax.default_matmul_precision("highest")`: Mosaic's fp32 contraction."""
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    d, f, experts, (_, rows, *_) = EXPERT_CELLS["kimi"]
    with jax.default_matmul_precision("highest"):
        _compile_for(
            devices, _expert_mlp(experts, rows), _expert_operands(d, f, rows)
        )


def _expert_lm():
    """An expert model at lane-tile widths: its one prefill bucket hands
    its expert layer 64 x 8 rows, a decode step 2 x 8."""
    from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_olmoe

    model = FFModel(FFConfig(batch_size=2, seed=0))
    tok = model.create_tensor([2, 64], dtype=DataType.INT32, name="tokens")
    build_olmoe(
        model, tok, vocab_size=50, hidden=128, num_heads=4, num_layers=1,
        expert_hidden=128, num_experts=8, experts_per_token=8,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
    )
    return model


def _served_programs(model, programs, seq):
    """{kind: lowered-for-TPU text} of the prefill and decode programs an
    engine builds for `model` (`step_programs` records them), and the
    engine."""
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    sched, eng, cache = build_scheduler(
        model,
        ServeConfig(max_seqs=2, max_seq_len=seq, prefill_buckets=(seq,)),
    )
    slot = cache.alloc(len(PROMPT), len(PROMPT) + 8)
    texts = {}
    eng.prefill(sched.params, [PROMPT], [slot])
    texts["prefill"] = programs[-1]
    _step("decode", eng, cache, sched.params, slot, 7)
    texts["decode"] = programs[-1]
    return {
        kind: jitted.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
        for kind, (jitted, shapes) in texts.items()
    }, eng


def _train_step_text(model):
    import numpy as np

    ex = model.executor
    batch = {
        name: np.zeros(
            tuple(d.size for d in shape.dims if not d.is_replica_dim),
            shape.dtype.to_jnp(),
        )
        for name, shape in ex.input_shapes().items()
    }
    return (
        ex.train_step()
        .trace(model.params, model.opt_state, ex.shard_batch(batch),
               jax.random.PRNGKey(0))
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )


KERNEL, XLAS = "grouped_", "ragged_dot"


def test_an_expert_models_served_programs_take_the_kernel_and_its_train_step_does_not(
    step_programs,
):
    """Lowered as on the chip: the prefill and the decode program's expert
    layer is the grouped-matmul kernel's two calls and no `ragged_dot`
    (the probe found no row count that XLA's call served better: no
    threshold), the engine counts the programs it dispatched, and the same
    model's TRAIN step, which differentiates the layer, keeps XLA's."""
    model = _expert_lm()
    texts, eng = _served_programs(model, step_programs, 64)
    for text in texts.values():
        assert text.count("grouped_gate_up") == 1
        assert KERNEL in text and XLAS not in text
    assert eng.prefill_programs == eng.moe_kernel_programs_prefill == 1
    assert eng.moe_kernel_programs_decode == 1
    train = _train_step_text(model)
    assert XLAS in train and KERNEL not in train


@pytest.mark.parametrize("family", ["decoder", "looped_decoder", "narrow_experts"])
def test_programs_without_a_prefills_expert_rows_hold_no_kernel_call(
    request, step_programs, family
):
    """The bypass, shown on lowered text and not asserted: a decoder's and
    a looped decoder's step programs and train steps have no expert layer
    and so neither maker's call; the CPU tests' narrow experts (32 wide)
    keep `ragged_dot` in every program."""
    if family == "decoder":
        model, seq = request.getfixturevalue("lm"), 32
    elif family == "looped_decoder":
        from tests import test_ouro

        model, seq = test_ouro._model(), test_ouro.SEQ
    else:
        from tests import test_olmoe

        model, seq = test_olmoe._model(), test_olmoe.SEQ
    texts, eng = _served_programs(model, step_programs, seq)
    texts["train"] = _train_step_text(model)
    for kind, text in texts.items():
        assert KERNEL not in text, kind
        assert (XLAS in text) == (family == "narrow_experts"), kind
    assert eng.moe_kernel_programs_prefill == eng.moe_kernel_programs_decode == 0


# -- the one-step kernel of a recurrent layer's decode step (PR 48) -----------

from flexflow_tpu.ops.pallas import kda_step as ks  # noqa: E402

# the longform cell's recurrent state: 32 slots, 32 heads of 128
KDA_CELL = (32, 32, 128)


def _kda_operands(slots, heads, d):
    return (
        *(_sds((slots, heads, d)),) * 4, _sds((slots, heads)),
        _sds((slots, heads, d, d)), _sds((slots,), jnp.bool_),
    )


@pytest.mark.parametrize("heads_a_block", [None, 8])
def test_kda_step_kernel_lowers_and_compiles_at_the_cells_shape(heads_a_block):
    """The Pallas TPU lowering here, Mosaic itself where libtpu describes
    a v5e, at the block the kernel picks (a whole slot: 32 heads) and at
    a sublane tile of heads; compiled, the new state is the state's own
    buffer (the call aliases it and the program donates it)."""
    slots, heads, d = KDA_CELL
    assert ks.use_kernel(heads, d, jnp.float32) is False  # a CPU
    assert ks.heads_per_block(heads) == 32

    def fn(q, k, v, g, beta, state, active):
        return ks.kda_step_rows(
            q, k, v, g, beta, state, active, heads=heads_a_block,
            interpret=False,
        )

    shapes = _kda_operands(*KDA_CELL)
    text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert ks.NAME in text
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    one_chip = SingleDeviceSharding(devices[0])
    placed = [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip) for s in shapes
    ]
    compiled = (
        jax.jit(fn, donate_argnums=(5,)).trace(*placed)
        .lower(lowering_platforms=("tpu",)).compile()
    )
    state_bytes = 4 * slots * heads * d * d
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 64
    # XLA does not stage the state through fast memory ahead of the call
    assert "slice-start" not in compiled.as_text()


# -- the scan kernel of a recurrent layer's prefill (PR 52) -------------------

from flexflow_tpu.ops.pallas import kda_scan as kc  # noqa: E402

KDA_CHUNK = 64


def _kda_scan_operands(tokens, slots, heads, d):
    n = tokens // KDA_CHUNK
    return (
        *(_sds((tokens, heads, d)),) * 4, _sds((tokens, heads)),
        _sds((slots, heads, d, d)), _sds((tokens,), jnp.bool_),
        _sds((n,), jnp.bool_), _sds((slots,), jnp.int32), _sds((slots,), jnp.int32),
    )


@pytest.mark.parametrize("tokens", [256, 1600])
def test_kda_scan_kernel_lowers_and_compiles_at_the_cells_shapes(tokens):
    """The Pallas TPU lowering here, Mosaic itself where libtpu describes
    a v5e, at the longform cell's smallest and largest prefill bucket (32
    heads of 128, chunks of 64, 32 slots): compiled, the new state is the
    state's own buffer (the call aliases it and the program donates it),
    nothing of the per-chunk states' size is a temporary, and XLA does
    not stage an operand through fast memory ahead of the call."""
    slots, heads, d = KDA_CELL
    assert kc.use_kernel(heads, d, KDA_CHUNK, jnp.float32) is False  # a CPU
    assert kc.heads_per_block(heads) == 8

    def fn(*operands):
        return kc.kda_scan_rows(*operands, chunk=KDA_CHUNK, interpret=False)

    shapes = _kda_scan_operands(tokens, *KDA_CELL)
    text = jax.jit(fn).trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert kc.NAME in text
    devices = _v5e_devices()
    if devices is None:
        pytest.skip("libtpu cannot describe a v5e topology here")
    one_chip = SingleDeviceSharding(devices[0])
    placed = [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip) for s in shapes
    ]
    compiled = (
        jax.jit(fn, donate_argnums=(5,)).trace(*placed)
        .lower(lowering_platforms=("tpu",)).compile()
    )
    state_bytes = 4 * slots * heads * d * d
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes
    # a state a chunk is 2 MiB x chunks a layer: none of it is kept
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * heads * d * d
    assert "slice-start" not in compiled.as_text()


def _all_slot_selects(text, state):
    """The lowered text's `select`s over an array of the state's shape."""
    shape = "x".join(map(str, state.shape)) + "xf32"
    return [
        line for line in text.splitlines()
        if "stablehlo.select" in line and shape in line
    ]


def _state_scatters(text, state):
    """The lowered text's `scatter`s into an array of the state's shape
    (the line that closes the op's region carries its types)."""
    import re

    shape = "x".join(map(str, state.shape)) + "xf32"
    return re.findall(
        rf"\}}\) : \(tensor<{shape}>, tensor<[0-9x]+xi32>, tensor<[^>]+>\) "
        rf"-> tensor<{shape}>", text,
    )


def test_a_recurrent_models_decode_program_takes_the_kernel_and_the_others_do_not(
    step_programs,
):
    """Lowered as on the chip, a model with recurrent layers of lane-tile
    heads: the decode program holds the step kernel and no `select` over
    the whole per-slot state (the `where` that handed the idle rows back);
    its prefill holds the scan kernel, whose output block is the slot's
    row, and no scatter over the `"S"` state; its train step
    differentiates the chunked form and holds neither call."""
    from tests import test_kimi_linear as kimi

    model = kimi._model(num_heads=8, kda_head_dim=128, kda_chunk=kc.SUB)
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    sched, eng, cache = build_scheduler(
        model,
        ServeConfig(max_seqs=2, max_seq_len=kimi.SEQ, prefill_buckets=kimi.BUCKETS),
    )
    state = cache.state[cache.spec.state_guids[0]]["S"]
    slot = cache.alloc(len(PROMPT), len(PROMPT) + 8)
    eng.prefill(sched.params, [PROMPT], [slot])
    _step("decode", eng, cache, sched.params, slot, 7)
    (prefill, p_shapes), (decode, d_shapes) = step_programs[-2:]
    prefill = prefill.trace(*p_shapes).lower(lowering_platforms=("tpu",)).as_text()
    decode = decode.trace(*d_shapes).lower(lowering_platforms=("tpu",)).as_text()
    assert ks.NAME in decode and not _all_slot_selects(decode, state)
    assert kc.NAME in prefill and not _state_scatters(prefill, state)
    assert ks.NAME not in prefill and kc.NAME not in decode
    train = _train_step_text(model)
    assert ks.NAME not in train and kc.NAME not in train
    assert eng.kda_kernel_programs_decode == 1 and eng.kernel_fallbacks == 0
    assert eng.kda_kernel_programs_prefill == eng.prefill_programs == 1


@pytest.mark.parametrize(
    "family", ["decoder", "experts", "latent", "looped_decoder", "toy_recurrent"]
)
def test_programs_without_lane_tile_recurrent_heads_hold_no_kda_kernel_call(
    request, step_programs, family
):
    """The bypass, shown on lowered text: a decoder's, an expert model's,
    a latent model's and a looped decoder's step programs and train steps
    have no recurrent layer and so no call; the CPU tests' recurrent
    heads of 16 keep `kda_step` and the `where`, the `select` over every
    slot's state that the kernel's programs do not have."""
    if family == "decoder":
        model, seq = request.getfixturevalue("lm"), 32
    elif family == "experts":
        model, seq = _expert_lm(), 64
    elif family == "latent":
        from tests import test_deepseek_v3

        model, seq = test_deepseek_v3._model(), test_deepseek_v3.SEQ
    elif family == "looped_decoder":
        from tests import test_ouro

        model, seq = test_ouro._model(), test_ouro.SEQ
    else:
        model, seq = request.getfixturevalue("recurrent_lm"), 64
    texts, eng = _served_programs(model, step_programs, seq)
    texts["train"] = _train_step_text(model)
    for kind, text in texts.items():
        assert ks.NAME not in text and kc.NAME not in text, kind
    assert eng.kda_kernel_programs_decode == eng.kda_kernel_programs_prefill == 0
    if family == "toy_recurrent":
        # heads of 16 keep `kda_chunked` and the scatter
        state = next(iter(eng.cache.state.values()))["S"]
        assert _all_slot_selects(texts["decode"], state)
        assert _state_scatters(texts["prefill"], state)
