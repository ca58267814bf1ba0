"""The `kimi_linear` block (Kimi Delta Attention over a per-slot recurrent
state beside latent attention WITHOUT positions over the latent paged
pool, a leading dense gated MLP, Kanana's expert layer holding a share)
through the operator, the builder, and the two step kinds a model with
recurrent layers is served by, each against the plain reference
`benchmarks/reference/kimi_linear.py` in exact float32 (conftest pins
`highest`), at a small size: 5 layers (KDA-dense, KDA, KDA, latent, KDA:
the published pattern's first five), hidden 64, 4 heads, KDA heads of 16
behind convolutions of 4 taps in chunks of 8, latent rank 32 + 8, 8
experts of width 24 with 2 per token of which this "chip" holds 4,
vocabulary 211, seeded weights; logits, never tokens. The contract of the
per-slot state (docs/serving.md, C1-C5) has a test a line."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.reference import kimi_linear as reference  # noqa: E402
from flexflow_tpu import (  # noqa: E402
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.core.types import OperatorType  # noqa: E402
from flexflow_tpu.models import build_kimi_linear  # noqa: E402
from flexflow_tpu.ops import attention as A  # noqa: E402
from flexflow_tpu.ops import linear_attention as L  # noqa: E402
from flexflow_tpu.ops.registry import LowerCtx, op_flops  # noqa: E402
from flexflow_tpu.serving import Request, ServeConfig, build_scheduler  # noqa: E402

VOCAB, K, SEQ, TOL, CHUNK = 211, 2, 64, 1e-4, 8
EPS, ROPE, SCALE, HELD = 1e-5, 8, 2.446, (0, 4)
SIZES = dict(
    vocab_size=VOCAB, hidden=64, num_heads=4, num_layers=5,
    kda_layers=(1, 2, 3, 5), full_attn_layers=(4,), kda_head_dim=16,
    kda_conv_kernel=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=ROPE, v_head_dim=16, dense_hidden=96, dense_layers=1,
    expert_hidden=24, num_experts=8, experts_per_token=K, shared_experts=1,
    routed_scale=SCALE, eps=EPS, kda_chunk=CHUNK,
)
BUCKETS = (16, 32, 64)


def _nodes(model, op_type):
    return [n for n in model.graph.nodes.values() if n.op_type == op_type]


def _draw_buffers(model, seed):
    """The trained buffers the builder leaves at zero, drawn so that they
    show: the routers' choice bias, and each KDA layer's A_log and dt_bias
    (per-token decays between about 0.2 and 0.95)."""
    for node in _nodes(model, OperatorType.SPARSE_MOE):
        ws = model.params[node.guid]
        ws[4] = jax.random.uniform(
            jax.random.PRNGKey(seed + node.guid), ws[4].shape, ws[4].dtype,
            -0.1, 0.1,
        )
    for node in _nodes(model, OperatorType.LINEAR_ATTENTION):
        ws = model.params[node.guid]
        key = jax.random.PRNGKey(seed + node.guid)
        assert not np.any(np.asarray(ws[8])) and not np.any(np.asarray(ws[9]))
        ws[8] = jax.random.uniform(key, ws[8].shape, ws[8].dtype, -2.0, 0.0)
        ws[9] = jax.random.uniform(
            jax.random.fold_in(key, 1), ws[9].shape, ws[9].dtype,
            np.log(0.5), np.log(4.0),
        )


def _model(held=HELD, seed=7, **sizes):
    cfg = FFConfig(batch_size=4)
    cfg.seed = seed
    model = FFModel(cfg)
    tok = model.create_tensor([4, SEQ], dtype=DataType.INT32, name="tokens")
    build_kimi_linear(model, tok, experts_held=held, **{**SIZES, **sizes})
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[], devices=jax.devices()[:1],
    )
    _draw_buffers(model, seed)
    return model


@pytest.fixture(scope="module")
def served():
    return _model()


def _weights(model):
    return [list(model.params[g]) for g in sorted(model.params)]


def _want(model, seq, positions=None, held=HELD, **kw):
    logits, _ = reference.run(
        _weights(model), seq, SEQ, EPS, ROPE, K, SCALE, held, **kw
    )
    return logits if positions is None else logits[np.asarray(positions)]


def _gap(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def _prompt(n, salt=0):
    return [(salt * 31 + 7 * j * j + 3 * j) % (VOCAB - 1) + 1 for j in range(n)]


def _serve(model, **kw):
    kw.setdefault("max_seqs", 4)
    kw.setdefault("max_seq_len", SEQ)
    kw.setdefault("prefill_buckets", BUCKETS)
    return build_scheduler(model, ServeConfig(**kw))


def _step(engine, model, feed):
    """One decode step of {slot: token}; the logits [max_seqs, V]."""
    tokens = np.zeros(4, np.int32)
    active = np.zeros(4, bool)
    for slot, tok in feed.items():
        tokens[slot], active[slot] = tok, True
    return np.asarray(engine.decode(model.params, tokens, active)[1])


def _state(cache):
    return {
        (g, name): np.asarray(a)
        for g, rows in cache.state.items() for name, a in rows.items()
    }


# -- the operator -------------------------------------------------------------


def _kda_inputs(b, s, h, d, seed=0, strength=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = -strength * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


@jax.jit
def _by_steps(q, k, v, g, beta, state):
    def one(state, token):
        o, state = L.kda_step(*token, state)
        return state, o

    tokens = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, outs = jax.lax.scan(one, state, tokens)
    return jnp.moveaxis(outs, 0, 1), state


_chunked = jax.jit(L.kda_chunked, static_argnames="chunk")


# (chunk, head_dim): a chunk of 8 is ONE sub-block; a chunk of 64 is four
# of 16 with matmuls between them, at a toy head and at the served one
FORMS = [(CHUNK, 8), (64, 8), (64, 128)]


def _heads(d):
    return 3 if d == 8 else 2


@pytest.mark.parametrize("chunk,d", FORMS)
@pytest.mark.parametrize("strength", [0.03, 1.0, 300.0])
def test_chunked_form_is_the_one_step_form(strength, chunk, d):
    """At a decay that forgets little in a chunk (0.03: the long memory
    that stresses the inverse), and at decays whose cumulative product
    underflows float32 within a chunk (exp(-300) a token): every decay
    inside a chunk is the exponential of a non-positive difference, so
    nothing overflows."""
    h, n = _heads(d), 5 if chunk == CHUNK else 3
    args = _kda_inputs(2, n * chunk, h, d, strength=strength)
    zero = jnp.zeros((2, h, d, d))
    want, last = _by_steps(*args, zero)
    got, states = _chunked(*args, zero, chunk=chunk)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(states[:, -1] - last).max()) < 1e-5
    assert states.shape == (2, n, h, d, d)


@pytest.mark.parametrize("chunk,d", FORMS)
def test_chunked_form_holds_on_one_token_repeated(chunk, d):
    """A prompt of one token repeated gives every position the same key,
    so tril(A, -1) is as far from small as it gets and a series in its
    powers (a Neumann product for the inverse) loses every digit; the
    substitution the chunked form does is held to the one-step form."""
    h = _heads(d)
    q, k, v, g, beta = _kda_inputs(1, 3 * chunk, h, d, seed=2, strength=0.03)
    q, k = (jnp.broadcast_to(t[:, :1], t.shape) for t in (q, k))
    beta = jax.nn.sigmoid(jax.scipy.special.logit(beta) + 3.0)
    zero = jnp.zeros((1, h, d, d))
    want, last = _by_steps(q, k, v, g, beta, zero)
    got, states = _chunked(q, k, v, g, beta, zero, chunk=chunk)
    limit = 1e-5 * max(1.0, float(jnp.abs(want).max()))
    assert float(jnp.abs(got - want).max()) < limit
    assert float(jnp.abs(states[:, -1] - last).max()) < limit


@pytest.mark.parametrize("chunk,d", FORMS)
def test_a_token_is_made_a_no_op_by_data_and_a_chunk_resets_by_flag(chunk, d):
    """What a packed row needs of one program: padding (beta = 0, g = 0,
    k = 0) leaves the state alone, and a chunk that starts another prompt
    starts from zero whatever was carried."""
    h, s, real = _heads(d), 3 * chunk, 2 * chunk + 3
    q, k, v, g, beta = _kda_inputs(1, s, h, d, seed=3)
    pad = jnp.arange(s) >= real  # the last chunk's tail is padding
    k = jnp.where(pad[None, :, None, None], 0, k)
    g = jnp.where(pad[None, :, None, None], 0, g)
    beta = jnp.where(pad[None, :, None], 0, beta)
    zero = jnp.zeros((1, h, d, d))
    _, states = _chunked(q, k, v, g, beta, zero, chunk=chunk)
    _, want = _by_steps(*(a[:, :real] for a in (q, k, v, g, beta)), zero)
    assert float(jnp.abs(states[:, -1] - want).max()) < 1e-5
    # chunks 0-1 are one prompt, chunk 2 another: its state is its own
    reset = jnp.array([[False, False, True]])
    got, states = _chunked(q, k, v, g, beta, zero + 5.0, reset, chunk)
    third = slice(2 * chunk, real)
    alone, last = _by_steps(*(a[:, third] for a in (q, k, v, g, beta)), zero)
    assert float(jnp.abs(got[:, third] - alone).max()) < 1e-5
    assert float(jnp.abs(states[:, 2] - last).max()) < 1e-5


@pytest.mark.parametrize("chunk,d", FORMS)
def test_chunked_gradients_are_the_one_step_forms(chunk, d):
    h, s = _heads(d), 3 * chunk
    args = _kda_inputs(1, s, h, d, seed=5)
    zero = jnp.zeros((1, h, d, d))
    w = jax.random.normal(jax.random.PRNGKey(9), (1, s, h, d))

    def chunked(*a):
        return jnp.sum(_chunked(*a, zero, chunk=chunk)[0] * w)

    def stepped(*a):
        return jnp.sum(_by_steps(*a, zero)[0] * w)

    got = jax.grad(chunked, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(stepped, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-4 * max(1.0, float(jnp.abs(b).max()))


def test_the_served_shape_has_no_chunk_wide_decays_and_no_triangular_solve():
    """What the scan's share of its roofline says on the chip, held here
    by the program's text: at a served layer's shape a chunk's decays are
    [16, 16, d] pieces, never [C, C, d], and its inverse is matmuls."""
    b, s, h, d, chunk = 1, 256, 32, 128, 64
    row = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
    text = jax.jit(
        lambda q, k, v, g, beta, state: L.kda_chunked(
            q, k, v, g, beta, state, chunk=chunk
        )
    ).lower(
        row, row, row, row, jax.ShapeDtypeStruct((b, s, h), jnp.float32),
        jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
    ).as_text()
    # `stablehlo.triangular_solve` on a TPU, `@_solve_triangular` calling
    # lapack's `strsm` here
    assert "triangular" not in text and "trsm" not in text
    assert f"x{chunk}x{chunk}x{d}xf32>" not in text
    assert f"x16x16x{d}xf32>" in text and "dot_general" in text


@pytest.mark.parametrize("n", [1, 3, 5, 8, 21, 40])
def test_the_lowering_is_the_references_layer(served, n):
    """Prompts shorter than the convolution's kernel (1, 3), shorter than
    a chunk (5), a whole chunk (8), and with chunk boundaries inside."""
    node = _nodes(served, OperatorType.LINEAR_ATTENTION)[1]
    ws = served.params[node.guid]
    x = jax.random.normal(jax.random.PRNGKey(n), (1, n, 64))
    want = reference._kda(x[0], jnp.ones(64), ws, EPS, None) - x[0]
    # the reference normalises its input (gain one here); feed it the same
    u = x[0] * jax.lax.rsqrt(jnp.mean(x[0] ** 2, -1, keepdims=True) + EPS)
    got = L._lower_linear_attention(node.params)(
        [u[None]], ws, LowerCtx(train=False)
    )[0]
    assert _gap(got[0], np.asarray(want)) < TOL


def test_latent_attention_without_positions_rotates_nothing(served):
    node = _nodes(served, OperatorType.LATENT_ATTENTION)[0]
    assert node.params["rope_theta"] is None
    ws = served.params[node.guid]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64))
    ctx = LowerCtx(train=False)
    q_nope, q_rope, latent = A.mla_project(x, ws, node.params, ctx, jnp.arange(12)[None])
    shifted = A.mla_project(x, ws, node.params, ctx, 5 + jnp.arange(12)[None])
    for a, b in zip((q_nope, q_rope, latent), shifted):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    u = x[0] * jax.lax.rsqrt(jnp.mean(x[0] ** 2, -1, keepdims=True) + EPS)
    got = A._lower_latent_attention(node.params)([u[None]], ws, ctx)[0]
    want = reference._latent(x[0], jnp.ones(64), ws, EPS, ROPE) - x[0]
    assert _gap(got[0], np.asarray(want)) < TOL


def test_search_counts_the_node_and_its_state(served):
    from flexflow_tpu.search.auto import estimate_max_in_flight

    node = _nodes(served, OperatorType.LINEAR_ATTENTION)[0]
    flops = op_flops(node.op_type, node.output_shapes, node.params)
    e, hd, r = 64, 64, 16
    proj = e * (3 * hd + 2 * r + 4) + 2 * r * hd + hd * e + 3 * hd * 4
    scan = 4 * (7 * CHUNK * 16 + 6 * 16 * 16)
    assert flops == 4 * SEQ * (2.0 * proj + scan)
    # a sequence's pages and its slot's row of the four recurrent layers
    state = 4 * 4 * (4 * 16 * 16 + 3 * 3 * 64)
    page = 16 * 128 * 4  # one latent layer, rows padded to 128
    fit = estimate_max_in_flight(
        served.graph, 10 * (state + page), 8, 8, SEQ, page_size=16
    )
    assert fit == 10


# -- the model ------------------------------------------------------------------


def test_the_builder_follows_the_published_pattern(served):
    kinds = [
        n.op_type for n in served.graph.nodes.values()
        if n.op_type in (OperatorType.LINEAR_ATTENTION, OperatorType.LATENT_ATTENTION)
    ]
    lin, lat = OperatorType.LINEAR_ATTENTION, OperatorType.LATENT_ATTENTION
    assert kinds == [lin, lin, lin, lat, lin]
    assert len(_nodes(served, OperatorType.SPARSE_MOE)) == 4
    with pytest.raises(ValueError, match="every layer"):
        build_kimi_linear(
            FFModel(FFConfig(batch_size=1)), None, num_layers=5,
            kda_layers=(1, 2), full_attn_layers=(4,),
        )


def test_forward_pass_is_the_references(served):
    seqs = np.stack([_prompt(SEQ, salt) for salt in range(4)]).astype(np.int32)
    ex = served.executor
    values = ex.forward_values(
        served.params, {"tokens": jnp.asarray(seqs)}, None, train=False
    )
    got = np.asarray(values[(ex.logits_ref.guid, ex.logits_ref.out_idx)])
    for row in (0, 3):
        assert _gap(got[row], _want(served, seqs[row])) < TOL


def test_the_four_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: at a small size the routed parts
    of the result that the shares give, with what every chip computes
    alike (the shared expert) counted once, add up to what the uncut
    reference gives for the whole layer."""
    from flexflow_tpu.ops import moe

    whole = _model(held=None)
    node = _nodes(whole, OperatorType.SPARSE_MOE)[0]
    ws = whole.params[node.guid]
    shared = whole.params[node.guid + 1]
    m = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 64))
    routed, chosen = reference.routed_experts(
        m[0], ws[0], ws[1], ws[2], ws[3], ws[4], K, SCALE
    )
    want = routed + reference._gated(m[0], *shared)
    parts = jnp.zeros_like(m[0])
    for first in (0, 2, 4, 6):
        held = dict(node.params, experts_held=(first, 2))
        part = [ws[0], ws[1][first: first + 2], ws[2][first: first + 2],
                ws[3][first: first + 2], ws[4]]
        y, _ = moe.sparse_moe(m, part, held, LowerCtx(train=False))
        parts = parts + y[0]
    got = parts + reference._gated(m[0], *shared)
    assert _gap(got, np.asarray(want)) < TOL


# -- the one-step form's kernel: live rows only, in place (PR 48) --------------

from flexflow_tpu.ops.pallas import kda_step as KS  # noqa: E402

# (slots, heads): the served layer's shape (32 slots, 32 heads of 128) and a
# small one; which slots are live
KERNEL_SHAPES = {"served": (32, 32), "small": (4, 8)}
LIVE = {
    "none": lambda b: np.zeros(b, bool),
    "one": lambda b: np.arange(b) == 1,
    "every_other": lambda b: np.arange(b) % 2 == 0,
    "all": lambda b: np.ones(b, bool),
    "last_alone": lambda b: np.arange(b) == b - 1,
}


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("shape", KERNEL_SHAPES)
def test_the_kernel_is_kda_step_on_the_live_rows_and_leaves_the_others(shape, live):
    """In the Pallas interpreter, against `kda_step` followed by the
    `where`: the live rows' outputs and new states within float32
    rounding, every other row of the state bit-equal to what went in
    (NaN included: a row the grid does not visit is not read)."""
    b, h = KERNEL_SHAPES[shape]
    d = 128
    assert KS.supports(h, d, jnp.float32) and not KS.use_kernel(h, d, jnp.float32)
    q, k, v, g, beta = (a[:, 0] for a in _kda_inputs(b, 1, h, d, seed=3))
    mask = LIVE[live](b)
    state = jax.random.normal(jax.random.PRNGKey(4), (b, h, d, d))
    state = jnp.where(mask[:, None, None, None], state, jnp.nan)
    before = np.asarray(state)
    want_o, want = L.kda_step(q, k, v, g, beta, state)
    got_o, got = KS.kda_step_rows(
        q, k, v, g, beta, state, jnp.asarray(mask), interpret=True
    )
    got_o, got = np.asarray(got_o), np.asarray(got)
    assert np.array_equal(got[~mask], before[~mask], equal_nan=True)
    assert not np.any(got_o[~mask])
    scale = float(jnp.abs(state[mask]).max()) if mask.any() else 1.0
    assert np.abs(got[mask] - np.asarray(want)[mask]).max(initial=0) < 1e-6 * scale
    assert np.abs(got_o[mask] - np.asarray(want_o)[mask]).max(initial=0) < 1e-5 * scale


def test_the_kernels_gate_reads_type_and_shape():
    assert KS.supports(32, 128, jnp.float32) and KS.supports(8, 256, "float32")
    assert not KS.supports(32, 128, jnp.bfloat16)  # a float32 state
    assert not KS.supports(4, 16, jnp.float32)  # the CPU tests' heads
    assert not KS.supports(12, 128, jnp.float32)  # whole sublane tiles of heads
    assert [KS.heads_per_block(h) for h in (8, 32, 40, 64)] == [8, 32, 8, 32]
    # off a TPU `kda_step_live` is `kda_step` and the `where`
    q, k, v, g, beta = (a[:, 0] for a in _kda_inputs(2, 1, 8, 128))
    state = jnp.ones((2, 8, 128, 128))
    o, new, kernel = L.kda_step_live(
        q, k, v, g, beta, state, jnp.asarray([True, False])
    )
    want_o, want = L.kda_step(q, k, v, g, beta, state)
    assert kernel is False
    assert np.array_equal(o, want_o) and np.array_equal(new[0], want[0])
    assert np.array_equal(new[1], state[1])


# -- serving: prefill, then decode ---------------------------------------------


# -- the scan kernel of a recurrent layer's prefill (PR 52) -------------------

from flexflow_tpu.ops.pallas import kda_scan as KC  # noqa: E402

SCAN_CHUNK = 64
# a packed row: (prompt lengths in order, their slots, tokens of the row,
# slots of the state, heads); a prompt begins on a chunk boundary
PACKED = {
    "prompts_on_boundaries_padding_behind": ((70, 64, 130), (3, 0, 4), 512, 5, 8),
    "one_chunk_and_many": ((9, 300), (1, 0), 384, 3, 8),
    "one_prompt_fills_the_row": ((256,), (2,), 256, 4, 8),
    "two_blocks_of_heads": ((100, 20), (0, 2), 256, 3, 16),
    "one_token_repeated": ((192,), (1,), 256, 2, 8),
}


def _packed_row(case, d=128):
    lens, slots, tokens, rows, h = PACKED[case]
    q, k, v, g, beta = _kda_inputs(
        1, tokens, h, d, seed=5, strength=0.03 if "repeated" in case else 1.0
    )
    if "repeated" in case:
        q, k = (jnp.broadcast_to(t[:, :1], t.shape) for t in (q, k))
        beta = jax.nn.sigmoid(jax.scipy.special.logit(beta) + 3.0)
    live, fresh = np.zeros(tokens, bool), np.zeros((1, tokens // SCAN_CHUNK), bool)
    ids = np.full(rows + 2, rows, np.int32)  # rows past the last: slot `rows`
    last = np.zeros(rows + 2, np.int32)
    spans, at = [], 0
    for i, (n, slot) in enumerate(zip(lens, slots)):
        live[at: at + n] = True
        fresh[0, at // SCAN_CHUNK] = True
        ids[i], last[i] = slot, at + n - 1
        spans.append((at, at + n))
        at += -(-n // SCAN_CHUNK) * SCAN_CHUNK
    # stale rows everywhere: an admitted slot's is never read, another's
    # comes back bit-equal (NaN included)
    state = np.full((rows, h, d, d), np.nan, np.float32)
    book = tuple(jnp.asarray(a) for a in (live, fresh, ids, last))
    return (q, k, v, g, beta), jnp.asarray(state), book, spans, slots


@pytest.mark.parametrize("case", PACKED)
def test_the_scan_kernel_is_kda_chunked_and_the_scatter(case, monkeypatch):
    """`kda_chunked_rows` through the kernel (the Pallas interpreter)
    against its fallback, `kda_chunked` over the masked row and the
    scatter, and against `kda_step` token by token, prompt by prompt: the
    live tokens' outputs and each admitted slot's row within float32
    rounding, every other slot's row bit-equal to what went in."""
    ins, state, book, spans, slots = _packed_row(case)
    with jax.default_matmul_precision("highest"):
        want_o, want, took = L.kda_chunked_rows(*ins, state, *book, SCAN_CHUNK)
        assert took is False  # a CPU
        monkeypatch.setattr(KC, "use_kernel", KC.supports)
        got_o, got, took = L.kda_chunked_rows(*ins, state, *book, SCAN_CHUNK)
        assert took is True
    live = np.asarray(book[0])
    got_o, got, before = np.asarray(got_o), np.asarray(got), np.asarray(state)
    others = [s for s in range(state.shape[0]) if s not in slots]
    assert np.array_equal(got[others], before[others], equal_nan=True)
    assert np.isfinite(got[list(slots)]).all() and np.isfinite(got_o[0, live]).all()
    scale = max(1.0, float(np.abs(np.asarray(want)[list(slots)]).max()))
    assert np.abs(got_o[0, live] - np.asarray(want_o)[0, live]).max() < 1e-5
    assert np.abs(got[list(slots)] - np.asarray(want)[list(slots)]).max() < 1e-5 * scale
    zero = jnp.zeros((1,) + state.shape[1:])
    for (lo, hi), slot in zip(spans, slots):
        steps_o, steps = _by_steps(*(a[:, lo:hi] for a in ins), zero)
        scale = max(float(jnp.abs(steps_o).max()), float(jnp.abs(steps).max()))
        limit = 2e-5 * max(1.0, scale)
        assert np.abs(got_o[0, lo:hi] - np.asarray(steps_o)[0]).max() < limit
        assert np.abs(got[slot] - np.asarray(steps)[0]).max() < limit


def test_the_scan_kernel_at_the_served_precision_is_one_bfloat16_pass_away():
    """At the default precision the three products with the state and B u
    take one bfloat16 pass, as XLA's DEFAULT does on a TPU (here, on a
    CPU, the fallback's are exact): close, not equal."""
    ins, state, book, _, slots = _packed_row("prompts_on_boundaries_padding_behind")
    want_o, want, _ = L.kda_chunked_rows(*ins, state, *book, SCAN_CHUNK)
    got_o, got = KC.kda_scan_rows(
        *(a[0] for a in ins), state, book[0], book[1][0], *book[2:],
        chunk=SCAN_CHUNK, interpret=True,
    )
    live = np.asarray(book[0])
    gap = np.abs(np.asarray(got_o)[live] - np.asarray(want_o)[0, live]).max()
    assert 1e-6 < gap < 2e-2 * float(jnp.abs(want_o[0, live]).max())
    rows = np.abs(np.asarray(got)[list(slots)] - np.asarray(want)[list(slots)]).max()
    assert rows < 2e-2 * float(jnp.abs(want[jnp.asarray(slots)]).max())


def test_the_scan_kernels_gate_reads_type_shape_and_mesh(monkeypatch):
    assert KC.supports(32, 128, 64, jnp.float32) and KC.supports(8, 256, 16, "float32")
    assert not KC.supports(32, 128, 64, jnp.bfloat16)  # a float32 state
    assert not KC.supports(4, 16, 8, jnp.float32)  # the CPU tests' heads
    assert not KC.supports(12, 128, 64, jnp.float32)  # whole sublane tiles of heads
    assert not KC.supports(32, 128, 24, jnp.float32)  # whole sub-blocks
    assert not KC.use_kernel(32, 128, 64, jnp.float32)  # a CPU
    assert [KC.heads_per_block(h) for h in (8, 32, 40)] == [8, 8, 8]
    # a mesh of more than one device keeps `kda_chunked` and the scatter,
    # whatever the platform: a Mosaic kernel is not partitioned
    monkeypatch.setattr(KC, "use_kernel", KC.supports)
    ins, state, book, _, _ = _packed_row("one_prompt_fills_the_row")

    class Ctx:
        mesh = type("Mesh", (), {"size": 2})()

    assert L.kda_chunked_rows(*ins, state, *book, SCAN_CHUNK, Ctx())[2] is False
    Ctx.mesh = None
    assert L.kda_chunked_rows(*ins, state, *book, SCAN_CHUNK, Ctx())[2] is True


@pytest.mark.parametrize("n", [2, 11, 16, 29])
def test_prefill_then_decode_is_the_references_forward_pass(served_either, n):
    """Prompts shorter than the kernel and a chunk (2), across a chunk
    (11), of whole chunks (16), and decode across chunk and page ends;
    through `kda_chunked` and `kda_step`, and through the two kernels."""
    served, kernel = served_either
    _, engine, cache = _serve(served)
    prompt = _prompt(n)
    slot = cache.alloc(n, n + 12)
    nxt, last = engine.prefill(served.params, [prompt], [slot])
    seq, got, tok = list(prompt), [last[0]], int(nxt[0])
    for _ in range(12):
        seq.append(tok)
        logits = _step(engine, served, {slot: tok})
        got.append(logits[slot])
        tok = int(np.argmax(logits[slot]))
    want = _want(served, seq, list(range(n - 1, n + 12)))
    assert _gap(np.stack(got), want) < TOL
    assert engine._decode_jit._cache_size() == 1
    assert engine.state_rows_decode == 12 * 4
    assert engine.state_resets_prefill == 4
    # the counters that say which makers ran: programs dispatched
    assert engine.kda_kernel_programs_prefill == (1 if kernel else 0)
    assert engine.kda_kernel_programs_decode == (12 if kernel else 0)
    assert engine.kernel_fallbacks == 0


def test_packed_admissions_serve_each_prompt_as_if_alone(served):
    """Four prompts in one row, each on a chunk boundary (5 -> 8, 17 -> 24,
    3 -> 8, 9 -> 16 tokens laid: the 64 bucket), then decoded together."""
    _, engine, cache = _serve(served)
    prompts = [_prompt(n, salt) for salt, n in enumerate((5, 17, 3, 9))]
    slots = [cache.alloc(len(p), len(p) + 6) for p in prompts]
    nxt, last = engine.prefill(served.params, prompts, slots)
    assert engine.prefill_programs == 1
    assert engine.prefill_tokens_padded == 64 and engine.prefill_tokens_real == 34
    seqs = [list(p) for p in prompts]
    got = [[row] for row in last]
    toks = [int(t) for t in nxt]
    for _ in range(6):
        for seq, tok in zip(seqs, toks):
            seq.append(tok)
        logits = _step(engine, served, dict(zip(slots, toks)))
        for i, slot in enumerate(slots):
            got[i].append(logits[slot])
        toks = [int(np.argmax(logits[slot])) for slot in slots]
    for seq, prompt, rows in zip(seqs, prompts, got):
        n = len(prompt)
        assert _gap(np.stack(rows), _want(served, seq, list(range(n - 1, n + 6)))) < TOL


def test_the_routers_choice_of_a_packed_row_is_read_from_where_prompts_lie(served):
    """`engine.moe_choice["prefill"]` lays the packed row's choice out by
    request: a prompt's tokens begin on its chunk boundary, not where the
    last prompt ended."""
    _, engine, cache = _serve(served)
    prompts = [_prompt(n, salt) for salt, n in enumerate((5, 17, 3))]
    slots = [cache.alloc(len(p), len(p) + 1) for p in prompts]
    engine.prefill(served.params, prompts, slots)
    picked = np.asarray(engine.moe_choice["prefill"])
    assert picked.shape == (4, 3, 17, K)
    for row, prompt in enumerate(prompts):
        _, chosen = reference.run(
            _weights(served), prompt, SEQ, EPS, ROPE, K, SCALE, HELD
        )
        got = picked[:, row, : len(prompt)]
        assert np.array_equal(np.sort(got, -1), np.sort(chosen, -1))
        assert (picked[:, row, len(prompt):] == -1).all()


def test_an_admission_over_the_largest_bucket_is_split_by_laid_lengths(served):
    _, engine, cache = _serve(served)
    prompts = [_prompt(n, salt) for salt, n in enumerate((25, 25, 9))]
    slots = [cache.alloc(len(p), len(p) + 2) for p in prompts]
    _, last = engine.prefill(served.params, prompts, slots)
    # 32 + 32 fill the 64 bucket; the third (16 laid) is a program of its own
    assert engine.prefill_programs == 2
    for prompt, row in zip(prompts, last):
        assert _gap(row, _want(served, prompt, [len(prompt) - 1])[0]) < TOL


# -- the per-slot state's contract ------------------------------------------------


def _poison(cache, slot=None):
    """NaN in every state row (of one slot)."""
    at = slice(None) if slot is None else slot
    cache.state = {
        g: {name: a.at[at].set(jnp.nan) for name, a in rows.items()}
        for g, rows in cache.state.items()
    }


def test_c1_a_prefill_starts_from_zero_and_never_reads_the_row(served_either):
    served, kernel = served_either
    _, engine, cache = _serve(served)
    _poison(cache)
    prompt = _prompt(13)
    slot = cache.alloc(13, 20)
    _, last = engine.prefill(served.params, [prompt], [slot])
    assert _gap(last[0], _want(served, prompt, [12])[0]) < TOL
    state = _state(cache)
    for (g, name), a in state.items():
        assert np.isfinite(a[slot]).all(), (g, name)
        others = np.delete(a, slot, axis=0)
        assert np.isnan(others).all()
    # and the row is the prompt's: a fresh engine's, bit for bit
    _, engine2, cache2 = _serve(served)
    slot2 = cache2.alloc(13, 20)
    engine2.prefill(served.params, [prompt], [slot2])
    for key, a in _state(cache2).items():
        assert np.array_equal(a[slot2], state[key][slot])


@pytest.fixture(scope="module")
def served_wide():
    """`served` with recurrent heads the kernels take, 8 of 128, and
    chunks of whole sub-blocks."""
    return _model(num_heads=8, kda_head_dim=128, kda_chunk=KC.SUB)


@pytest.fixture
def kernel_here(monkeypatch):
    """`kda_step_live` and `kda_chunked_rows` choose as on the chip, but
    for the platform: the kernels wherever type and shape allow, in the
    interpreter."""
    monkeypatch.setattr(KS, "use_kernel", KS.supports)
    monkeypatch.setattr(KC, "use_kernel", KC.supports)


@pytest.fixture(params=["kda_step", "kernel"])
def served_either(request, served):
    """The toy model through `kda_chunked`, the scatter, `kda_step` and the
    `where`, and the wide one through the two kernels: (model, whether its
    prefills and decode steps take them)."""
    if request.param == "kda_step":
        return served, False
    request.getfixturevalue("kernel_here")
    return request.getfixturevalue("served_wide"), True


def test_c2_a_decode_step_touches_its_active_slots_rows_only(served_either):
    served, kernel = served_either
    _, engine, cache = _serve(served)
    prompts = [_prompt(9, 1), _prompt(12, 2)]
    slots = [cache.alloc(len(p), len(p) + 4) for p in prompts]
    nxt, _ = engine.prefill(served.params, prompts, slots)
    idle = [s for s in range(4) if s not in slots]
    _poison(cache, idle[0])
    before = _state(cache)
    # only the first is active: the second was admitted and joins the NEXT
    # step (the default loop's order), free rows keep what they hold
    _step(engine, served, {slots[0]: int(nxt[0])})
    after = _state(cache)
    for key in before:
        assert not np.array_equal(after[key][slots[0]], before[key][slots[0]])
        for s in [slots[1]] + idle:
            assert np.array_equal(after[key][s], before[key][s], equal_nan=True)
    # the counter that says which maker ran: decode steps dispatched
    assert engine.kda_kernel_programs_decode == (1 if kernel else 0)


def test_the_kernels_decode_steps_are_the_references_and_counted(
    served_wide, kernel_here
):
    """Two prompts prefilled and decoded side by side through the kernel
    (a slot idle beside them), against the reference's forward pass; the
    engine counts a decode step a dispatch, and the scheduler mirrors it."""
    _, engine, cache = _serve(served_wide)
    prompts = [_prompt(11, 1), _prompt(5, 2)]
    slots = [cache.alloc(len(p), len(p) + 6) for p in prompts]
    nxt, last = engine.prefill(served_wide.params, prompts, slots)
    seqs, got = [list(p) for p in prompts], [[row] for row in last]
    toks = [int(t) for t in nxt]
    for _ in range(6):
        for seq, tok in zip(seqs, toks):
            seq.append(tok)
        logits = _step(engine, served_wide, dict(zip(slots, toks)))
        for i, slot in enumerate(slots):
            got[i].append(logits[slot])
        toks = [int(np.argmax(logits[slot])) for slot in slots]
    for prompt, seq, rows in zip(prompts, seqs, got):
        want = _want(served_wide, seq, list(range(len(prompt) - 1, len(seq))))
        assert _gap(np.stack(rows), want) < TOL
    assert engine._decode_jit._cache_size() == 1
    assert engine.kda_kernel_programs_decode == 6 and engine.kernel_fallbacks == 0
    assert engine.kda_kernel_programs_prefill == engine.prefill_programs == 1
    sched, engine, _ = _serve(served_wide)
    done = sched.run(_requests()[:3])
    assert all(r.status == "finished" for r in done)
    assert (
        sched.stats.kda_kernel_programs_decode
        == engine.kda_kernel_programs_decode
        == sched.stats.decode_steps > 0
    )
    assert (
        sched.stats.kda_kernel_programs_prefill
        == engine.kda_kernel_programs_prefill
        == engine.prefill_programs > 0
    )


def test_a_packed_admission_through_the_scan_kernel_serves_each_prompt_as_if_alone(
    served_wide, kernel_here
):
    """Three prompts in one row of the 64 bucket, each on a chunk boundary
    (5 -> 16, 17 -> 32, 3 -> 16 tokens laid), a stale row in every slot:
    each prompt's logits are the reference's of the prompt alone, each
    admitted slot's row is written whole and the fourth slot's is not
    touched; then two admissions of different buckets, one kernel each."""
    _, engine, cache = _serve(served_wide)
    _poison(cache)
    prompts = [_prompt(n, salt) for salt, n in enumerate((5, 17, 3))]
    slots = [cache.alloc(len(p), len(p) + 2) for p in prompts]
    _, last = engine.prefill(served_wide.params, prompts, slots)
    assert engine.prefill_programs == engine.kda_kernel_programs_prefill == 1
    assert engine.prefill_tokens_padded == 64 and engine.prefill_tokens_real == 25
    for prompt, row in zip(prompts, last):
        assert _gap(row, _want(served_wide, prompt, [len(prompt) - 1])[0]) < TOL
    (idle,) = [s for s in range(4) if s not in slots]
    for (g, name), a in _state(cache).items():
        assert np.isfinite(a[slots]).all() and np.isnan(a[idle]).all(), (g, name)
    cache.free(slots[0])
    again = cache.alloc(9, 12)
    engine.prefill(served_wide.params, [_prompt(9, 5)], [again])  # the 16 bucket
    assert engine.prefill_programs == engine.kda_kernel_programs_prefill == 2
    assert engine.kernel_fallbacks == 0


def test_c3_a_stale_step_on_a_freed_slot_cannot_corrupt_the_newcomer(served):
    """The default loop keeps a decode step in flight. A request ends on
    EOS while the next step, dispatched with its slot still active, is on
    its way: that step writes the freed slot's row. The slot's next
    request's prefill is dispatched behind it and overwrites the whole
    row (C1), so the newcomer's tokens are a fresh scheduler's."""
    probe = _serve(served)[0]
    first = Request(rid=1, prompt=_prompt(7, 3), max_new_tokens=6)
    probe.run([first])
    eos = first.generated[2]  # ends the same request after three tokens

    def serve(requests):
        sched, engine, cache = _serve(served, max_seqs=1)
        done = sched.run(requests)
        assert all(r.status == "finished" for r in done)
        return sched, engine

    early = Request(rid=1, prompt=_prompt(7, 3), max_new_tokens=6, eos_token=eos)
    late = Request(rid=2, prompt=_prompt(10, 4), max_new_tokens=8)
    sched, engine = serve([early, late])
    assert early.generated == first.generated[:3]
    # the step in flight when EOS was read was thrown away, on slot 0,
    # which the second request then took
    assert sched.stats.decode_slot_steps_discarded >= 1
    alone = Request(rid=2, prompt=_prompt(10, 4), max_new_tokens=8)
    serve([alone])
    assert late.generated == alone.generated
    seq = list(late.prompt) + late.generated[:-1]
    want = np.argmax(_want(served, seq, list(range(9, 17))), -1)
    assert late.generated == want.tolist()


def test_c4_free_has_no_device_work_and_a_recompute_rebuilds_the_state(served):
    _, engine, cache = _serve(served)
    prompt = _prompt(14, 5)
    slot = cache.alloc(14, 30)
    nxt, _ = engine.prefill(served.params, [prompt], [slot])
    seq, tok = list(prompt), int(nxt[0])
    for _ in range(5):
        seq.append(tok)
        tok = int(np.argmax(_step(engine, served, {slot: tok})[slot]))
    held = {key: id(a) for key, a in (
        ((g, n), a) for g, rows in cache.state.items() for n, a in rows.items()
    )}
    kept = _state(cache)
    cache.free(slot)
    assert held == {
        (g, n): id(a) for g, rows in cache.state.items() for n, a in rows.items()
    }
    # preemption by recompute: the history is prefilled again, into
    # whichever slot, and the state is what decode had made of it
    again = cache.alloc(len(seq), 30)
    _, last = engine.prefill(served.params, [seq], [again])
    assert _gap(last[0], _want(served, seq, [len(seq) - 1])[0]) < TOL
    for key, a in _state(cache).items():
        assert np.allclose(a[again], kept[key][slot], atol=1e-5), key


@pytest.mark.parametrize("what,config", [
    ("speculative", dict(spec_draft="ngram")),
    ("chunked-prefill", dict(token_budget=32, chunk_size=16)),
    ("kv_swap", dict(kv_swap=True, kv_swap_bytes=1 << 20)),
    ("int8", dict(kv_dtype="int8")),
    ("prefix_cache", dict(prefix_cache=True)),
    ("adapters", dict(adapters=2)),
])
def test_c5_what_cannot_keep_the_state_is_refused_in_words(served, what, config):
    with pytest.raises(ValueError, match="recurrent"):
        _serve(served, **config)


@pytest.mark.parametrize("call", [
    lambda e, c, p: e._verify_fn(3),
    lambda e, c, p: e._tree_fn(3),
    lambda e, c, p: e._chunk_fn((1, 8)),
    lambda e, c, p: e.prefill_suffix(p, [_prompt(9)], [0], [4]),
    lambda e, c, p: e.require("draft"),
    lambda e, c, p: c.truncate(0, 4),
    lambda e, c, p: c.swap_out(0),
    lambda e, c, p: c.swap_in(0),
    lambda e, c, p: c.import_swap({}),
], ids=["verify", "tree", "chunk", "suffix", "draft", "truncate",
        "swap_out", "swap_in", "import_swap"])
def test_c5_the_step_kinds_and_cache_calls_raise_not_serve(served, call):
    _, engine, cache = _serve(served)
    cache.alloc(9, 12)
    with pytest.raises(ValueError, match="recurrent"):
        call(engine, cache, served.params)


def test_c5_a_serving_mesh_is_refused_in_words():
    model = _model()  # compile_for_serving re-places the model: its own
    # the latent layers' refusal of a mesh speaks first; either is in words
    with pytest.raises(ValueError, match="serving mesh .* is not supported"):
        _serve(model, serve_mesh="1,1")


def test_c5_buckets_must_be_whole_chunks(served):
    with pytest.raises(ValueError, match="whole chunks"):
        _serve(served, prefill_buckets=(12, 64))


# -- the loops ------------------------------------------------------------------


def _requests():
    return [
        Request(rid=i, prompt=_prompt(n, i), max_new_tokens=m)
        for i, (n, m) in enumerate(((6, 9), (19, 5), (3, 12), (11, 7), (8, 4), (27, 6)))
    ]


def test_sync_and_async_loops_are_token_identical_and_the_references(served):
    outs = []
    for serve_async in (True, False):
        sched, engine, _ = _serve(served, serve_async=serve_async)
        reqs = _requests()
        done = sched.run(reqs)
        assert all(r.status == "finished" for r in done)
        outs.append([list(r.generated) for r in reqs])
        if serve_async:
            assert sched.stats.decode_steps_chained > 0
            assert sched.stats.state_rows_decode == engine.state_rows_decode > 0
            assert sched.stats.state_resets_prefill == 6 * 4
        assert engine._decode_jit._cache_size() == 1
    assert outs[0] == outs[1]
    for r, got in zip(_requests(), outs[0]):
        seq = list(r.prompt) + got[:-1]
        n = len(r.prompt)
        want = np.argmax(_want(served, seq, list(range(n - 1, len(seq)))), -1)
        assert got == want.tolist()


def test_the_state_is_priced_and_published(served):
    serve = ServeConfig(
        max_seqs=4, max_seq_len=SEQ, prefill_buckets=BUCKETS, telemetry=True
    )
    sched, engine, cache = build_scheduler(served, serve)
    spec = cache.spec
    assert len(spec.state_guids) == 4 and len(spec.layer_guids) == 1
    per_slot = 4 * 4 * (4 * 16 * 16 + 3 * 3 * 64)
    assert spec.state_bytes_per_slot == per_slot
    assert spec.total_bytes == spec.bytes_per_layer + 4 * per_slot
    assert cache.state[spec.state_guids[0]]["S"].shape == (4, 4, 16, 16)
    assert cache.state[spec.state_guids[0]]["conv"].shape == (4, 3, 192)
    reg = sched.telemetry.registry
    assert reg.gauge("serve_state_layers").value == 4
    assert reg.gauge("serve_state_bytes").value == 4 * per_slot
