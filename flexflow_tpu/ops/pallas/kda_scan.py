"""A prefill's gated delta-rule scan over a packed row, a head's state in
fast memory from chunk to chunk, each request's final state written
straight into its slot's row (Pallas, TPU).

`ops/linear_attention.py:kda_chunked` is a `lax.scan` over the row's
chunks: some 128 small instructions a chunk and layer whose carried state
`[H, d, d]` goes out to HBM after every chunk (the `states` array, one
state a chunk), and the serving engine then scatters each admitted
request's last one into its slot's row of the per-slot state. This kernel
is the same chunked algorithm (`_kda_chunk_terms` and `kda_chunked.one`:
the running sum of g, decays that are exponentials of non-positive
differences only, A and B through reference points, T by substitution,
the three products with the state and B u) as one call a layer:

  * **The grid walks the row's chunks in order** under blocks of
    `heads_per_block` heads; a head's `[d, d]` float32 state lives in VMEM
    scratch and never goes to HBM between two chunks. A chunk that starts
    a prompt (`fresh`) starts from zeros. A chunk's count of live tokens
    is prefetched: a chunk of padding is skipped outright (its output is
    zeros: nobody reads it) and a partial one masks k, g and beta behind
    its last live token, which makes those tokens no-ops.
  * **A chunk's whole body in one visit**: q, k, v, g come in once as
    `[C, hb, d]` blocks of the row as it lies (`[T, H, d]`: no relayout; a
    head's `[C, d]` is a sublane-strided load), and o goes out once the
    same way.
  * **A block's heads at once**: a head's body is chains of small
    dependent steps (15 substitution steps, 7 dependent products), which
    one head at a time left the chip waiting on: 3.7 us a head and chunk,
    1.7 with the eight heads' `[hb, C, .]` arrays worked together (the
    probe, PERF.md section 6, PR 52).
  * **Lane reductions are what the rest costs**, so there are fewer than
    in `_kda_chunk_terms`: the elementwise decays are an OCTET's (8 steps
    over `[hb C / 8, 8, d]`, not 16), the seven octets below the diagonal
    come through reference points (the keys an octet meets are the last
    octet's, decayed once more), an octet's inverse takes 7 substitution
    steps and a sub-block's comes from its two octets' by two products.
  * **Each request's state goes straight to its slot's row**: the
    per-slot state `[slots, H, d, d]` is aliased to the output and never
    read; the output block of a chunk is the row its request owns
    (prefetched), so the pipeline writes a row back once, after the
    request's last chunk, and rows of slots the admission does not own
    are never touched and stay bit-equal.

Precision is `kda_chunked`'s: float32 throughout; the products that stand
where elementwise float32 sums stood (the running sum of g, A's and B's
blocks, T and its two products) as fp32 contractions whatever the ambient
precision; the three products with the state and B u at the ambient one
(one bfloat16 pass as XLA's DEFAULT is on a TPU, fp32 under
`jax.default_matmul_precision("highest")`).

`use_kernel()` is the gate `kda_chunked_rows` consults (`ops/
linear_attention.py`). No `cost_estimate` on the call: see `kda_step.py`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.pallas import resolve_interpret
from flexflow_tpu.ops.pallas.grouped_matmul import ambient_exact
from flexflow_tpu.ops.pallas.kda_step import LANES, SUBLANES

SUB = 16  # tokens of a sub-block of a chunk: `linear_attention._SUB`
OCTET = 8  # tokens of the part of it whose decays are elementwise
HEADS_PER_BLOCK = 8
NAME = "kda_chunk_scan"  # the Mosaic call, as a profile names it


def supports(heads: int, head_dim: int, chunk: int, dtype) -> bool:
    """Whether the kernel takes a state [slots, heads, head_dim, head_dim]
    of `dtype` and chunks of `chunk` tokens: float32, a head's [d, d] in
    whole lane tiles, whole sublane tiles of heads, whole sub-blocks."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and head_dim % LANES == 0
        and heads % SUBLANES == 0
        and chunk % SUB == 0
    )


def use_kernel(heads: int, head_dim: int, chunk: int, dtype) -> bool:
    """`kda_chunked_rows`' choice, from what it can see: the platform, the
    state's type and the static shapes."""
    return jax.default_backend() == "tpu" and supports(
        heads, head_dim, chunk, dtype
    )


def heads_per_block(heads: int) -> int:
    """The heads of a block: as many as are worked at once, in whole
    sublane tiles and dividing `heads`."""
    hb = min(heads, HEADS_PER_BLOCK)
    while heads % hb or hb % SUBLANES:
        hb -= 1
    return hb


def _exact(a, b, dims=((2,), (1,))):
    """A batched product as an fp32 contraction (`HIGHEST`)."""
    return lax.dot_general(
        a, b, (dims, ((0,), (0,))), precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _ambient(a, b, exact, dims=((2,), (1,))):
    """A batched product at the ambient precision: an fp32 contraction, or
    one bfloat16 pass with float32 accumulation (XLA's DEFAULT on a TPU)."""
    if exact:
        return _exact(a, b, dims)
    return lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), (dims, ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _chunk(q, k, v, g, beta, live, s0, exact):
    """One chunk of a block's heads, all at once (a head's chain of small
    dependent steps is latency, and the heads' chains are independent): q,
    k, v, g [hb, C, d], beta [hb, 1, C] (the tokens along the lanes),
    `live` the count of the chunk's tokens that are someone's, s0 [hb, d,
    d] -> (o [hb, C, d], the states after the chunk). Elementwise work
    runs on `[hb C / 8, 8, .]` views, an octet a leading index, so that an
    octet's row j is one slice for all of them."""
    f32, i32 = jnp.float32, jnp.int32
    hb, c, d = k.shape
    m = c // SUB

    def sub(x):  # [hb, C, w] -> [hb C / 8, 8, w]
        return x.reshape(hb * c // OCTET, OCTET, x.shape[-1])

    def whole(x):  # and back
        return x.reshape(hb, c, x.shape[-1])

    # of a `sub` view [., 8, C]: its row and its column in the chunk
    shape = (hb * c // OCTET, OCTET, c)
    row = (
        lax.broadcasted_iota(i32, shape, 0) % (c // OCTET) * OCTET
        + lax.broadcasted_iota(i32, shape, 1)
    )
    col = lax.broadcasted_iota(i32, shape, 2)

    on = lax.broadcasted_iota(i32, (1, c, 1), 1) < live
    k, g = jnp.where(on, k, 0.0), jnp.where(on, g, 0.0)
    # beta along the lanes (a column's token) and, turned, down the rows
    beta_j = jnp.where(lax.broadcasted_iota(i32, (1, 1, c), 2) < live, beta, 0.0)
    eye = (
        lax.broadcasted_iota(i32, (1, c, c), 1)
        == lax.broadcasted_iota(i32, (1, c, c), 2)
    )
    beta = jnp.sum(jnp.where(eye, beta_j, 0.0), -1, keepdims=True)
    tri = (
        lax.broadcasted_iota(i32, (hb, c, c), 1)
        >= lax.broadcasted_iota(i32, (hb, c, c), 2)
    ).astype(f32)
    run = _exact(tri, g)  # G: the running sum of g
    # A and B inside an octet of tokens, elementwise: [t, j] holds the pair
    # (t, token j % OCTET of t's octet). exp(-|G_t - G_j|) is the decay
    # from the earlier to the later of the two, so the sums with k are
    # SYMMETRIC in an octet: below its diagonal A, above it A transposed
    runs, ks, qs = sub(run), sub(k), sub(q)
    place = col % OCTET
    a_in = b_in = jnp.zeros(row.shape, f32)
    for j in range(OCTET):
        diff = runs - runs[:, j: j + 1]
        kd = ks[:, j: j + 1] * jnp.exp(jnp.minimum(diff, -diff))
        here = place == j
        a_in = jnp.where(here, jnp.sum(ks * kd, -1, keepdims=True), a_in)
        b_in = jnp.where(here, jnp.sum(qs * kd, -1, keepdims=True), b_in)
    # between octets, one product an octet through G_ref, G at the last
    # token before it: exp(G_t - G_ref) exp(G_ref - G_j), both <= 1. The
    # keys an octet meets are the last octet's, decayed by that octet's
    # own g, and the last octet itself
    a_out = b_out = [jnp.zeros((hb, OCTET, c), f32)]
    far = ref = None
    for i in range(1, c // OCTET):
        rows = slice(i * OCTET, (i + 1) * OCTET)
        last = slice((i - 1) * OCTET, i * OCTET)
        earlier, ref = ref, run[:, i * OCTET - 1: i * OCTET]
        new = k[:, last] * jnp.exp(ref - run[:, last])
        far = new if far is None else jnp.concatenate(
            [far * jnp.exp(ref - earlier), new], 1
        )
        near = jnp.exp(run[:, rows] - ref)
        out = _exact(
            jnp.concatenate([k[:, rows] * near, q[:, rows] * near], 1),
            jnp.concatenate([far, jnp.zeros((hb, c - i * OCTET, d), f32)], 1),
            ((2,), (2,)),
        )
        a_out = a_out + [out[:, :OCTET]]
        b_out = b_out + [out[:, OCTET:]]
    a_out, b_out = sub(jnp.concatenate(a_out, 1)), sub(jnp.concatenate(b_out, 1))
    same, before = col // OCTET == row // OCTET, col // OCTET < row // OCTET
    bm = jnp.where(before, b_out, jnp.where(same & (col <= row), b_in, 0.0))
    low = sub(beta) * jnp.where(
        before, a_out, jnp.where(same & (col < row), a_in, 0.0)
    )  # L = diag(beta) tril(A, -1)
    # T = (I + L)^-1. An octet's own inverse X by substitution, column by
    # column from the last (X (I + L) = I: column c is e_c less X's later
    # columns times L's column c, which lies along the lanes in L
    # transposed); a sub-block's from its two octets' (its lower left is
    # -X_2 L_21 X_1); then block forward substitution over the sub-blocks
    lt = jnp.where(
        same & (col > row),
        a_in * sub(jnp.broadcast_to(beta_j, (hb, c, c))), 0.0,
    )
    inv = (col == row).astype(f32)
    for j in range(OCTET - 2, -1, -1):
        head = (row % OCTET == j).astype(f32)
        tail = jnp.sum(inv * lt[:, j: j + 1], -1, keepdims=True)
        inv = jnp.where(same & (place == j), head - tail, inv)
    pair = col // SUB == row // SUB
    inv = whole(inv)
    inv = inv - _exact(inv, _exact(whole(jnp.where(pair & before, low, 0.0)), inv))
    l_out = whole(jnp.where(pair, 0.0, low))
    unit = jnp.broadcast_to(eye.astype(f32), (hb, c, c))
    zeros = jnp.zeros((hb, SUB, c), f32)
    blocks = [inv[:, :SUB]]
    for i in range(1, m):
        rows = slice(i * SUB, (i + 1) * SUB)
        done = jnp.concatenate(blocks + [zeros] * (m - i), 1)
        rest = unit[:, rows] - _exact(l_out[:, rows], done)
        placed = jnp.concatenate([zeros] * i + [rest] + [zeros] * (m - 1 - i), 1)
        blocks.append(_exact(inv[:, rows], placed))
    t = jnp.concatenate(blocks, 1)
    grown = jnp.exp(run)
    w = _exact(t, beta * k * grown)
    uv = _exact(t, beta * v)
    left = k * jnp.exp(run[:, c - 1:] - run)
    u = uv - _ambient(w, s0, exact)
    o = _ambient(q * grown, s0, exact) + _ambient(whole(bm), u, exact)
    # exp G_last down the rows of the state
    last = jnp.swapaxes(jnp.broadcast_to(grown[:, c - 1:], (hb, d, d)), 1, 2)
    return o, last * s0 + _ambient(left, u, exact, ((1,), (1,)))


def _kernel(
    fresh, count, owner, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
    o_ref, out_ref, state, *, hb, d, exact,
):
    del owner, s_ref  # the index map's; the row's old content is never read
    i = pl.program_id(1)
    live = count[i]

    @pl.when(live == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live > 0)
    def _():
        f32 = jnp.float32

        def heads(ref):  # [C, hb, d] as it lies -> [hb, C, d]
            return jnp.stack([ref[:, h, :].astype(f32) for h in range(hb)])

        s0 = jnp.where(fresh[i] > 0, 0.0, state[...])
        o, s1 = _chunk(
            heads(q_ref), heads(k_ref), heads(v_ref), heads(g_ref),
            beta_ref[0][:, None, :].astype(f32), live, s0, exact,
        )
        for h in range(hb):
            o_ref[:, h, :] = o[h].astype(o_ref.dtype)
        state[...] = s1
        # the request's row: written back when the next request's first
        # chunk (or the grid's end) moves the block on
        out_ref[0] = s1


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "exact", "interpret"))
def _scan(q, k, v, g, beta, state, fresh, count, owner, *, chunk, hb, exact, interpret):
    """q, k, v, g [T, H, d], beta [T / chunk, H, chunk], state [slots, H,
    d, d], the three per-chunk int32 vectors -> (o, the new state). An
    inner `jax.jit`: a program traces it once, not once a layer."""
    slots, heads, d, _ = state.shape
    tokens = q.shape[0]
    wide = pl.BlockSpec((chunk, hb, d), lambda j, i, *_: (i, j, 0))
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, d=d, exact=exact),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(heads // hb, tokens // chunk),
            in_specs=[wide] * 4 + [
                pl.BlockSpec((1, hb, chunk), lambda j, i, *_: (i, j, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                wide,
                pl.BlockSpec(
                    (1, hb, d, d),
                    lambda j, i, fresh, count, owner: (owner[i], j, 0, 0),
                ),
            ],
            scratch_shapes=[pltpu.VMEM((hb, d, d), jnp.float32)],
        ),
        # the state: operand 8, behind the three prefetched vectors and
        # the five streams
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20,
        ),
        interpret=interpret,
        name=NAME,
    )(fresh, count, owner, q, k, v, g, beta, state)


def kda_scan_rows(
    q, k, v, g, beta, state, live, fresh, slots, last_at, *, chunk, interpret=None
):
    """The recurrence over ONE packed row of requests laid in order, each
    from the zero state, its final state written to its slot's row in
    place: q, k, v, g [T, H, d], beta [T, H], state [slots, H, d, d]
    float32 (aliased, never read), live bool [T] (a token that is
    someone's; a request's live tokens are the first of its chunks), fresh
    bool [T / chunk] (the chunk starts a request), slots and last_at int32
    [requests] (each request's slot and where its last token stands; the
    rows past the last request name a slot out of range) -> (o [T, H, d],
    zeros in a chunk without a live token; the new state, the rows of
    every other slot bit-equal)."""
    tokens, h, d = q.shape
    n = tokens // chunk
    hb = heads_per_block(h)
    i32 = jnp.int32
    count = jnp.sum(live.reshape(n, chunk).astype(i32), axis=1)
    # the request a chunk belongs to: as many as ended before it. A chunk
    # of padding behind the last stays on the last one's row: the output
    # block does not move, and the row is written back once
    admitted = slots < state.shape[0]
    ends = jnp.where(admitted, last_at // chunk, n)
    owner = jnp.minimum(
        jnp.sum(ends[None, :] < jnp.arange(n)[:, None], axis=1),
        jnp.sum(admitted) - 1,
    )
    o, new = _scan(
        q, k, v, g, jnp.moveaxis(beta.reshape(n, chunk, h), 2, 1),
        state, fresh.astype(i32), count, slots.astype(i32)[owner],
        chunk=chunk, hb=hb, exact=ambient_exact(),
        interpret=resolve_interpret(interpret),
    )
    return o, new
