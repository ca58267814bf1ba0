"""One decode step of the gated delta rule over the LIVE slots' state,
in place (Pallas, TPU).

`ops/linear_attention.py:kda_step` advances the state of every row it is
handed, and a serving decode step hands it every slot's: all `max_seqs`
rows of `[H, d, d]` float32 go through the program, and a `where` over
them hands the idle slots' rows back, a second pass. The step's floor is
the LIVE rows, once in and once out (`benchmarks/lib/kda_counts.py:
state_step_bytes`), and at the occupancy the longform cell runs at less
than half the rows are live. This kernel works at that floor:

  * **Only live rows are visited** — the live slots' numbers are compacted
    inside the program (`_live_rows`, plain `lax` calls) and the grid
    walks them through a scalar-prefetch index map; its length is the
    number of live slots, a runtime value. A slot that is not live is
    never fetched.
  * **In place** — the state operand is aliased to the output, so a row
    the grid never visits is never read, never written, and stays
    bit-equal. The step program donates the per-slot state already.
  * **One pass over a block in VMEM does the whole recurrence** — a block
    is one slot by `heads_per_block` heads, `[hb, d, d]` float32 in and
    out. Per head: decay by `exp(g)`, `k^T S`, the rank-one correction by
    `beta (v - k^T S)`, `S^T q`; the same float32 sums as `kda_step`, no
    matmul. The three vectors that run along the state's rows (the decay,
    k, q) reach the kernel lane-major, a head a row, and each is turned
    onto the sublanes by transposing its broadcast. The block's copy in
    and out is all the kernel's time, 76-80% of the live rows' byte floor
    at every live count: a body that only scales the block reads the same
    (scripts/probe_kda_step.py; PERF.md section 6, PR 48).

`supports()` is the gate `kda_step_live` consults (`ops/
linear_attention.py`): a TPU backend, a float32 state, heads of whole
lane tiles, whole sublane tiles of heads. Everything else keeps
`kda_step` and the `where`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.pallas import resolve_interpret

SUBLANES = 8
LANES = 128
# the most heads of one grid step: 2 MiB of state each way at d = 128,
# twice for the pipeline's two buffers
MAX_HEADS_PER_BLOCK = 32
NAME = "kda_state_step"  # the Mosaic call, as a profile names it


def supports(heads: int, head_dim: int, dtype) -> bool:
    """Whether the kernel takes a state [slots, heads, head_dim, head_dim]
    of `dtype`: float32, a head's [d, d] in whole lane tiles, the heads in
    whole sublane tiles (a block's vectors are `[heads, d]` tiles)."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and head_dim % LANES == 0
        and heads % SUBLANES == 0
    )


def use_kernel(heads: int, head_dim: int, dtype) -> bool:
    """`kda_step_live`'s choice, from what it can see: the platform, the
    state's type and the static shapes."""
    return jax.default_backend() == "tpu" and supports(heads, head_dim, dtype)


def heads_per_block(heads: int) -> int:
    """The most heads of a block, in whole sublane tiles and dividing
    `heads`: fewer, longer grid steps (a step costs a third of a
    microsecond, and the first block's fetch and the last one's write
    are not hidden)."""
    hb = min(heads, MAX_HEADS_PER_BLOCK)
    while heads % hb or hb % SUBLANES:
        hb -= 1
    return hb


def _live_rows(active):
    """active bool [slots] -> (ids int32 [slots]: the live slots' numbers
    in order, then the others'; n int32 [1]: how many are live). A stable
    sort of the slot numbers by idleness: plain `lax` calls, as
    `grouped_matmul._schedule` (the probe read the same time for a
    [slots, slots] match summed by rank)."""
    slots = active.shape[0]
    i32 = jnp.int32
    live = lax.convert_element_type(active, i32)
    _, ids = lax.sort(
        (lax.sub(lax.full((slots,), 1, i32), live), lax.iota(i32, slots)),
        num_keys=1, is_stable=True,
    )
    return ids, lax.reshape(lax.reduce(live, i32(0), lax.add, (0,)), (1,))


def _kernel(ids, n, g_ref, k_ref, q_ref, v_ref, beta_ref, s_ref, o_ref, out_ref, *, hb, d):
    del ids
    live = pl.program_id(0) < n[0]

    def column(row):  # [1, d] along the lanes -> [d, d]: row[c] at [c, :]
        return jnp.broadcast_to(row, (d, d)).T

    @pl.when(live)
    def _():
        decay = jnp.exp(g_ref[0])
        for h in range(hb):
            rows = slice(h, h + 1)
            k = column(k_ref[0, rows])
            decayed = column(decay[rows]) * s_ref[0, h]
            u = beta_ref[0, rows] * (
                v_ref[0, rows] - jnp.sum(k * decayed, axis=0, keepdims=True)
            )
            new = decayed + k * u
            out_ref[0, h] = new
            o_ref[0, rows] = jnp.sum(
                column(q_ref[0, rows]) * new, axis=0, keepdims=True
            )

    @pl.when(jnp.logical_not(live))
    def _():
        # no slot is live: the grid's one visit hands slot `ids[0]` back
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _step(g, k, q, v, beta, state, active, *, hb, interpret):
    """The vectors [slots, H, d] (beta along d too), state [slots, H, d,
    d], active bool [slots] -> (o [slots, H, d], the new state). An inner
    `jax.jit`: a program traces it once, not once a layer."""
    slots, heads, d, _ = state.shape
    ids, n = _live_rows(active)
    vector = pl.BlockSpec((1, hb, d), lambda i, j, ids, n: (ids[i], j, 0))
    block = pl.BlockSpec((1, hb, d, d), lambda i, j, ids, n: (ids[i], j, 0, 0))
    # No `cost_estimate`: told the call's bytes, XLA copies the WHOLE
    # state into fast memory ahead of it (`slice-start`, 64 MiB a layer
    # whatever is live: seen in the probe's program, PR 48)
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, d=d),
        out_shape=(
            jax.ShapeDtypeStruct((slots, heads, d), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lax.max(n[0], jnp.int32(1)), heads // hb),
            in_specs=[vector] * 5 + [block],
            out_specs=[vector, block],
        ),
        # the state: operand 7, behind the two prefetched scalars and the
        # five vectors
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * hb * d * d * 4 + (16 << 20),
        ),
        interpret=interpret,
        name=NAME,
    )(ids, n, g, k, q, v, beta, state)


def kda_step_rows(q, k, v, g, beta, state, active, *, heads=None, interpret=None):
    """`kda_step` for the rows whose `active` flag is set, in place: q, k,
    v, g [b, H, d], beta [b, H], state [b, H, d, d] float32, active bool
    [b] -> (o [b, H, d], zeros where not active; the new state, every
    other row bit-equal to what came in). `heads` names the heads of a
    block outright (the probe's sweep)."""
    f32 = jnp.float32
    o, new = _step(
        g.astype(f32), k.astype(f32), q.astype(f32), v.astype(f32),
        jnp.broadcast_to(beta.astype(f32)[..., None], q.shape), state, active,
        hb=heads or heads_per_block(state.shape[1]),
        interpret=resolve_interpret(interpret),
    )
    # a row the grid did not visit holds whatever the buffer held
    o = jnp.where(active[:, None, None], o, 0.0)
    return o.astype(q.dtype), new
