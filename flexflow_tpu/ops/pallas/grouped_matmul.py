"""Grouped matmul for a served expert layer's rows (Pallas, TPU).

`ops/moe.py:sparse_moe` sorts its (token, choice) rows by expert and
multiplies each expert's ragged group by that expert's own matrices. A
prefill hands it 6-80 rows an expert and touches (almost) every expert,
a decode step one to a few rows of a third of them: either way the floor
of the three products is the touched experts' weights, read once (1.61 GB
a layer at OLMoE's widths, 2 ms at a v5e's 819 GB/s, against 0.03-0.33 ms
of matrix-unit time). This module is the kernel that works at that floor
(85-97% of it at every served shape, where XLA's expansion of
`jax.lax.ragged_dot` read 48-59% in prefill and 59-82% in decode:
scripts/probe_expert_matmul.py; PERF.md section 6, PR 46):

  * **Expert-major visits over fixed row tiles** — the rows are cut into
    tiles of `tile_rows` and the grid walks the (group, tile) pairs that
    hold a row, in group order (`_schedule`: a group that starts inside a
    tile shares it with its neighbour, and a store mask keeps each to its
    own rows). An expert with no row has no visit and is never fetched;
    rows behind the last group (a layer that holds a share of its experts
    sorts the others' rows there) have none either and are left as they
    were. The grid's length is the number of visits, a runtime value.
  * **A group's weights leave HBM once a call and a column sweep** — they
    stay in `pl.ANY` and the kernel copies a group's `[K, tile_cols]`
    slab itself into one of two VMEM buffers, at the FIRST visit of the
    group BEFORE it (so the copy runs behind all of that group's visits,
    where a BlockSpec's one-step lookahead would start it behind the last
    of them only and leave the DMA idle for the others). `tile_cols` is
    the whole width where two slabs of every operand fit `_WEIGHT_VMEM`.
  * **The row tile follows the rows an expert gets** (`tile_rows()`, from
    the static shapes alone): a visit pays one pass of the slab through
    the matrix unit however few rows it multiplies, so the tile is about
    `_TILE_FACTOR` times the rows a group is expected to have: enough
    that most groups are one or two visits, no more than the matrix unit
    streams for free beside a slab's load.
  * **Gate, up and `silu(gate) * up` are one call** — `_kernel` takes one
    weight operand (rows @ w) or two (silu(rows @ w0) * (rows @ w1)): the
    row tile is read once and the `[rows, f]` products never leave the
    chip. Down is the same body with one operand.
  * **The ambient matmul precision, as any `jnp.matmul` of the model** —
    float32 in, float32 accumulation and out; under the default
    precision the operands are rounded to bfloat16 for ONE pass (what
    XLA's DEFAULT is on a TPU), under `jax.default_matmul_precision(
    "highest")` (or any other name that asks for more than one pass) the
    contraction is Mosaic's fp32. Nothing is stored or accumulated lower.

`use_kernel()` is the gate `sparse_moe` consults: a TPU backend, no
gradient wanted, float32 operands, whole sublane tiles of rows and whole
lane tiles of both widths. Everything else keeps `jax.lax.ragged_dot`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops.pallas import resolve_interpret

SUBLANES = 8
LANES = 128
MAX_TILE_ROWS = 128
_TILE_FACTOR = 2
# two slabs of every weight operand, in bytes: what decides `tile_cols`
_WEIGHT_VMEM = 40 << 20
_ONE_PASS = (None, "default", "fastest", "bfloat16", "BF16_BF16_F32")


def supports(rows: int, k: int, n: int) -> bool:
    """Whether the kernel takes rows [rows, k] against weights [*, k, n]:
    whole sublane tiles of rows, whole lane tiles of both widths (the
    kernel copies `[k, tile_cols]` slabs out of HBM itself, and a row
    tile is a `[tile_rows, k]` block)."""
    return rows >= SUBLANES and rows % SUBLANES == 0 and k % LANES == 0 and n % LANES == 0


def use_kernel(rows: int, hidden: int, expert_hidden: int, dtype, grad: bool) -> bool:
    """`sparse_moe`'s choice, from what it can see: the platform, whether
    a gradient is wanted (the kernel has no VJP), the operands' type and
    the static shapes. True for a serving step's expert layer on a TPU at
    lane-tile widths, prefill and decode alike (the probe found no row
    count at which XLA's call was the faster: no threshold); training and
    the CPU tests' narrow experts keep `jax.lax.ragged_dot`."""
    return (
        not grad
        and jax.default_backend() == "tpu"
        and jnp.dtype(dtype) == jnp.float32
        and supports(rows, hidden, expert_hidden)
        and supports(rows, expert_hidden, hidden)
    )


def tile_rows(rows: int, rows_per_group: float) -> int:
    """The row tile for `rows` rows of which a group is expected to get
    `rows_per_group`: the power of two at or above `_TILE_FACTOR` times
    that, within [SUBLANES, MAX_TILE_ROWS], halved until it divides
    `rows`."""
    want = SUBLANES
    while want < min(MAX_TILE_ROWS, _TILE_FACTOR * rows_per_group):
        want *= 2
    while rows % want:
        want //= 2
    return want


def _tile_cols(k: int, n: int, operands: int, itemsize: int) -> int:
    """The widest divisor of n in whole lane tiles of which two slabs an
    operand fit `_WEIGHT_VMEM`."""
    for sweeps in range(1, n // LANES + 1):
        cols = n // sweeps
        if n % sweeps or cols % LANES:
            continue
        if 2 * operands * k * cols * itemsize <= _WEIGHT_VMEM:
            return cols
    return LANES


def ambient_exact() -> bool:
    """Whether the ambient matmul precision asks for more than one
    bfloat16 pass (read at trace time, like any matmul of the model)."""
    return jax.config.jax_default_matmul_precision not in _ONE_PASS


class _Schedule(NamedTuple):
    starts: jax.Array  # [groups] the first row of each group
    ends: jax.Array  # [groups] one past its last
    upto: jax.Array  # [groups] the visits of this group and those before it
    slot: jax.Array  # [groups] which of the two weight buffers holds it
    group: jax.Array  # [most] the group a visit works for
    tile: jax.Array  # [most] the row tile it works on


def _schedule(group_sizes, rows: int, tm: int) -> _Schedule:
    """The (group, row tile) pairs that hold a row, in group order. A
    group of n rows from row s visits tiles s // tm .. (s + n - 1) // tm;
    an empty group visits none. At most rows / tm + groups - 1 visits, of
    which `upto[-1]` exist. Plain `lax` calls, one each: a serving process
    traces this once a step program, and `jax.numpy`'s wrappers cost a
    program's trace several times what these do."""
    groups = group_sizes.shape[0]
    most = rows // tm + groups - 1
    i32 = jnp.int32

    def full(value, n=groups):
        return lax.full((n,), value, i32)

    sizes = lax.convert_element_type(group_sizes, i32)
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    touched = lax.gt(sizes, full(0))
    first = lax.div(starts, full(tm))
    last = lax.div(lax.sub(ends, full(1)), full(tm))
    count = lax.select(touched, lax.add(lax.sub(last, first), full(1)), full(0))
    upto = lax.cumsum(count)
    # visit v lies past group g where v >= upto[g]: it works for the group
    # it lies past all the earlier ones of, on tile v + shift[group], shift
    # = first - (upto - count), summed from its steps between groups
    visit = lax.iota(i32, most)
    past = lax.convert_element_type(
        lax.ge(
            lax.broadcast_in_dim(visit, (most, groups), (0,)),
            lax.broadcast_in_dim(upto, (most, groups), (1,)),
        ),
        i32,
    )
    shift = lax.sub(first, lax.sub(upto, count))
    step = lax.pad(
        lax.sub(lax.slice(shift, (1,), (groups,)), lax.slice(shift, (0,), (groups - 1,))),
        i32(0), ((0, 1, 0),),
    )
    group = lax.reduce(past, i32(0), lax.add, (1,))
    moved = lax.reduce(
        lax.mul(past, lax.broadcast_in_dim(step, (most, groups), (1,))),
        i32(0), lax.add, (1,),
    )
    return _Schedule(
        starts,
        ends,
        upto,
        lax.bitwise_and(
            lax.sub(lax.cumsum(lax.convert_element_type(touched, i32)), full(1)),
            full(1),
        ),
        lax.min(group, full(groups - 1, most)),
        lax.clamp(i32(0), lax.add(visit, moved), i32(rows // tm - 1)),
    )


def _dot(x, w, exact: bool):
    """x @ w at the ambient precision. Not `mxu_dot`: handed float32
    operands without a precision, Mosaic multiplies them in fp32 passes
    (megablox's `gmm` read 35 TFLOP/s so), where XLA's DEFAULT is one
    bfloat16 pass; so the rounding is done here, by name."""
    dims = (((1,), (0,)), ((), ()))
    if exact:
        return lax.dot_general(
            x, w, dims, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    return lax.dot_general(
        lax.convert_element_type(x, jnp.bfloat16),
        lax.convert_element_type(w, jnp.bfloat16), dims,
        precision=lax.Precision.DEFAULT, preferred_element_type=jnp.float32,
    )


def _kernel(
    starts, ends, upto, slot, group, tile, x_ref, *refs,
    operands: int, groups: int, most: int, tm: int, tn: int, exact: bool,
):
    weights, out_ref = refs[:operands], refs[operands]
    buffers, sem = refs[operands + 1:-1], refs[-1]
    sweep, visit = pl.program_id(0), pl.program_id(1)
    g = group[visit]
    held = slot[g]
    col = pl.multiple_of(sweep * tn, LANES)

    def copies(of, into):
        return [
            pltpu.make_async_copy(
                w.at[of, :, pl.ds(col, tn)], buf.at[into], sem.at[i, into]
            )
            for i, (w, buf) in enumerate(zip(weights, buffers))
        ]

    @pl.when(visit == 0)
    def _():
        for copy in copies(g, held):
            copy.start()

    arrives = lax.bitwise_or(visit == 0, group[lax.max(visit - 1, 0)] != g)

    @pl.when(arrives)
    def _():
        # the next group's slab goes behind ALL of this group's visits:
        # the group of the first visit past this group's, if there is one
        after = upto[g]

        @pl.when(after < upto[groups - 1])
        def _():
            for copy in copies(group[lax.min(after, most - 1)], 1 - held):
                copy.start()

        for copy in copies(g, held):
            copy.wait()

    x = x_ref[...]
    products = [_dot(x, buf[held], exact) for buf in buffers]
    y = products[0]
    if operands == 2:
        y = lax.mul(lax.mul(y, lax.logistic(y)), products[1])  # silu(gate) * up
    row = lax.add(
        lax.broadcasted_iota(jnp.int32, (tm, tn), 0),
        lax.broadcast(tile[visit] * tm, (tm, tn)),
    )
    mine = lax.bitwise_and(
        lax.ge(row, lax.broadcast(starts[g], (tm, tn))),
        lax.lt(row, lax.broadcast(ends[g], (tm, tn))),
    )
    out_ref[...] = lax.select(mine, y, out_ref[...])


def _grouped_call(rows, weights: Tuple[jax.Array, ...], plan: _Schedule, tm, exact, interpret):
    r, k = rows.shape
    groups, _, n = weights[0].shape
    operands = len(weights)
    itemsize = weights[0].dtype.itemsize
    tn = _tile_cols(k, n, operands, itemsize)
    vmem = (
        2 * operands * k * tn * itemsize  # the slabs
        + operands * k * tn * 2  # one of each rounded for its pass
        + 2 * tm * (k + tn) * 4 + (operands + 1) * tm * tn * 4
        + (8 << 20)
    )
    kernel = functools.partial(
        _kernel, operands=operands, groups=groups, most=plan.group.shape[0],
        tm=tm, tn=tn, exact=exact,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(n // tn, plan.upto[groups - 1]),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, *plan: (plan[-1][v], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * operands,
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, *plan: (plan[-1][v], j)),
            scratch_shapes=[pltpu.VMEM((2, k, tn), w.dtype) for w in weights]
            + [pltpu.SemaphoreType.DMA((operands, 2))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * operands * r * k * n,
            bytes_accessed=itemsize * operands * groups * k * n + 4 * r * (k + n),
            transcendentals=r * n if operands == 2 else 0,
        ),
        interpret=interpret,
        name="grouped_matmul" if operands == 1 else "grouped_gate_up",
    )(*plan, rows, *weights)


@functools.partial(jax.jit, static_argnames=("tm", "exact", "interpret"))
def _grouped_chain(rows, stages, group_sizes, *, tm, exact, interpret):
    """`rows` through `stages`, each one weight operand or a pair, over
    one schedule. An inner `jax.jit`: a program traces it once, not once
    a layer."""
    plan = _schedule(group_sizes, rows.shape[0], tm)
    for weights in stages:
        rows = _grouped_call(rows, weights, plan, tm, exact, interpret)
    return rows


def _grouped_run(rows, stages, group_sizes, rows_per_group, tile, interpret):
    if tile is None:
        groups = group_sizes.shape[0]
        tile = tile_rows(
            rows.shape[0],
            rows.shape[0] / groups if rows_per_group is None else rows_per_group,
        )
    return _grouped_chain(
        rows, stages, group_sizes, tm=tile, exact=ambient_exact(),
        interpret=resolve_interpret(interpret),
    )


def grouped_matmul(
    rows, weights, group_sizes, *, rows_per_group=None, tile=None, interpret=None
):
    """rows [R, K] float32 sorted by group, `weights` one [E, K, N] array
    (-> rows @ w of the row's group) or a pair (-> silu(rows @ w0) *
    (rows @ w1)), group_sizes [E] int32 -> [R, N] float32. Rows past the
    last group belong to nobody: their outputs hold anything.
    `rows_per_group`: the rows a group is expected to get where that is
    not R / E (a layer that holds a share of its experts); `tile` names
    the row tile outright (the probe's sweep)."""
    weights = tuple(weights) if isinstance(weights, (tuple, list)) else (weights,)
    return _grouped_run(rows, (weights,), group_sizes, rows_per_group, tile, interpret)


def expert_mlp(
    rows, w_gate, w_up, w_down, group_sizes, *, rows_per_group=None, tile=None,
    interpret=None,
):
    """An expert layer's three products on sorted rows: (silu(rows @
    w_gate) * (rows @ w_up)) @ w_down, each row by its group's matrices,
    [R, d] float32: two kernel calls over one schedule."""
    return _grouped_run(
        rows, ((w_gate, w_up), (w_down,)), group_sizes, rows_per_group, tile,
        interpret,
    )
