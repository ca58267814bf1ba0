"""ops/pallas/grouped_matmul.py on the CPU, in the Pallas interpreter: the
kernel against `jax.lax.ragged_dot` and against a plain loop of one
`jnp.matmul` an expert, at the three expert configurations' widths with
few experts and rows; the schedule it walks; and `sparse_moe` through the
chooser (`ops.moe.expert_products`), whose outputs and counts do not
depend on which of the two makes the products.

At the default ambient precision the kernel rounds the operands of each
product to bfloat16 for one pass, as XLA's DEFAULT does on a TPU (the CPU
backend multiplies float32 as it is): the references round the same
operands and multiply exactly. At `highest` nothing is rounded and the
kernel agrees to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import moe
from flexflow_tpu.ops.pallas import grouped_matmul as gm

# hidden, expert width: OLMoE, Kanana (six lane tiles), Kimi-Linear (18)
WIDTHS = {"olmoe": (2048, 1024), "kanana": (2048, 768), "kimi": (2304, 1024)}
ROWS = 64
# group sizes over 5 experts and 64 rows
GROUPS = {
    "empty_groups": (0, 23, 0, 41, 0),
    "group_of_one": (1, 30, 1, 31, 1),
    "no_multiple_of_a_tile": (13, 7, 19, 3, 22),
    "rows_behind_the_last_group": (5, 0, 11, 9, 2),  # 27 live of 64
    "one_group_has_all": (0, 0, 64, 0, 0),
}
TOLERANCE = {"default": 2e-3, "highest": 2e-5}


def _round(a, precision):
    if precision == "highest":
        return a
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _ragged(rows, w, sizes):
    return jax.lax.ragged_dot(
        rows, w, sizes, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _loop(rows, w, sizes):
    out, at = np.zeros((rows.shape[0], w.shape[2]), np.float32), 0
    for g, n in enumerate(np.asarray(sizes)):
        out[at:at + n] = jnp.matmul(
            rows[at:at + n], w[g], precision=jax.lax.Precision.HIGHEST
        )
        at += n
    return jnp.asarray(out)


def _chain(product, rows, w_gate, w_up, w_down, sizes, precision):
    rows, w_gate, w_up, w_down = (
        _round(a, precision) for a in (rows, w_gate, w_up, w_down)
    )
    hidden = jax.nn.silu(product(rows, w_gate, sizes)) * product(rows, w_up, sizes)
    return hidden, product(_round(hidden, precision), w_down, sizes)


def _operands(config, seed=0):
    d, f = WIDTHS[config]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(keys[0], (ROWS, d)),
        jax.random.normal(keys[1], (5, d, f)) * d ** -0.5,
        jax.random.normal(keys[2], (5, d, f)) * d ** -0.5,
        jax.random.normal(keys[3], (5, f, d)) * f ** -0.5,
    )


def _close(got, want, live, tolerance):
    got, want = np.asarray(got)[:live], np.asarray(want)[:live]
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) <= tolerance * np.max(np.abs(want))


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("config", WIDTHS)
def test_expert_mlp_against_ragged_dot_and_a_loop(config, groups, precision):
    rows, w_gate, w_up, w_down = _operands(config)
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    live = int(sizes.sum())
    with jax.default_matmul_precision(precision):
        got = gm.expert_mlp(rows, w_gate, w_up, w_down, sizes)
        hidden = gm.grouped_matmul(rows, (w_gate, w_up), sizes)
    assert got.shape == rows.shape and got.dtype == jnp.float32
    for product in (_ragged, _loop):
        want_hidden, want = _chain(
            product, rows, w_gate, w_up, w_down, sizes, precision
        )
        _close(hidden, want_hidden, live, TOLERANCE[precision])
        _close(got, want, live, TOLERANCE[precision])


@pytest.mark.parametrize("tile", [8, 16, 32, 64])
@pytest.mark.parametrize("groups", GROUPS)
def test_one_product_at_every_row_tile(groups, tile):
    """rows @ w of the row's group whatever the row tile: a group that
    starts inside a tile shares it with its neighbours."""
    rows, w_gate, _, _ = _operands("kanana", seed=1)
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = gm.grouped_matmul(rows, w_gate, sizes, tile=tile)
    _close(got, _ragged(rows, w_gate, sizes), int(sizes.sum()), 2e-5)


@pytest.mark.parametrize("tile", [8, 16, 32])
@pytest.mark.parametrize("groups", GROUPS)
def test_schedule_visits_every_row_once_and_no_empty_group(groups, tile):
    sizes = np.asarray(GROUPS[groups], np.int32)
    plan = gm._schedule(jnp.asarray(sizes), ROWS, tile)
    visits = int(plan.upto[-1])
    group, where = np.asarray(plan.group)[:visits], np.asarray(plan.tile)[:visits]
    offsets = np.append(np.asarray(plan.starts), sizes.sum())
    np.testing.assert_array_equal(np.asarray(plan.ends) - np.asarray(plan.starts), sizes)
    assert visits <= ROWS // tile + len(sizes) - 1
    assert (sizes[group] > 0).all()
    assert (np.diff(group) >= 0).all() and (np.diff(where) >= 0).all()
    covered = np.zeros(ROWS, np.int32)
    for g, t in zip(group, where):
        lo, hi = max(offsets[g], t * tile), min(offsets[g + 1], (t + 1) * tile)
        assert lo < hi  # a visit always holds a row of its group
        covered[lo:hi] += 1
    assert (covered[:sizes.sum()] == 1).all() and not covered[sizes.sum():].any()
    # the weight buffers alternate over the groups that have a row, and
    # each names the next of them: a group's slab is copied once
    touched = np.flatnonzero(sizes)
    assert list(np.asarray(plan.slot)[touched]) == [i % 2 for i in range(len(touched))]
    # the visit after a group's last is the first of the next such group
    upto = np.asarray(plan.upto)
    assert list(np.asarray(plan.group)[upto[touched[:-1]]]) == list(touched[1:])


def test_the_row_tile_follows_the_rows_a_group_gets():
    # OLMoE's buckets (8 of 64), Kanana's (6 of 128), Kimi-Linear's (8 of 256)
    assert [gm.tile_rows(t * 8, t * 8 / 64) for t in (128, 256, 640)] == [32, 64, 128]
    assert [gm.tile_rows(t * 6, t * 6 / 128) for t in (128, 256, 640)] == [16, 32, 64]
    assert [gm.tile_rows(t * 8, t * 8 / 256) for t in (256, 512, 1024, 1600)] == [
        16, 32, 64, 128,
    ]
    assert gm.tile_rows(24, 100.0) == 8  # halved until it divides the rows


def test_the_gate_reads_platform_gradient_type_and_shapes(monkeypatch):
    f32 = jnp.float32
    assert not gm.use_kernel(1024, 2048, 1024, f32, grad=False)  # no TPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm.use_kernel(1024, 2048, 1024, f32, grad=False)
    assert gm.use_kernel(768, 2048, 768, f32, grad=False)
    assert gm.use_kernel(12800, 2304, 1024, f32, grad=False)
    assert not gm.use_kernel(1024, 2048, 1024, f32, grad=True)
    assert not gm.use_kernel(1024, 2048, 1024, jnp.bfloat16, grad=False)
    for rows in (96, 128, 256):  # a decode step's rows: no threshold
        assert gm.use_kernel(rows, 2048, 1024, f32, grad=False)
    assert not gm.use_kernel(12, 2048, 1024, f32, grad=False)  # no sublane tile
    assert not gm.use_kernel(1024, 64, 32, f32, grad=False)  # the CPU tests' experts
    assert not gm.use_kernel(1024, 2048, 1000, f32, grad=False)


@pytest.fixture
def through_the_kernel(monkeypatch):
    """`expert_products` chooses as on the chip, and the kernel it takes
    runs in the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gm, "resolve_interpret", lambda interpret: True)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all_experts", "a_held_share"])
@pytest.mark.parametrize("precision", ["default", "highest"])
def test_sparse_moe_is_the_same_layer_through_the_chooser(
    through_the_kernel, precision, held
):
    d, f, n, k, tokens = 256, 128, 8, 4, 128
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    stacked = n if held is None else held[1]
    ws = [
        jax.random.normal(keys[0], (d, n)),
        jax.random.normal(keys[1], (stacked, d, f)) * d ** -0.5,
        jax.random.normal(keys[2], (stacked, d, f)) * d ** -0.5,
        jax.random.normal(keys[3], (stacked, f, d)) * f ** -0.5,
    ]
    params = dict(num_experts=n, k=k, expert_hidden=f, renormalise=True)
    if held is not None:
        params["experts_held"] = held
    x = jax.random.normal(keys[4], (1, tokens, d))
    if precision == "default":
        # what the chip's one pass rounds, rounded for both makers
        x, ws = _round(x, precision), [ws[0]] + [_round(w, precision) for w in ws[1:]]
    with jax.default_matmul_precision("highest"):
        took = []
        want, want_counts = moe.sparse_moe(x, ws, params, took=took)
        assert took == [False]  # a gradient may be wanted: `ragged_dot`
    with jax.default_matmul_precision(precision):
        got, counts = moe.sparse_moe(x, ws, params, grad=False, took=took)
    assert took == [False, True]
    assert got.shape == want.shape and np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= TOLERANCE[precision] * scale
    # a decode step's few rows take it too, and rows that are no whole
    # sublane tile keep XLA's call
    moe.sparse_moe(x[:, :16], ws, params, grad=False, took=took)
    moe.sparse_moe(x[:, :3], ws, params, grad=False, took=took)
    assert took[-2:] == [True, False]
