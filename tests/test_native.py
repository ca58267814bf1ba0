"""Tests for the native C++ core (flexflow_tpu/native ↔ native/src/*.cc).

Mirrors the reference's pure-logic unit tests (reference:
tests/unit/test_dominators.cc scenarios) plus simulator/loader checks.
Each algorithm is tested through BOTH the native library and the
pure-Python fallback (FFTPU_NO_NATIVE path) via the `impl` fixture.
"""

import os

import numpy as np
import pytest

from flexflow_tpu import native


@pytest.fixture(params=["native", "fallback"])
def impl(request, monkeypatch):
    if request.param == "native":
        if not native.available():
            pytest.skip("native library unavailable")
    else:
        # Force the pure-Python fallbacks without rebuilding module state.
        monkeypatch.setattr(native, "get_lib", lambda: None)
    return request.param


# A diamond with a tail:   0 -> 1 -> 3 -> 4
#                          0 -> 2 -> 3
DIAMOND = (5, [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4)])


def test_topo_sort_diamond(impl):
    n, edges = DIAMOND
    order = native.topo_sort(n, edges)
    pos = {v: i for i, v in enumerate(order)}
    for s, d in edges:
        assert pos[s] < pos[d]
    assert order[0] == 0 and order[-1] == 4


def test_topo_sort_cycle_detected(impl):
    assert native.topo_sort(2, [(0, 1), (1, 0)]) is None


def test_imm_post_dominators_diamond(impl):
    n, edges = DIAMOND
    ipdom = native.imm_post_dominators(n, edges)
    # 3 post-dominates both branches and 0; 4 is the sink.
    assert ipdom[0] == 3
    assert ipdom[1] == 3
    assert ipdom[2] == 3
    assert ipdom[3] == 4
    assert ipdom[4] == -1


def test_imm_post_dominators_parallel_sinks(impl):
    # 0 -> 1, 0 -> 2: two sinks, nothing post-dominates 0.
    ipdom = native.imm_post_dominators(3, [(0, 1), (0, 2)])
    assert ipdom[0] == -1
    assert ipdom[1] == -1 and ipdom[2] == -1


def test_imm_post_dominators_chain(impl):
    ipdom = native.imm_post_dominators(3, [(0, 1), (1, 2)])
    assert ipdom == [1, 2, -1]


def test_transitive_reduction(impl):
    # 0->1->2 plus shortcut 0->2: the shortcut must be dropped.
    edges = [(0, 1), (1, 2), (0, 2)]
    keep = native.transitive_reduction(3, edges)
    assert keep == [True, True, False]


def test_transitive_reduction_keeps_parallel_edges(impl):
    n, edges = DIAMOND
    keep = native.transitive_reduction(n, edges)
    assert all(keep)


def test_simulate_chain(impl):
    # Three sequential tasks on one chip: makespan = sum.
    ms, busy = native.simulate([0, 0, 0], [1.0, 2.0, 3.0], [(0, 1), (1, 2)], 1)
    assert ms == pytest.approx(6.0)
    assert busy[0] == pytest.approx(6.0)


def test_simulate_parallel_chips(impl):
    # Two independent tasks on two chips overlap fully.
    ms, busy = native.simulate([0, 1], [2.0, 3.0], [], 2)
    assert ms == pytest.approx(3.0)
    assert busy[0] == pytest.approx(2.0) and busy[1] == pytest.approx(3.0)


def test_simulate_comm_overlap(impl):
    # chip0 runs A (2s) then C (2s); a transfer task T (1s) on link
    # resource 2 feeds chip1's B (2s). B starts at 3s, ends 5s; C ends 4s.
    resource_of = [0, 2, 1, 0]  # A, T, B, C
    duration = [2.0, 1.0, 2.0, 2.0]
    edges = [(0, 1), (1, 2), (0, 3)]
    ms, busy = native.simulate(resource_of, duration, edges, 3)
    assert ms == pytest.approx(5.0)
    assert busy[0] == pytest.approx(4.0)


def test_simulate_serialized_resource(impl):
    # Two ready tasks on one chip serialize even without dependencies.
    ms, _ = native.simulate([0, 0], [2.0, 2.0], [], 1)
    assert ms == pytest.approx(4.0)


def test_simulate_cycle_returns_none(impl):
    assert native.simulate([0, 0], [1.0, 1.0], [(0, 1), (1, 0)], 1) is None


def test_loader_batches_and_shuffle(impl):
    x = np.arange(20, dtype=np.float32).reshape(10, 2)
    y = np.arange(10, dtype=np.int32)
    dl = native.NativeLoader([x, y], batch_size=4, shuffle=True, seed=7)
    assert dl.num_batches == 2
    seen = []
    batches = 0
    while True:
        b = dl.next_batch()
        if b is None:
            break
        bx, by = b
        assert bx.shape == (4, 2) and by.shape == (4,)
        # rows stay aligned across arrays
        np.testing.assert_array_equal(bx[:, 0], by.astype(np.float32) * 2)
        seen.extend(by.tolist())
        batches += 1
    assert batches == 2
    assert len(set(seen)) == len(seen)  # no duplicate samples within epoch


def test_loader_reset_determinism(impl):
    x = np.arange(12, dtype=np.float32).reshape(12, 1)
    dl = native.NativeLoader([x], batch_size=3, shuffle=True, seed=5)
    first = [dl.next_batch()[0].ravel().tolist() for _ in range(4)]
    dl.reset(5)
    second = [dl.next_batch()[0].ravel().tolist() for _ in range(4)]
    assert first == second
    dl.reset(6)
    third = [dl.next_batch()[0].ravel().tolist() for _ in range(4)]
    assert sorted(sum(first, [])) == sorted(sum(third, []))


def test_loader_no_shuffle_order(impl):
    x = np.arange(8, dtype=np.int64).reshape(8, 1)
    dl = native.NativeLoader([x], batch_size=4, shuffle=False)
    b0 = dl.next_batch()[0].ravel().tolist()
    b1 = dl.next_batch()[0].ravel().tolist()
    assert b0 == [0, 1, 2, 3] and b1 == [4, 5, 6, 7]
    assert dl.next_batch() is None


def test_loader_pads_short_final_batch(impl):
    x = np.arange(5, dtype=np.int64).reshape(5, 1)
    dl = native.NativeLoader([x], batch_size=4, shuffle=False, drop_last=False)
    assert dl.num_batches == 2
    dl.next_batch()
    b1 = dl.next_batch()[0].ravel().tolist()
    assert b1[0] == 4 and len(b1) == 4


@pytest.mark.parametrize("use_lib", [True, False], ids=["core", "python"])
def test_loader_goes_on_into_a_queued_epoch(use_lib):
    """With the next epoch's order queued the stream crosses the epoch's
    end with no reset: batches counted on from the last reset, slots lent
    across the turn kept, and the end where the orders end."""
    x = np.arange(24, dtype=np.int64).reshape(12, 2)
    perms = [np.random.RandomState(s).permutation(12) for s in range(3)]
    dl = native.NativeLoader([x], batch_size=4, shuffle=False, use_lib=use_lib)
    assert (dl._handle is not None) == (use_lib and native.available())
    dl.reset_perm(perms[0])
    queued = perms[1].copy()
    dl.queue_perm(queued)
    queued[:] = 0  # the caller's to write: the loader took a copy
    held = {}
    for g in range(9):
        if g == 3:  # an order queued late, beside the epoch it follows
            dl.queue_perm(perms[2])
        index, views = dl.borrow()
        assert index == g and dl.gathered() > g
        rows = perms[g // 3][(g % 3) * 4 : (g % 3 + 1) * 4]
        np.testing.assert_array_equal(views[0], x[rows])
        held[g] = rows
        if g >= 2:  # two leases stay out, across both turns
            np.testing.assert_array_equal(
                dl._slots[(g - 2) % dl.depth][0], x[held[g - 2]]
            )
            dl.release(g - 2)
    assert dl.borrow() is None and dl.gathered() == 9
    dl.reset_perm(perms[1])  # a reset drops what was queued
    assert [dl.next_batch() is None for _ in range(4)] == [False] * 3 + [True]
    with pytest.raises(ValueError, match="samples"):
        dl.queue_perm(perms[0][:5])
    dl.close()
    assert dl._handle is None
    dl.close()


@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_loader_rolls_over_many_epochs_under_a_hurried_caller(impl, shuffle):
    """The worker and the caller share the ring across every turn: 300
    epochs of three batches in a ring of three, each order queued one
    epoch ahead, each slot given back at once. A batch gathered by the
    wrong order, into a lent slot or twice would show other rows."""
    import time

    x = np.arange(36, dtype=np.int64).reshape(12, 3)
    rng = np.random.RandomState(3)
    dl = native.NativeLoader([x], batch_size=4, shuffle=False)
    perms = [
        rng.permutation(12) if shuffle else np.arange(12) for _ in range(300)
    ]
    dl.reset_perm(perms[0])
    deadline = time.monotonic() + 60.0
    for e, perm in enumerate(perms):
        if e + 1 < len(perms):
            dl.queue_perm(perms[e + 1])
        for b in range(3):
            index, views = dl.borrow()
            assert index == 3 * e + b
            np.testing.assert_array_equal(views[0], x[perm[4 * b : 4 * b + 4]])
            dl.release(index)
        assert time.monotonic() < deadline
    assert dl.borrow() is None and dl.gathered() == 900


@pytest.mark.parametrize("use_native", [True, False], ids=["core", "python"])
@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_single_dataloader_rolled_epochs_are_the_reset_epochs(use_native, shuffle):
    """Three epochs started as `fit()` starts them (`begin_epoch`: the
    ring goes on, the next order drawn an epoch early) are, batch for
    batch, three epochs started by a reset each: the same draws from the
    same RNG in the same sequence. A reset after an early stop starts
    from the order already drawn, as the epoch that never ran would; a
    caller that runs off the stream's end (`borrow_batch()`'s rollover)
    draws the order after it."""
    from flexflow_tpu.runtime.dataloader import SingleDataLoader

    data = {
        "x": np.arange(48, dtype=np.float32).reshape(24, 2),
        "y": np.arange(24, dtype=np.int32),
    }

    def stream(how, epochs=3):
        dl = SingleDataLoader(
            dict(data), batch_size=4, shuffle=shuffle, seed=11,
            use_native=use_native,
        )
        out = []
        for e in range(epochs):
            if how == "reset":
                dl.reset()
            else:
                dl.begin_epoch(follows=how != "rolled" or e + 1 < epochs)
            for _ in range(dl.num_batches):
                got = dl.borrow_batch()
                out.append({k: v.copy() for k, v in got.items()})
                dl.lend({})
        if how == "then_reset":  # the order drawn ahead is not skipped
            out += [{k: v.copy() for k, v in b.items()} for b in dl]
        if how == "then_runs_on":  # nor is it used twice
            for _ in range(2 * dl.num_batches):
                out.append({k: v.copy() for k, v in dl.borrow_batch().items()})
                dl.lend({})
        return out

    want = stream("reset")
    for got in (
        stream("rolled"),
        stream("then_reset", epochs=2),
        stream("then_runs_on", epochs=1),
    ):
        assert len(got) == len(want) == 18
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a["x"], b["x"])
            np.testing.assert_array_equal(a["y"], b["y"])


def test_single_dataloader_native_matches_fallback(monkeypatch):
    """Same seed → bit-identical batch stream with and without the native
    prefetch path (the permutation is always drawn from numpy's RNG)."""
    from flexflow_tpu.runtime.dataloader import SingleDataLoader

    data = {
        "x": np.arange(48, dtype=np.float32).reshape(24, 2),
        "y": np.arange(24, dtype=np.int32),
    }

    def stream(use_native):
        dl = SingleDataLoader(
            {k: v.copy() for k, v in data.items()},
            batch_size=4,
            shuffle=True,
            seed=11,
            use_native=use_native,
        )
        out = []
        for _ in range(2):  # two epochs: reset path must also agree
            for batch in dl:
                out.append({k: v.copy() for k, v in batch.items()})
        return out

    a = stream(True)
    b = stream(False)
    assert len(a) == len(b) == 12
    for ba, bb in zip(a, b):
        np.testing.assert_array_equal(ba["x"], bb["x"])
        np.testing.assert_array_equal(ba["y"], bb["y"])
