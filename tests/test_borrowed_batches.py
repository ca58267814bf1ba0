"""`fit()` lends the loader's slot to the transfer (ISSUE 25).

The loader gathers each batch into a ring of slots it reuses; `fit()`
places views of the slot on the device and the slot is refilled only
after those device arrays are ready. Held here:

* the rows that reach the step are the dataset's, in the loader's order,
  with every placed batch of two epochs kept alive: an early refill or a
  backend that silently keeps the slot's memory would show other rows;
* the same whether the slots sit on a 64-byte boundary (the CPU backend
  then keeps host memory it is handed, and the loader has to notice and
  copy) or off it (the backend copies, the slot is purely lent);
* a slot keeps its batch until the wait for its device arrays, across an
  epoch's turn too: with another epoch to follow the ring goes on into it
  by itself (ISSUE 56), every batch of every epoch the one a reset an epoch
  gave, the next epoch's first batch ready before the turn, and nothing
  gathered for an epoch that will not run;
* the steady state allocates no block of batch size;
* `fit()`'s per-step losses are the parent commit's, bit for bit, and an
  early stop leaves the parameters of the epochs that ran and no slot lent;
* the public `next_batch()` still hands out arrays the caller owns.
"""

import time
import tracemalloc

import numpy as np
import pytest

import jax

from flexflow_tpu import (
    ActiMode,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
    native,
)
from flexflow_tpu.runtime import model as model_mod
from flexflow_tpu.runtime.dataloader import SingleDataLoader
from flexflow_tpu.runtime.metrics import PerfMetrics
from flexflow_tpu.serving import Telemetry

BATCH = 8
ROWS = 40  # 5 batches an epoch: the ring of 3 turns over
EPOCHS = 2


def _off_boundary(shape, dtype):
    """A slot at 16 past a 64-byte boundary, where a large malloc'd block
    happens to sit: no backend can keep it as an aligned buffer."""
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.empty(nbytes + 128, np.uint8)
    start = -raw.ctypes.data % 64 + 16
    return raw[start : start + nbytes].view(dtype).reshape(shape)


@pytest.fixture(params=["native", "fallback"])
def impl(request, monkeypatch):
    if request.param == "native":
        if not native.available():
            pytest.skip("native library unavailable")
    else:
        monkeypatch.setattr(native, "get_lib", lambda: None)
    return request.param


@pytest.fixture(params=["on_boundary", "off_boundary"])
def slots(request, monkeypatch):
    """Where the ring's slots sit; the default is on the boundary."""
    if request.param == "off_boundary":
        monkeypatch.setattr(native, "_alloc_slot", _off_boundary)
    return request.param


def _trainer(ndev=1):
    cfg = FFConfig(batch_size=BATCH, seed=3)
    model = FFModel(cfg)
    x = model.create_tensor([BATCH, 16], name="x")
    t = model.dense(x, 16, activation=ActiMode.RELU, name="d0")
    model.dense(t, 4, name="head")
    model.compile(
        optimizer=SGDOptimizer(lr=0.05),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:ndev],
    )
    return model


def _dataset():
    rng = np.random.RandomState(0)
    return (
        rng.randn(ROWS, 16).astype(np.float32),
        rng.randint(0, 4, size=(ROWS, 1)).astype(np.int32),
    )


def _orders(shuffle, epochs=EPOCHS):
    """The sample order of each epoch, as `SingleDataLoader(seed=0)`
    draws it: one in-place shuffle per reset."""
    rng = np.random.RandomState(0)
    order = np.arange(ROWS)
    out = []
    for _ in range(epochs):
        if shuffle:
            rng.shuffle(order)
        out.append(order.copy())
    return out


def _registry_value(tele, name):
    return tele.registry.sample()[name]


# -- (a), (b): the rows that reach the step ------------------------------------


@pytest.mark.parametrize("ndev", [1, 4], ids=["one_device", "four_devices"])
@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_the_rows_that_reach_the_step_are_the_datasets(
    impl, slots, shuffle, ndev
):
    model = _trainer(ndev)
    x, y = _dataset()
    reached = []  # every batch a step was called with, kept alive
    real_train_step = model.executor.train_step

    def train_step():
        step = real_train_step()

        def recording(params, opt_state, batch, key):
            reached.append(batch)
            return step(params, opt_state, batch, key)

        return recording

    model.executor.train_step = train_step
    tele = Telemetry()
    model.fit(
        x, y, epochs=EPOCHS, shuffle=shuffle, verbose=False, telemetry=tele
    )
    per_epoch = ROWS // BATCH
    assert len(reached) == EPOCHS * per_epoch
    for e, order in enumerate(_orders(shuffle)):
        for b in range(per_epoch):
            rows = order[b * BATCH : (b + 1) * BATCH]
            got = reached[e * per_epoch + b]
            np.testing.assert_array_equal(np.asarray(got["x"]), x[rows])
            np.testing.assert_array_equal(np.asarray(got["label"]), y[rows])
    # which way the batches went is what the slots' place decides here,
    # on the CPU backend: on the boundary it keeps the memory, so each
    # batch is copied; off it the slot is lent as on an accelerator
    borrowed = _registry_value(tele, "train_input_batches_borrowed")
    copied = _registry_value(tele, "train_input_batches_copied")
    assert borrowed + copied == EPOCHS * per_epoch
    assert copied == (EPOCHS * per_epoch if slots == "on_boundary" else 0)


def test_the_cpu_backend_keeps_a_slot_on_the_boundary_and_lend_notices(impl):
    """The premise of the copy: without it, the placed array IS the slot."""
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH)
    loader.reset()
    views = loader.borrow_batch()
    kept = jax.device_put(views["x"])
    assert kept.unsafe_buffer_pointer() == views["x"].ctypes.data
    safe = loader.lend({"x": kept, "label": jax.device_put(views["label"])})
    assert safe["x"] is not kept
    for _ in range(ROWS // BATCH - 1):  # the ring turns over the slot
        loader.lend(
            {k: jax.device_put(v) for k, v in loader.borrow_batch().items()}
        )
    np.testing.assert_array_equal(np.asarray(safe["x"]), x[:BATCH])
    assert loader.take_counts()[:2] == (0, ROWS // BATCH)


# -- the lease --------------------------------------------------------------------


class _Transfer:
    """Stands for the device arrays of one batch: `block_until_ready` is
    the moment the loader may refill the slot, and not before."""

    def __init__(self, views):
        self.views = views
        self.expect = {k: v.copy() for k, v in views.items()}
        self.waited = False

    def block_until_ready(self):
        for k, v in self.views.items():
            np.testing.assert_array_equal(v, self.expect[k])
        self.waited = True
        return self


@pytest.mark.parametrize("turn", ["reset", "rolled"])
@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_a_slot_keeps_its_batch_until_its_transfer_is_waited_for(
    impl, shuffle, turn
):
    """`turn`: how the epochs are started, by a reset each (every lease
    ends there) or as `fit()` starts them, the ring going on by itself (no
    lease ends at the turn: a slot lent in one epoch goes back as inside
    an epoch, after the wait, however far the next epoch's gather is)."""
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH, shuffle=shuffle)
    waits = []
    transfers = []
    epochs = 3
    orders = _orders(shuffle, epochs)
    per_epoch = ROWS // BATCH
    for e in range(epochs):
        if turn == "reset":
            loader.reset()
            # a reset ends every lease, after waiting for each
            assert all(t.waited for t in transfers)
        else:
            assert loader.begin_epoch(follows=e + 1 < epochs) == (e + 1 < epochs)
            # the last epoch's final two leases are still out, and whole
            assert [t.waited for t in transfers[-2:]] == [False, False][: 2 * e]
        for b in range(per_epoch):
            views = loader.borrow_batch(lambda: _Noted(waits))
            rows = orders[e][b * BATCH : (b + 1) * BATCH]
            np.testing.assert_array_equal(views["x"], x[rows])
            np.testing.assert_array_equal(views["label"], y[rows])
            t = _Transfer(views)
            loader.lend({"x": t, "label": t})
            transfers.append(t)
            # one transfer in flight and the batch just lent: older ones
            # were waited for (`_Transfer` looks at its slot then: it
            # still held the batch), these two were not
            done = [t.waited for t in transfers]
            out = min(b + 1 if turn == "reset" else len(done), 2)
            assert done == [True] * (len(done) - out) + [False] * out
    # inside borrow_batch the wait is entered through the caller's context
    assert len(waits) == (
        epochs * (per_epoch - 2) if turn == "reset" else epochs * per_epoch - 2
    )
    borrowed, copied, waited_s, ahead = loader.take_counts()
    assert (borrowed, copied) == (epochs * per_epoch, 0)
    assert waited_s >= 0.0
    # nothing is ahead of a reset; the fallback gathers when asked, and
    # here nobody asks before the turn
    assert ahead == 0 if turn == "reset" or impl == "fallback" else ahead <= 4
    assert loader.take_counts() == (0, 0, 0.0, 0)
    loader.close()
    assert all(t.waited for t in transfers)


def _until(cond, what, seconds=20.0):
    """Waits for the ring's worker, which nothing here can hurry; only a
    worker that never gets there fails."""
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.001)


@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_three_epochs_of_borrowed_batches_are_the_parents_stream(impl, shuffle):
    """Taken as `fit()` takes them, an epoch's first batch borrowed before
    the epoch is begun: row for row `arrays[order]`, the orders those a
    reset an epoch draws from a fresh `RandomState(seed)`. Every epoch
    but the last says another follows; the stream ends with the last."""
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH, shuffle=shuffle)
    epochs, per_epoch = 3, ROWS // BATCH
    kept = []  # a batch lives on the device: a copy of what was lent

    def take():
        views = loader.borrow_batch()
        kept.append({k: v.copy() for k, v in views.items()})
        t = _Transfer(views)
        loader.lend({"x": t, "label": t})

    for e in range(epochs):
        rolls_on = loader.begin_epoch(follows=e + 1 < epochs)
        for _ in range(per_epoch - (e > 0)):
            take()
        if rolls_on:
            take()  # the next epoch's first, before this one's drain
    assert len(kept) == epochs * per_epoch
    for e, order in enumerate(_orders(shuffle, epochs)):
        for b in range(per_epoch):
            rows = order[b * BATCH : (b + 1) * BATCH]
            np.testing.assert_array_equal(kept[e * per_epoch + b]["x"], x[rows])
            np.testing.assert_array_equal(kept[e * per_epoch + b]["label"], y[rows])
    # one batch of each later epoch was in the ring before the epoch began
    # (the worker may have had a second there); the last had no successor
    ring = loader._ring
    assert 2 <= loader.take_counts()[3] <= 4
    assert ring.gathered() == epochs * per_epoch
    assert ring.borrow() is None


@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_the_next_epochs_first_batch_is_ready_before_the_last_lease_is_returned(
    impl, shuffle
):
    """The loader is asked, not timed. With the epoch's last two batches
    still lent the worker gathers the next epoch's first into the one free
    slot, and stops: it refills nothing under a lease. The fallback has no
    worker: there the batch is ready when `fit()` asks, which is before
    the turn too. Either way both of the old epoch's leases are still out,
    and whole, when the new epoch's first batch is in hand."""
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH, shuffle=shuffle)
    per_epoch = ROWS // BATCH
    orders = _orders(shuffle, 2)
    assert loader.begin_epoch(follows=True)
    held = []
    for b in range(per_epoch):
        views = loader.borrow_batch()
        held.append(_Transfer(views))
        loader.lend({"x": held[-1], "label": held[-1]})
    ring = loader._ring
    if impl == "native":
        _until(lambda: ring.gathered() == per_epoch + 1, "the next epoch's first")
        time.sleep(0.05)  # were the worker to go on under a lease, it would now
    assert ring.gathered() == per_epoch + (impl == "native")
    assert [t.waited for t in held] == [True] * (per_epoch - 2) + [False] * 2
    views = loader.borrow_batch()  # returns batch n-2's lease, keeps n-1's
    np.testing.assert_array_equal(views["x"], x[orders[1][:BATCH]])
    np.testing.assert_array_equal(views["label"], y[orders[1][:BATCH]])
    first = _Transfer(views)
    loader.lend({"x": first, "label": first})
    loader.begin_epoch()  # the turn: nothing rewinds, no lease ends
    assert not held[-1].waited and not first.waited
    ahead = loader.take_counts()[3]
    assert 1 <= ahead <= 2  # the first batch, and what the worker added
    views = loader.borrow_batch()
    np.testing.assert_array_equal(views["x"], x[orders[1][BATCH : 2 * BATCH]])
    assert held[-1].waited and not first.waited


def test_a_reset_drops_what_was_gathered_ahead_and_skips_no_draw(impl):
    """An order drawn ahead for an epoch that never began is what the next
    reset starts from, as the parent's reset would have drawn it then; a
    `begin_epoch` in mid-epoch is such a reset, whatever was promised."""
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH, shuffle=True)
    orders = _orders(True, 4)
    per_epoch = ROWS // BATCH
    loader.begin_epoch(follows=True)
    for _ in range(per_epoch + 1):  # the epoch, and one batch placed ahead
        t = _Transfer(loader.borrow_batch())
        loader.lend({"x": t, "label": t})
    got = [b["x"] for b in loader]  # `__iter__`: a true reset
    assert t.waited and not loader._out
    for b in range(per_epoch):
        np.testing.assert_array_equal(got[b], x[orders[1][b * BATCH : (b + 1) * BATCH]])
    loader.begin_epoch(follows=True)
    first = loader.borrow_batch()["x"].copy()
    np.testing.assert_array_equal(first, x[orders[2][:BATCH]])
    loader.begin_epoch()  # in mid-epoch: not a turn
    np.testing.assert_array_equal(
        loader.borrow_batch()["x"], x[orders[3][:BATCH]]
    )
    assert loader.take_counts()[3] == 0


class _Noted:
    def __init__(self, log):
        self.log = log

    def __enter__(self):
        self.log.append("wait")

    def __exit__(self, *exc):
        return False


def test_a_batch_borrowed_and_never_lent_goes_back_to_the_ring(impl):
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH)
    loader.reset()
    for b in range(ROWS // BATCH):  # more than the ring holds
        views = loader.borrow_batch()
        np.testing.assert_array_equal(views["x"], x[b * BATCH : (b + 1) * BATCH])


def test_the_ring_refuses_to_lend_more_slots_than_it_has(impl):
    x, _ = _dataset()
    ring = native.NativeLoader([x], BATCH, shuffle=False)
    out = [ring.borrow() for _ in range(ring.depth)]
    with pytest.raises(RuntimeError, match="slots are lent"):
        ring.borrow()
    ring.release(out[0][0])
    index, views = ring.borrow()
    assert index == ring.depth
    np.testing.assert_array_equal(
        views[0], x[index * BATCH : (index + 1) * BATCH]
    )


# -- (c): nothing of batch size is allocated in the steady state -----------------


def test_steady_state_steps_allocate_no_block_of_batch_size(impl):
    rows, width = 64, 4096  # a batch of x is 1 MiB
    x = np.random.RandomState(1).randn(8 * rows, width).astype(np.float32)
    y = np.zeros((8 * rows, 1), np.int32)
    loader = SingleDataLoader({"x": x, "label": y}, rows, shuffle=True)
    loader.reset()

    def step():
        views = loader.borrow_batch()
        t = _Transfer({})  # nothing to compare: no copy of the batch here
        loader.lend({k: t for k in views})

    for _ in range(3):  # the ring's slots come into being
        step()
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        for _ in range(5):
            step()
        peak = tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()
    assert peak < x[:rows].nbytes // 8, peak
    # the public path does allocate its copy: the measurement sees one
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        owned = loader.next_batch()
        grown = tracemalloc.get_traced_memory()[0] - before
    finally:
        tracemalloc.stop()
    assert grown >= owned["x"].nbytes


def test_fit_counts_every_step_as_borrowed_and_none_as_copied(
    impl, monkeypatch
):
    monkeypatch.setattr(native, "_alloc_slot", _off_boundary)
    model = _trainer()
    x, y = _dataset()
    tele = Telemetry()
    model.fit(x, y, epochs=EPOCHS, verbose=False, telemetry=tele)
    steps = EPOCHS * (ROWS // BATCH)
    assert _registry_value(tele, "train_iterations_total") == steps
    assert _registry_value(tele, "train_input_batches_borrowed") == steps
    assert _registry_value(tele, "train_input_batches_copied") == 0
    assert _registry_value(tele, "train_input_lease_wait_ms") >= 0.0
    # evaluate() borrows the same way and leaves the training counters be
    model.evaluate(x, y)
    assert _registry_value(tele, "train_input_batches_borrowed") == steps


class _Stop:
    """A `fit()` callback that stops training at the end of one epoch."""

    model = None

    def __init__(self, at):
        self.at = at

    def set_model(self, model):
        self.model = model

    def on_epoch_end(self, epoch):
        return epoch == self.at

    def _nothing(self, *args):
        pass

    on_train_begin = on_train_end = on_epoch_begin = _nothing
    on_batch_begin = on_batch_end = _nothing


@pytest.fixture
def loaders(monkeypatch):
    """Every loader `fit()` builds, kept to be asked afterwards."""
    made = []

    class Watched(SingleDataLoader):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.asked = 0
            made.append(self)

        def borrow_batch(self, *args, **kwargs):
            self.asked += 1
            return super().borrow_batch(*args, **kwargs)

    monkeypatch.setattr(model_mod, "SingleDataLoader", Watched)
    return made


@pytest.mark.parametrize(
    "epochs,stop_at", [(1, None), (3, None), (50, 1)],
    ids=["one_epoch", "three_epochs", "stopped_after_two"],
)
def test_fit_gathers_ahead_at_every_turn_and_for_no_epoch_that_will_not_run(
    impl, loaders, epochs, stop_at
):
    """`train_input_batches_gathered_ahead` says the mechanism engages:
    nothing in a one-epoch `fit()`, at least the batch `fit()` places
    before the drain at every turn taken (the worker may add one). The
    last of `epochs` has no successor and gathers nothing ahead; a stop
    drops the batch placed ahead, unstepped, with what lay behind it."""
    model = _trainer()
    x, y = _dataset()
    tele = Telemetry()
    callbacks = [] if stop_at is None else [_Stop(stop_at)]
    history = model.fit(
        x, y, epochs=epochs, verbose=False, telemetry=tele, callbacks=callbacks
    )
    ran = epochs if stop_at is None else stop_at + 1
    turns, per_epoch = ran - 1, ROWS // BATCH
    assert [h["epoch"] for h in history] == list(range(ran))
    assert _registry_value(tele, "train_iterations_total") == ran * per_epoch
    ahead = _registry_value(tele, "train_input_batches_gathered_ahead")
    assert turns <= ahead <= 2 * turns
    (loader,) = loaders
    stopped = stop_at is not None
    assert loader.asked == ran * per_epoch + stopped
    # no slot is lent, the worker is joined
    assert not loader._out and loader._pending is None
    assert not loader._ring._lent and loader._ring._handle is None
    if not stopped and impl == "fallback":
        assert loader._ring.gathered() == ran * per_epoch


@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_an_early_stop_leaves_the_parameters_of_the_epochs_that_ran(
    impl, shuffle
):
    """A callback that says stop at the end of the second epoch of many:
    the third epoch's first batch, placed beside the second's last steps,
    took no step. The parameters are those of a run of exactly two."""

    def leaves(model):
        return [
            np.asarray(w).tobytes()
            for w in jax.tree_util.tree_leaves(model.params)
        ]

    x, y = _dataset()
    want, got = _trainer(), _trainer()
    assert leaves(want) == leaves(got)
    want_history = want.fit(x, y, epochs=2, shuffle=shuffle, verbose=False)
    got_history = got.fit(
        x, y, epochs=50, shuffle=shuffle, verbose=False, callbacks=[_Stop(1)]
    )
    assert leaves(got) == leaves(want)
    for h in want_history + got_history:
        h.pop("throughput")
    assert got_history == want_history


# -- (d): the parent commit's losses ----------------------------------------------

#: per-step losses of `fit()` on commit 44b8b20 (PR 24), before the loader
#: lent anything: `_trainer()` on `_dataset()`, two epochs, `float.hex()`;
#: the third epoch's on commit f109f0a (PR 54's tree), a reset an epoch
PARENT_LOSSES = {
    False: [
        "0x1.e89f240000000p+0", "0x1.11faea0000000p+1", "0x1.0c77d20000000p+1",
        "0x1.8600cc0000000p+0", "0x1.86b0f40000000p+0", "0x1.bf2dca0000000p+0",
        "0x1.f8e2240000000p+0", "0x1.eb08680000000p+0", "0x1.6b46ec0000000p+0",
        "0x1.65fb8a0000000p+0",
        "0x1.9e9e5e0000000p+0", "0x1.d44fec0000000p+0", "0x1.c9d9f80000000p+0",
        "0x1.5620da0000000p+0", "0x1.4d4d800000000p+0",
    ],
    True: [
        "0x1.015bc00000000p+1", "0x1.422d660000000p+0", "0x1.0e4ba40000000p+1",
        "0x1.afadaa0000000p+0", "0x1.100bc00000000p+1", "0x1.79d7b80000000p+0",
        "0x1.82dad80000000p+0", "0x1.a1d3cc0000000p+0", "0x1.eaba400000000p+0",
        "0x1.ed7ac20000000p+0",
        "0x1.8b0f4a0000000p+0", "0x1.03210c0000000p+1", "0x1.d891720000000p+0",
        "0x1.6472160000000p+0", "0x1.1e481c0000000p+0",
    ],
}


@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_fit_losses_are_the_parents_bit_for_bit(impl, slots, shuffle, monkeypatch):
    got = []
    real_update = PerfMetrics.update

    def update(self, step_metrics, loss):
        got.append(float(loss).hex())
        return real_update(self, step_metrics, loss)

    monkeypatch.setattr(PerfMetrics, "update", update)
    model = _trainer()
    x, y = _dataset()
    history = model.fit(x, y, epochs=3, shuffle=shuffle, verbose=False)
    assert got == PARENT_LOSSES[shuffle]
    # and `history` is those floats, summed in that order, an epoch a row
    per_epoch = ROWS // BATCH
    for e, h in enumerate(history):
        want = 0.0
        for v in got[e * per_epoch : (e + 1) * per_epoch]:
            want += float.fromhex(v) * BATCH
        assert (h["epoch"], h["train_all"]) == (e, ROWS)
        assert h["loss_sum"] == want


# -- (e): the public path still hands out the caller's own arrays ------------------


def test_public_next_batch_survives_the_next_call_and_a_reset(impl):
    x, y = _dataset()
    ring = native.NativeLoader([x, y], BATCH, shuffle=False)
    first = ring.next_batch()
    rest = [ring.next_batch() for _ in range(ROWS // BATCH - 1)]
    assert ring.next_batch() is None
    ring.reset()
    again = ring.next_batch()
    for b, got in enumerate([first] + rest):
        np.testing.assert_array_equal(got[0], x[b * BATCH : (b + 1) * BATCH])
        np.testing.assert_array_equal(got[1], y[b * BATCH : (b + 1) * BATCH])
        assert got[0].flags.owndata or got[0].base is not again[0].base
    first[0][:] = -1.0  # the caller's to write: the loader's next is untouched
    ring.reset()
    np.testing.assert_array_equal(ring.next_batch()[0], x[:BATCH])

    loader = SingleDataLoader({"x": x, "label": y}, BATCH, shuffle=True)
    kept = [dict(b) for b in loader] + [dict(b) for b in loader]
    for e, order in enumerate(_orders(True)):
        for b in range(ROWS // BATCH):
            rows = order[b * BATCH : (b + 1) * BATCH]
            np.testing.assert_array_equal(
                kept[e * (ROWS // BATCH) + b]["x"], x[rows]
            )
