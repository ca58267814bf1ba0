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
* a slot keeps its batch until the wait for its device arrays;
* the steady state allocates no block of batch size;
* `fit()`'s per-step losses are the parent commit's, bit for bit;
* the public `next_batch()` still hands out arrays the caller owns.
"""

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


def _orders(shuffle):
    """The sample order of each epoch, as `SingleDataLoader(seed=0)`
    draws it: one in-place shuffle per reset."""
    rng = np.random.RandomState(0)
    order = np.arange(ROWS)
    out = []
    for _ in range(EPOCHS):
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


@pytest.mark.parametrize("shuffle", [False, True], ids=["in_order", "shuffled"])
def test_a_slot_keeps_its_batch_until_its_transfer_is_waited_for(impl, shuffle):
    x, y = _dataset()
    loader = SingleDataLoader({"x": x, "label": y}, BATCH, shuffle=shuffle)
    waits = []
    transfers = []
    orders = _orders(shuffle)
    per_epoch = ROWS // BATCH
    for e in range(EPOCHS):
        loader.reset()
        # a reset ends every lease, after waiting for each
        assert all(t.waited for t in transfers)
        for b in range(per_epoch):
            views = loader.borrow_batch(lambda: _Noted(waits))
            rows = orders[e][b * BATCH : (b + 1) * BATCH]
            np.testing.assert_array_equal(views["x"], x[rows])
            np.testing.assert_array_equal(views["label"], y[rows])
            t = _Transfer(views)
            loader.lend({"x": t, "label": t})
            transfers.append(t)
            # one transfer in flight and the batch just lent: older ones
            # were waited for, these two were not
            done = [t.waited for t in transfers]
            assert done == [True] * (len(done) - min(b + 1, 2)) + [False] * min(b + 1, 2)
    # inside borrow_batch the wait is entered through the caller's context
    assert len(waits) == EPOCHS * (per_epoch - 2)
    borrowed, copied, waited_s = loader.take_counts()
    assert (borrowed, copied) == (EPOCHS * per_epoch, 0)
    assert waited_s >= 0.0
    assert loader.take_counts() == (0, 0, 0.0)


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


# -- (d): the parent commit's losses ----------------------------------------------

#: per-step losses of `fit()` on commit 44b8b20 (PR 24), before the loader
#: lent anything: `_trainer()` on `_dataset()`, two epochs, `float.hex()`
PARENT_LOSSES = {
    False: [
        "0x1.e89f240000000p+0", "0x1.11faea0000000p+1", "0x1.0c77d20000000p+1",
        "0x1.8600cc0000000p+0", "0x1.86b0f40000000p+0", "0x1.bf2dca0000000p+0",
        "0x1.f8e2240000000p+0", "0x1.eb08680000000p+0", "0x1.6b46ec0000000p+0",
        "0x1.65fb8a0000000p+0",
    ],
    True: [
        "0x1.015bc00000000p+1", "0x1.422d660000000p+0", "0x1.0e4ba40000000p+1",
        "0x1.afadaa0000000p+0", "0x1.100bc00000000p+1", "0x1.79d7b80000000p+0",
        "0x1.82dad80000000p+0", "0x1.a1d3cc0000000p+0", "0x1.eaba400000000p+0",
        "0x1.ed7ac20000000p+0",
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
    model.fit(x, y, epochs=EPOCHS, shuffle=shuffle, verbose=False)
    assert got == PARENT_LOSSES[shuffle]


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
