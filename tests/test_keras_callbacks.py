"""Keras callback protocol (reference: python/flexflow/keras/callbacks.py:1-90
and the invocation points in keras/models/base_model.py:374-430)."""

import numpy as np
import pytest

from flexflow_tpu.frontends import keras_api as keras
from flexflow_tpu.frontends.keras_callbacks import (
    Callback,
    EpochVerifyMetrics,
    LearningRateScheduler,
    VerifyMetrics,
)


def _mnist_like(n=32, d=20, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    # make it learnable: class mean offsets
    for c in range(classes):
        x[y == c, c] += 3.0
    return x, y


def _model(d=20, classes=4, lr=0.1, batch_size=8):
    cfg = keras.FFConfig(batch_size=batch_size)
    model = keras.Sequential(
        [
            keras.Input(shape=(d,)),
            keras.Dense(16, activation="relu"),
            keras.Dense(classes),
        ],
        config=cfg,
    )
    model.compile(
        optimizer=keras.SGD(lr),
        loss="sparse_categorical_crossentropy",
        metrics=["accuracy"],
    )
    return model


class _Recorder(Callback):
    def __init__(self):
        super().__init__()
        self.events = []

    def on_train_begin(self, logs=None):
        self.events.append("train_begin")

    def on_train_end(self, logs=None):
        self.events.append("train_end")

    def on_epoch_begin(self, epoch, logs=None):
        self.events.append(("epoch_begin", epoch))

    def on_epoch_end(self, epoch, logs=None):
        self.events.append(("epoch_end", epoch))

    def on_batch_begin(self, batch, logs=None):
        self.events.append(("batch_begin", batch))

    def on_batch_end(self, batch, logs=None):
        self.events.append(("batch_end", batch))


def test_hook_ordering_and_set_model():
    x, y = _mnist_like()
    model = _model()
    rec = _Recorder()
    model.fit(x, y, epochs=2, callbacks=[rec], verbose=False)
    assert rec.model is model  # keras model, not the FFModel
    ev = rec.events
    assert ev[0] == "train_begin" and ev[-1] == "train_end"
    assert ev[1] == ("epoch_begin", 0)
    assert ("batch_begin", 0) in ev and ("batch_end", 3) in ev
    assert ("epoch_end", 1) in ev
    # batch hooks nest inside epoch hooks
    assert ev.index(("epoch_begin", 0)) < ev.index(("batch_begin", 0))
    assert ev.index(("batch_end", 0)) < ev.index(("epoch_end", 0))


def test_learning_rate_scheduler_applies_schedule():
    x, y = _mnist_like()
    model = _model(lr=0.5)
    seen = []

    def schedule(epoch):
        lr = 0.1 / (epoch + 1)
        seen.append(lr)
        return lr

    model.fit(
        x, y, epochs=3,
        callbacks=[LearningRateScheduler(schedule)],
        verbose=False,
    )
    assert seen == [0.1, 0.05, pytest.approx(0.1 / 3)]
    # the schedule's last LR is live on the engine
    assert model.ffmodel.optimizer.lr == pytest.approx(0.1 / 3)


def test_learning_rate_scheduler_rejects_non_float():
    x, y = _mnist_like()
    model = _model()
    with pytest.raises(ValueError, match="should be float"):
        model.fit(
            x, y, epochs=1,
            callbacks=[LearningRateScheduler(lambda e: "fast")],
            verbose=False,
        )


def test_verify_metrics_passes_and_fails():
    x, y = _mnist_like()
    model = _model()
    model.fit(x, y, epochs=20, callbacks=[VerifyMetrics(60.0)], verbose=False)
    with pytest.raises(AssertionError, match="Accuracy is wrong"):
        model.fit(x, y, epochs=1, callbacks=[VerifyMetrics(101.0)], verbose=False)


def test_epoch_verify_metrics_early_stops():
    x, y = _mnist_like()
    model = _model()
    rec = _Recorder()
    history = model.fit(
        x, y, epochs=50,
        callbacks=[EpochVerifyMetrics(60.0), rec],
        verbose=False,
    )
    assert len(history) < 50  # stopped before the epoch budget
    assert rec.events[-1] == "train_end"


def test_callbacks_direct_on_ffmodel():
    # callbacks also work on FFModel.fit without the keras wrapper
    x, y = _mnist_like()
    model = _model()
    ff = model.ffmodel
    rec = _Recorder()
    ff.fit(x, y, epochs=1, callbacks=[rec], verbose=False)
    assert rec.model is ff
    assert rec.events[0] == "train_begin" and rec.events[-1] == "train_end"


def test_evaluate_callbacks():
    x, y = _mnist_like()
    model = _model()
    model.fit(x, y, epochs=5, verbose=False)
    rec = _Recorder()
    perf = model.evaluate(x, y, callbacks=[rec])
    assert rec.events[0] == "train_begin" and rec.events[-1] == "train_end"
    assert perf.get_accuracy() >= 0.0


# -- the epoch's turn (ISSUE 56) ---------------------------------------------------
#
# `fit()` places the next epoch's first batch on the device beside this
# epoch's last steps. That is a transfer: what a callback may count on at
# an epoch's end and beginning is as it was.


@pytest.mark.parametrize("stop_at", [None, 1], ids=["to_the_end", "stopped"])
def test_no_step_of_an_epoch_runs_before_its_hooks_have_returned(stop_at):
    """Every hook and every dispatched step in one log: an epoch's steps
    lie between its `on_epoch_begin` and its `on_epoch_end`, whose `True`
    stops the run with no step more; a `LearningRateScheduler` that
    rebinds the step at `on_epoch_begin` is the step the epoch runs."""
    x, y = _mnist_like()
    ff = _model().ffmodel
    rec = _Recorder()
    real_train_step = ff.executor.train_step

    def train_step():
        step = real_train_step()

        def logged(*args):
            rec.events.append("step")
            return step(*args)

        return logged

    ff.executor.train_step = train_step

    class Stop(Callback):
        def on_epoch_end(self, epoch, logs=None):
            return epoch == stop_at

    ff.fit(x, y, epochs=3, callbacks=[rec, Stop()], verbose=False)
    want = ["train_begin"]
    for e in range(3 if stop_at is None else stop_at + 1):
        want.append(("epoch_begin", e))
        for b in range(4):
            want += [("batch_begin", b), "step", ("batch_end", b)]
        want.append(("epoch_end", e))
    assert rec.events == want + ["train_end"]


def test_a_scheduled_rate_takes_effect_at_the_turn_it_was_set_for():
    """Two runs of three epochs, one with the rate of the last epoch set
    by a `LearningRateScheduler` at its `on_epoch_begin`, one from two
    `fit()`s at the two rates: the same parameters, whatever was placed
    on the device before the turn."""
    import jax

    def leaves(ff):
        return [
            np.asarray(w).tobytes() for w in jax.tree_util.tree_leaves(ff.params)
        ]

    x, y = _mnist_like()
    scheduled, by_hand = _model(), _model()
    assert leaves(scheduled.ffmodel) == leaves(by_hand.ffmodel)
    scheduled.fit(
        x, y, epochs=3, verbose=False,
        callbacks=[LearningRateScheduler(lambda e: 0.1 if e < 2 else 0.01)],
    )
    by_hand.fit(x, y, epochs=2, verbose=False)
    by_hand.ffmodel.set_learning_rate(0.01)
    by_hand.fit(x, y, epochs=1, verbose=False)
    assert leaves(scheduled.ffmodel) == leaves(by_hand.ffmodel)
