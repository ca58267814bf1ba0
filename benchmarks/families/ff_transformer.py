"""The trainer family: the flagship encoder stack through the user's
path, `FFModel` builder -> `compile()` -> `fit()`.

The untraced run is one `fit()` call over the host dataset. The harness
adds no host sync inside a step: its callback stamps the clock at epoch
ends, which `fit()` has already blocked on (`block_until_ready` of the
parameters), and says stop when the window is over. The traced run wraps
each batch's host work in a `train.input` span and the epoch's drain in
`train.epoch_end`, and traces `trace_epochs` whole epochs.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.lib import peaks, window
from benchmarks.lib.loading import load_module

SPANS = ("bench.trace", "train.input", "train.epoch_end")


def build(config: dict, global_batch: int, devices, seed: int):
    """chip_smoke.py's `_transformer`, by copy."""
    from examples.transformer import build_transformer
    from flexflow_tpu import FFConfig, LossType, SGDOptimizer

    cfg = FFConfig(batch_size=global_batch, learning_rate=config["learning_rate"])
    cfg.allow_mixed_precision = bool(config["mixed_precision"])
    cfg.seed = int(seed) % (2**31 - 1)
    model, _ = build_transformer(
        cfg,
        batch_size=global_batch,
        seq_len=config["seq_len"],
        hidden=config["hidden_size"],
        num_heads=config["num_heads"],
        num_layers=config["num_layers"],
        compile_now=False,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=config["learning_rate"]),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
        devices=list(devices),
    )
    return model


def flat_weights(params):
    """The program's parameter tree in graph order, on the host."""
    import jax

    return [
        [np.asarray(jax.device_get(w), np.float32) for w in params[guid]]
        for guid in sorted(params)
    ]


def step_temp_bytes(model, x, y):
    """The train step's temporaries as the compiler counts them. The
    runtime's `peak_bytes_in_use` leaves a program's temporaries out
    (PR 22 read 0.76 GB beside 6.5 GB of them), so the peak a chip
    really held is the counter plus this. The lowering is the one
    `fit()` made, so the executable comes from the cache."""
    import jax

    step = model.executor.train_step()
    placed = model.executor.shard_batch({"x": x, "label": y})
    key = jax.random.split(jax.random.PRNGKey(0))[1]
    compiled = step.lower(model.params, model.opt_state, placed, key).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


class EpochClock:
    """`fit()` callback: the clock, the stop, and (traced) the spans."""

    model = None

    def __init__(self, ctx, lead_in: int, trace_epochs: int, batches: int):
        self.ctx = ctx
        self.lead_in = lead_in
        self.trace_epochs = trace_epochs
        self.batches = batches
        self.stamps = []  # one per epoch end, lead-in included
        self.window_compiles = None
        self._span = None

    def set_model(self, model):
        self.model = model

    def on_train_begin(self):
        pass

    def on_train_end(self):
        pass

    def _open(self, name):
        self._span = window.span(name)
        self._span.__enter__()

    def _close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def on_epoch_begin(self, epoch):
        if self.ctx.trace and epoch == self.lead_in:
            self.ctx.tracer.start()
            self._trace_span = window.span("bench.trace")
            self._trace_span.__enter__()

    def on_batch_begin(self, it):
        if self.ctx.tracer.started and not self.ctx.tracer.stopped:
            self._open("train.input")

    def on_batch_end(self, it):
        if self.ctx.tracer.started and not self.ctx.tracer.stopped:
            self._close()
            if it == self.batches - 1:
                self._open("train.epoch_end")

    def on_epoch_end(self, epoch):
        now = time.perf_counter()
        self.stamps.append(now)
        counted = epoch + 1 - self.lead_in
        if counted == 0:
            self.ctx.compiles.reset()  # the window opens here
        if self.ctx.trace:
            if counted == self.trace_epochs:
                self._close()
                self._trace_span.__exit__(None, None, None)
                self.window_compiles = self.ctx.compiles.snapshot()
                self.ctx.tracer.stop()
                return True
            return False
        if counted >= 1 and now - self.stamps[self.lead_in - 1] >= self.ctx.seconds:
            self.window_compiles = self.ctx.compiles.snapshot()
            return True
        return False


def run(ctx) -> dict:
    import jax

    config, traffic = ctx.config, ctx.traffic
    batch = int(traffic["global_batch"])
    model = build(config, batch, ctx.devices, ctx.seed)
    ctx.mark("model_built")
    data = load_module("generators", traffic["kind"]).generate(
        traffic, ctx.seed, config["seq_len"], config["hidden_size"]
    )
    x, y = data["x"], data["label"]
    ctx.mark("dataset_filled")

    # correctness, outside the window: the first step's loss against the
    # plain reference on the same weights and the same batch. The same
    # call compiles (or fetches) the step, so it is the warm-up too.
    weights0 = flat_weights(model.params)
    first = model.fit(x[:batch], y[:batch], epochs=1, verbose=False)
    first_loss = first[0]["loss_sum"] / max(first[0]["train_all"], 1)
    ctx.mark("first_step")
    reference = load_module("reference", config["family"])
    ref_loss = reference.loss(weights0, x[:batch], y[:batch], chunk=min(8, batch))
    del weights0
    ctx.mark("reference_loss")
    temp_bytes = step_temp_bytes(model, x[:batch], y[:batch])
    ctx.mark("step_memory_analysis")
    placed = sorted({
        s.device.id
        for leaf in jax.tree_util.tree_leaves(model.params)
        for s in leaf.addressable_shards
    })

    lead_in = max(1, int(traffic["lead_in_epochs"]))
    clock = EpochClock(
        ctx, lead_in, int(traffic["trace_epochs"]), data["batches_per_epoch"]
    )
    window.settle(ctx)
    history = model.fit(x, y, epochs=10**6, verbose=False, callbacks=[clock])

    losses = [h["loss_sum"] / max(h["train_all"], 1) for h in history]
    tol = config["tolerance"]
    rel = abs(first_loss - ref_loss) / max(abs(ref_loss), 1e-30)
    rise = tol["epoch_loss_rise_rel"]
    checks = {
        "first_step_loss": first_loss,
        "reference_loss": ref_loss,
        "first_step_loss_rel_gap": rel,
        "first_step_within_tolerance": rel <= tol["first_step_loss_rel"],
        "losses_finite": all(math.isfinite(v) for v in losses + [first_loss]),
        "losses_non_increasing": all(
            b <= a + rise * losses[0] for a, b in zip(losses, losses[1:])
        ),
        "params_on_every_chip": placed == sorted(d.id for d in ctx.devices),
        "last_below_first": len(losses) < 2 or losses[-1] < losses[0],
        "epochs": len(losses),
        "epoch_losses_head": losses[:4],
        "epoch_losses_tail": losses[-4:],
    }
    counted = len(clock.stamps) - lead_in
    steps = counted * data["batches_per_epoch"]
    return {
        "kind": "train",
        "spans": SPANS,
        "correct": all(
            checks[k] for k in (
                "first_step_within_tolerance", "losses_finite",
                "losses_non_increasing", "last_below_first",
                "params_on_every_chip",
            )
        ),
        "checks": checks,
        "attempted": steps,
        "failed": 0 if checks["losses_finite"] else steps,
        "window_start": clock.stamps[lead_in - 1],
        "epoch_stamps": clock.stamps[lead_in - 1:],
        "tokens_per_epoch": data["tokens_per_epoch"],
        "steps_per_epoch": data["batches_per_epoch"],
        "compiles": clock.window_compiles or ctx.compiles.snapshot(),
        "flops_per_token": peaks.transformer_train_flops_per_token(
            config["num_layers"], config["hidden_size"], config["num_heads"],
            config["seq_len"],
        ),
        "step_module": "jit_step",
        "program_temp_bytes": temp_bytes,
    }
