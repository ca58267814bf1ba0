"""Shared train-step timing (search/audit.py, scripts/bench_configs.py,
scripts/calibrate.py callers).

On the chip `block_until_ready` waits for the device (chip_smoke.py's
sync phase re-checks it on every run), so a step of tens of
milliseconds can be timed with a host clock round work that ends in it.
What this module adds is for steps and ops far shorter than that, where
one host dispatch and one device->host readback cost as much as the
work: the N-step loop runs INSIDE one jitted program (`lax.scan` over
the train step — the analog of the reference's Legion begin/end_trace
replay loop, transformer.cc:192-198), ended by a scalar readback that
forces the whole chain; two chain lengths are differenced so the
per-run constants cancel, and the measurement repeats `reps` times
taking the MIN of each window (a stall only ever adds time).
"""

from __future__ import annotations

import time


def _adaptive_differenced(make_chain, run_args, n1, n2, reps, cap=20000):
    """Differenced timing with the adaptive-window guard: grow the chain
    until the differenced window dominates the host's per-call jitter
    (sub-ms steps — e.g. the sparse-embedding DLRM at ~26 us — sit below
    it at short chains). A measurement that stays non-positive at the cap
    is reported as NaN, never as a negative time."""
    import numpy as np

    while True:
        r1, r2 = make_chain(n1), make_chain(n2)
        _ = float(np.asarray(r1(*run_args)))  # compile + warmup
        _ = float(np.asarray(r2(*run_args)))
        best1 = best2 = float("inf")
        for _i in range(reps):
            t0 = time.perf_counter()
            _ = float(np.asarray(r1(*run_args)))
            t1 = time.perf_counter()
            _ = float(np.asarray(r2(*run_args)))
            t2 = time.perf_counter()
            # min each window SEPARATELY, then difference: min of the
            # per-rep difference is biased LOW by stalls landing in the
            # short chain (a spike in t1-t0 fakes a speedup), which
            # min() then selects for
            best1 = min(best1, t1 - t0)
            best2 = min(best2, t2 - t1)
        best = (best2 - best1) / (n2 - n1)
        window = best * (n2 - n1)
        if window >= 0.05:
            return best
        if n2 >= cap:
            return best if best > 0 else float("nan")
        n1 *= 10
        n2 *= 10


def measure_train_step(
    model, batch, n1: int = 5, n2: int = 20, reps: int = 6,
    estimates: int = 1,
):
    """Differenced per-train-step seconds via on-device lax.scan chains.

    `batch` must already be sharded (executor.shard_batch).

    estimates > 1: run the whole adaptive differencing that many times
    and take the MEDIAN. Median, not min: a stall landing selectively in
    one estimate's SHORT chain biases that estimate LOW, and min() would
    select exactly the contaminated one (the same asymmetry the
    per-window-min rule in _adaptive_differenced exists to avoid)."""
    import statistics

    import jax
    from jax import lax

    step_fn = model.executor.train_step_fn()
    key = jax.random.PRNGKey(0)

    def chain(n):
        @jax.jit
        def run(p, o):
            def body(c, _):
                cp, co = c
                p2, o2, loss, _ = step_fn(cp, co, batch, key)
                return (p2, o2), loss

            _, losses = lax.scan(body, (p, o), None, length=n)
            return losses[-1]

        return run

    vals = []
    for _ in range(max(1, estimates)):
        t = _adaptive_differenced(
            chain, (model.params, model.opt_state), n1, n2, reps
        )
        if t == t:  # NaN-safe
            vals.append(t)
    return statistics.median(vals) if vals else float("nan")


def measure_fn(fn, args, n1: int = 4, n2: int = 12, reps: int = 3):
    """Differenced per-call seconds of an arbitrary jittable fn(*args),
    chained on-device with a data dependency between iterations so XLA
    cannot hoist the body; same adaptive-window guard as
    measure_train_step."""
    import jax
    from jax import lax

    def chain(n):
        @jax.jit
        def run(*a):
            def body(c, _):
                out = fn(*c)
                dep = (out.sum() * 1e-12).astype(c[0].dtype)
                return (c[0] + dep, *c[1:]), out.sum()

            _, s = lax.scan(body, a, None, length=n)
            return s[-1]

        return run

    return _adaptive_differenced(chain, tuple(args), n1, n2, reps, cap=1200)
