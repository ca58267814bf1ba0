#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives the trainer and the server once, through the entry
points a user calls, at the full width of the model the repo carries
(12 layers, hidden 1024, 16 heads, sequence 512), with random weights
made from a seed, and checks what comes out:

  sync      a known matmul chain timed three ways: does
            `block_until_ready` wait for the device?
  kernels   every Pallas entry point the serving and training paths can
            select, compiled by Mosaic (never interpreted), against the
            repo's dense jnp references at the flagship geometry
  train     examples/transformer.py's flagship, FFModel builder ->
            compile() -> fit() under mixed precision: loss finite on
            every step and lower at the end; then the same path at
            sequence 2048, where it selects the tiled flash kernel
  serve     the flagship decoder LM, ServeConfig() defaults (paged
            cache, decode_kernel="auto"), build_scheduler -> FrontDoor,
            mixed-length requests each ending FINISHED, no kernel
            fallback, and greedy streams identical to a second engine
            with decode_kernel="dense"
  families  every kernel family through the serving path (speculative
            chain and tree, a draft model on its own paged cache, int8
            pages) on a 2-layer cut of the same width, streams identical
            to dense
  four_chip with four or more devices: training data-parallel and
            dp2 x tp2, serving on a (1, 4) and a (2, 2) mesh

The dense comparisons run under `jax_default_matmul_precision =
"highest"` and require TOKEN-IDENTICAL greedy streams: at the TPU's
default precision an f32 matmul is one bf16 pass, the kernel and the XLA
path round differently, and an argmax over 32000 near-flat random
logits is not a fair witness. The user-facing serve phase itself runs
at the default precision.

Exit code 0 only when every check passed, and then the last two stdout
lines are JSON: the summary (phases, timings, compile seconds and peak
memory as observations, not metrics, ending with `"claim": null`), and
last of all the verdict, with exactly these keys and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed check raises: no phase is wrapped in a try/except that lets
the run go on, and no verdict is printed.

    python chip_smoke.py                 # everything
    python chip_smoke.py --phase serve   # one phase (repeatable), for
                                         # a builder on a chip budget
"""

from __future__ import annotations

import asyncio
import gc
import json
import sys
import time

PHASES = (
    "sync", "kernels", "train", "serve", "families", "four_chip",
)

# the flagship geometry (transformer.cc:79-85)
LAYERS, HIDDEN, HEADS, SEQ, BATCH = 12, 1024, 16, 512, 8
VOCAB, MAX_SEQS, MAX_LEN = 32000, 8, 512
HEAD_DIM = HIDDEN // HEADS
# the sequence at which the training path selects the tiled flash kernel
FLASH_SEQ = 2048
# request mix: prompt lengths and generation budgets, drawn per request
PROMPT_LENS = (5, 12, 27, 40, 90, 150)
NEW_TOKENS = (8, 16, 24, 40)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Compiles:
    """Seconds JAX spent tracing, lowering and compiling (or fetching
    from the persistent cache), and cache hits/misses, since the last
    `take()` — from jax.monitoring, so nothing the program does is
    timed twice."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self.EVENTS:
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {
            "compile_s": round(self.seconds, 2),
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


def verdict(device: dict) -> str:
    """The LAST stdout line of a run that passed: these keys and no
    others (the driver refuses anything more)."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def peak_bytes() -> list:
    import jax

    return [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
        for d in jax.devices()
    ]


def highest():
    import jax

    return jax.default_matmul_precision("highest")


# -- sync ---------------------------------------------------------------------


def phase_sync(chip: str) -> dict:
    """Time one jitted chain of bf16 matmuls (a known number of FLOPs)
    to dispatch return, to `block_until_ready`, and to a host readback.
    If block_until_ready waits for the device, the second equals the
    third and neither beats the chip's peak."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from flexflow_tpu.core.machine import CHIP_SPECS

    n, steps = 4096, 64
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, n), jnp.bfloat16)
    w = jax.random.normal(kw, (n, n), jnp.bfloat16) / jnp.sqrt(n)

    @jax.jit
    def chain(x, w):
        return lax.scan(lambda c, _: (c @ w, None), x, None, length=steps)[0]

    chain(x, w).block_until_ready()
    floor_s = 2.0 * n**3 * steps / (CHIP_SPECS[chip][0] * 1e12)
    dispatch, blocked, readback = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        y = chain(x, w)
        t1 = time.perf_counter()
        y.block_until_ready()
        t2 = time.perf_counter()
        dispatch.append(t1 - t0)
        blocked.append(t2 - t0)
        t0 = time.perf_counter()
        float(chain(x, w)[0, 0])
        readback.append(time.perf_counter() - t0)
    d, b, r = min(dispatch), min(blocked), min(readback)
    say(
        f"sync: {steps} x {n}^3 bf16 matmuls, peak floor {floor_s * 1e3:.1f}"
        f" ms; dispatch returned after {d * 1e3:.2f} ms, block_until_ready"
        f" after {b * 1e3:.1f} ms, host readback after {r * 1e3:.1f} ms"
    )
    synchronises = b >= floor_s and abs(b - r) <= 0.2 * r
    check(
        synchronises,
        "block_until_ready does not wait for the device "
        f"(blocked {b:.4f}s, readback {r:.4f}s, floor {floor_s:.4f}s)",
    )
    return {
        "block_until_ready_synchronises": synchronises,
        "dispatch_ms": round(d * 1e3, 3),
        "blocked_ms": round(b * 1e3, 2),
        "readback_ms": round(r * 1e3, 2),
        "peak_floor_ms": round(floor_s * 1e3, 2),
    }


# -- kernels ------------------------------------------------------------------


def phase_kernels() -> dict:
    """Each entry point once, compiled, against the dense reference of
    ops/attention.py on the same random inputs (f32, "highest")."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops import attention as A
    from flexflow_tpu.ops.pallas import decode_kernel as dk
    from flexflow_tpu.ops.pallas import flash_kernel as fk

    rng = np.random.RandomState(0)
    b, h, d, max_len = MAX_SEQS, HEADS, HEAD_DIM, MAX_LEN
    errors = {}

    def close(name, got, want, atol):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        errors[name] = err
        check(
            bool(jnp.all(jnp.isfinite(got))) and err <= atol,
            f"kernel {name}: max |kernel - dense| = {err:.3g} > {atol}",
        )

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    def paged_case(ps, w):
        pages = b * max_len // ps
        perm = rng.permutation(pages).reshape(b, max_len // ps)
        lens = rng.randint(0, max_len - w, size=b).astype(np.int32)
        return (
            arr(b, w, h, d), arr(pages, ps, h, d), arr(pages, ps, h, d),
            jnp.asarray(perm.astype(np.int32)), jnp.asarray(lens),
        )

    def quantize(pool):
        scale = jnp.max(jnp.abs(pool), axis=(1, 3)) / 127.0  # [pages, h]
        q = jnp.round(pool / scale[:, None, :, None]).astype(jnp.int8)
        return q, scale

    def tree_parents(w):
        # a branching tree in topological order: node j hangs off j // 2
        p = np.array([-1] + [(j - 1) // 2 for j in range(1, w)], np.int32)
        return jnp.asarray(np.tile(p, (b, 1)))

    def latent_case(slots=16, heads=32, row=640, v_width=512, ps=16, max_pos=2048):
        """The latent pool at the geometry `serve_kanana2_assist` serves:
        every head reads the same rows of 640 and the values are a row's
        first 512 lanes, in blocks of 128 rows. Slots of one row, of less
        than a block, of many blocks and of every position, with idle
        slots between live ones (-1: no page allocated)."""
        lens = np.array(
            [0, 700, -1, 127, 128, 1663, -1, -1, 15, 2047, 300, 16, -1, 1024,
             129, 5], np.int32,
        )
        np_seq, pages = max_pos // ps, slots * max_pos // ps
        tbl = rng.permutation(pages).reshape(slots, np_seq).astype(np.int32)
        held = -(-(lens + 1) // ps)  # pages that hold rows 0 .. length
        tbl[np.arange(np_seq)[None, :] >= held[:, None]] = pages
        return (
            arr(slots, 1, heads, row), arr(pages, ps, row), jnp.asarray(tbl),
            jnp.asarray(np.maximum(lens, 0)), v_width, 192 ** -0.5,
        )

    with highest():
        ql, *latent = latent_case()
        got = dk.paged_flash_decode_latent(ql, *latent)
        want = A.paged_latent_decode_attention(ql, *latent, kernel="dense")
        live = np.asarray(latent[1])[:, 0] < latent[0].shape[0]
        close(
            "paged_flash_decode_latent/served_geometry",
            got[live], want[live], 1e-4,
        )
        for w in (1, 5, 13):
            q = arr(b, w, h, d)
            k, v = arr(b, max_len, h, d), arr(b, max_len, h, d)
            lens = jnp.asarray(
                rng.randint(0, max_len - w, size=b).astype(np.int32)
            )
            ref = A.verify_attention(q, k, v, lens, kernel="dense")
            entry = dk.flash_decode if w == 1 else dk.flash_verify
            close(f"{entry.__name__}/w{w}", entry(q, k, v, lens), ref, 1e-4)
            parents = tree_parents(w)
            allowed = A.tree_allowed_mask(parents, lens, w, max_len)
            close(
                f"flash_verify_tree/w{w}",
                dk.flash_verify_tree(q, k, v, lens, allowed),
                A.verify_attention(
                    q, k, v, lens, kernel="dense", tree_parents=parents
                ),
                1e-4,
            )
            for ps in (16, 32):
                q, kp, vp, tbl, lens = paged_case(ps, w)
                ref = A.paged_verify_attention(
                    q, kp, vp, tbl, lens, kernel="dense"
                )
                entry = (
                    dk.paged_flash_decode if w == 1 else dk.paged_flash_verify
                )
                close(
                    f"{entry.__name__}/ps{ps}/w{w}",
                    entry(q, kp, vp, tbl, lens), ref, 1e-4,
                )
                allowed = A.tree_allowed_mask(parents, lens, w, max_len)
                tree_ref = A.paged_verify_attention(
                    q, kp, vp, tbl, lens, kernel="dense",
                    tree_parents=parents,
                )
                close(
                    f"paged_flash_verify_tree/ps{ps}/w{w}",
                    dk.paged_flash_verify_tree(q, kp, vp, tbl, lens, allowed),
                    tree_ref, 1e-4,
                )
                if ps % 32:
                    continue
                (k8, ks), (v8, vs) = quantize(kp), quantize(vp)
                ref = A.paged_verify_attention(
                    q, k8, v8, tbl, lens, kernel="dense",
                    k_scale=ks, v_scale=vs,
                )
                entry = (
                    dk.paged_flash_decode_quant
                    if w == 1
                    else dk.paged_flash_verify_quant
                )
                close(
                    f"{entry.__name__}/ps{ps}/w{w}",
                    entry(q, k8, v8, ks, vs, tbl, lens), ref, 1e-4,
                )
                close(
                    f"paged_flash_verify_tree_quant/ps{ps}/w{w}",
                    dk.paged_flash_verify_tree_quant(
                        q, k8, v8, ks, vs, tbl, lens, allowed
                    ),
                    A.paged_verify_attention(
                        q, k8, v8, tbl, lens, kernel="dense",
                        k_scale=ks, v_scale=vs, tree_parents=parents,
                    ),
                    1e-4,
                )

        # the training kernel at a sequence that selects it, forward and
        # backward, f32 tight and bf16 (what mixed precision hands it)
        q, k, v = (arr(2, FLASH_SEQ, h, d) for _ in range(3))

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

        def tiled(q, k, v):
            return fk.flash_attention_tpu(q, k, v, causal=True)

        def dense(q, k, v):
            return A.scaled_dot_product_attention(q, k, v, causal=True)

        close("flash_attention_tpu/fwd", tiled(q, k, v), dense(q, k, v), 1e-4)
        got = jax.grad(loss(tiled), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
        for name, g, wnt in zip("qkv", got, want):
            close(f"flash_attention_tpu/d{name}", g, wnt, 2e-3)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        close(
            "flash_attention_tpu/fwd/bf16", tiled(qb, kb, vb),
            dense(q, k, v), 5e-2,
        )
        for g in jax.grad(loss(tiled), argnums=(0, 1, 2))(qb, kb, vb):
            check(bool(jnp.all(jnp.isfinite(g))), "bf16 flash grads finite")
    worst = max(errors, key=errors.get)
    say(
        f"kernels: {len(errors)} kernel/geometry cases agree with dense; "
        f"largest error {errors[worst]:.3g} ({worst})"
    )
    return {"cases": len(errors), "max_abs_error": errors[worst]}


# -- train --------------------------------------------------------------------


def _fit_losses(model, batch, steps: int):
    """`steps` passes of fit() over one batch, one step per epoch, so the
    per-epoch history is the per-step loss. Returns (losses, seconds of
    the first call — compile included —, seconds per later step)."""
    t0 = time.perf_counter()
    history = model.fit(batch["x"], batch["label"], epochs=1, verbose=False)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    history += model.fit(
        batch["x"], batch["label"], epochs=steps - 1, verbose=False
    )
    step_s = (time.perf_counter() - t0) / (steps - 1)
    losses = [h["loss_sum"] / max(h["train_all"], 1) for h in history]
    return losses, first_s, step_s


def _check_losses(name, losses):
    import math

    check(
        all(math.isfinite(x) for x in losses),
        f"{name}: loss not finite on every step: {losses}",
    )
    check(
        losses[-1] < losses[0],
        f"{name}: loss did not fall: {losses}",
    )


def _transformer(batch, seq, layers, devices=None, strategy=None):
    from examples.transformer import build_transformer
    from flexflow_tpu import FFConfig, LossType, SGDOptimizer

    cfg = FFConfig(batch_size=batch, learning_rate=0.01)
    cfg.allow_mixed_precision = True
    model, _ = build_transformer(
        cfg, batch_size=batch, seq_len=seq, hidden=HIDDEN,
        num_heads=HEADS, num_layers=layers, compile_now=False,
    )
    if strategy is not None:
        strategy = strategy(model)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
        metrics=[],
        devices=devices,
        strategy=strategy,
    )
    return model


def phase_train(chip: str, compiles: Compiles) -> dict:
    import jax

    from examples.transformer import synthetic_batch
    from flexflow_tpu.ops import pallas

    out = {}
    one = jax.devices()[:1]
    model = _transformer(BATCH, SEQ, LAYERS, devices=one)
    check(
        model.config.chip == chip,
        f"model.config.chip is {model.config.chip!r} on a {chip}",
    )
    losses, first_s, step_s = _fit_losses(
        model, synthetic_batch(BATCH, SEQ, HIDDEN), 10
    )
    _check_losses("flagship", losses)
    out["flagship"] = {
        "losses": [round(x, 5) for x in losses],
        "first_step_s": round(first_s, 2),
        "later_step_s": round(step_s, 4),
        **compiles.take(),
    }
    say(f"train: {LAYERS}L/{HIDDEN}h/seq{SEQ}/b{BATCH} losses "
        f"{out['flagship']['losses']}, first step {first_s:.1f}s, later "
        f"steps {step_s * 1e3:.1f} ms")
    del model
    gc.collect()

    # sequence 2048: one sample's score block passes the dense cap, so
    # _lower_mha hands attention to the tiled flash kernel (fwd + bwd)
    before = pallas.TRACE_MODES["compiled"]
    model = _transformer(2, FLASH_SEQ, 2, devices=one)
    losses, first_s, step_s = _fit_losses(
        model, synthetic_batch(2, FLASH_SEQ, HIDDEN), 4
    )
    _check_losses("seq2048", losses)
    check(
        pallas.TRACE_MODES["compiled"] > before,
        "the seq-2048 train step did not trace the tiled flash kernel",
    )
    out["seq2048_flash"] = {
        "losses": [round(x, 5) for x in losses],
        "first_step_s": round(first_s, 2),
        "later_step_s": round(step_s, 4),
        **compiles.take(),
    }
    say(f"train: 2L/{HIDDEN}h/seq{FLASH_SEQ}/b2 (tiled flash) losses "
        f"{out['seq2048_flash']['losses']}")
    del model
    gc.collect()
    return out


# -- serve --------------------------------------------------------------------


def _decoder(layers: int, devices=None):
    import jax

    from flexflow_tpu import (
        DataType, FFConfig, FFModel, LossType, SGDOptimizer,
    )
    from flexflow_tpu.models import build_decoder_lm

    model = FFModel(FFConfig(batch_size=MAX_SEQS))
    tok = model.create_tensor(
        [MAX_SEQS, MAX_LEN], dtype=DataType.INT32, name="tokens"
    )
    build_decoder_lm(
        model, tok, vocab_size=VOCAB, hidden=HIDDEN, num_heads=HEADS,
        num_layers=layers, ff_dim=4 * HIDDEN,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=jax.devices()[:1] if devices is None else devices,
    )
    return model


def _workload(n: int, seed: int = 0):
    """n (prompt, max_new_tokens) pairs of mixed length. Prompts repeat a
    short random motif, so the n-gram drafter has something to look up."""
    import numpy as np

    rng = np.random.RandomState(seed)
    work = []
    for i in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        motif = rng.randint(1, VOCAB, size=int(rng.randint(3, 7)))
        prompt = np.resize(motif, plen).tolist()
        work.append((prompt, int(rng.choice(NEW_TOKENS))))
    return work


def _serve_config(**kw):
    from flexflow_tpu.serving import ServeConfig

    return ServeConfig(max_seqs=MAX_SEQS, max_seq_len=MAX_LEN, **kw)


def _run_streams(model, serve, work, draft_model=None):
    """build_scheduler(...).run(...): (streams by request, stats, engine)."""
    from flexflow_tpu.serving import Request, build_scheduler

    sched, engine, cache = build_scheduler(model, serve, draft_model=draft_model)
    done = sched.run(
        [
            Request(rid=i, prompt=list(p), max_new_tokens=n)
            for i, (p, n) in enumerate(work)
        ]
    )
    for r in done:
        check(r.status == "finished", f"request {r.rid}: {r.status} {r.error}")
    streams = {r.rid: list(r.generated) for r in done}
    check(len(streams) == len(work), "a request was lost")
    return streams, sched.stats, engine


def _check_kernel_engine(engine, mode: str, what: str) -> None:
    check(
        engine.kernel_fallbacks == 0,
        f"{what}: kernel fell back to dense: {engine.kernel_fallback_error}",
    )
    check(
        engine.decode_kernel == mode,
        f"{what}: decode_kernel is {engine.decode_kernel!r}, not {mode!r}",
    )


def _same_streams(what, got, want):
    bad = [rid for rid in want if got[rid] != want[rid]]
    check(
        not bad,
        f"{what}: {len(bad)}/{len(want)} greedy streams differ from dense "
        f"(first: request {bad[:1]}, {got.get(bad[0]) if bad else ''} vs "
        f"{want.get(bad[0]) if bad else ''})",
    )


async def _front_door(sched, work):
    from flexflow_tpu.serving.frontend.server import FrontDoor

    door = FrontDoor(sched)

    async def client(prompt, n):
        rid = await door.submit(prompt, max_new_tokens=n)
        tokens, status, error = [], None, None
        async for ev in door.stream(rid):
            if ev.kind == "token":
                tokens.append(ev.token)
            else:
                status, error = ev.status, ev.error
        return tokens, status, error

    return await asyncio.gather(*(client(p, n) for p, n in work))


def phase_serve(compiles: Compiles) -> dict:
    from flexflow_tpu.ops import pallas
    from flexflow_tpu.serving import build_scheduler

    model = _decoder(LAYERS)
    compiles.take()
    work = _workload(32)

    # the user's path: ServeConfig() defaults (only the cache geometry of
    # the flagship preset is named), the front door, default precision
    serve = _serve_config()
    check(
        serve.kv_layout == "paged" and serve.decode_kernel == "auto",
        "ServeConfig() defaults moved",
    )
    before = pallas.TRACE_MODES["compiled"]
    sched, engine, cache = build_scheduler(model, serve)
    t0 = time.perf_counter()
    results = asyncio.run(_front_door(sched, work))
    wall = time.perf_counter() - t0
    for i, ((_, n), (tokens, status, error)) in enumerate(zip(work, results)):
        check(
            status == "finished",
            f"front door request {i}: {status} {error}",
        )
        check(
            len(tokens) == n and all(0 <= t < VOCAB for t in tokens),
            f"front door request {i}: {len(tokens)} tokens of {n}",
        )
    _check_kernel_engine(engine, "auto", "front door")
    check(
        pallas.TRACE_MODES["compiled"] > before,
        "the default serving path traced no compiled kernel",
    )
    out = {
        "front_door": {
            "requests": len(work),
            "tokens": sum(len(t) for t, _, _ in results),
            "wall_s": round(wall, 2),
            "decode_steps": int(sched.stats.decode_steps),
            "prefill_batches": int(sched.stats.prefill_batches),
            **compiles.take(),
        }
    }
    say(f"serve: front door finished {len(work)} requests, "
        f"{out['front_door']['tokens']} tokens, {wall:.1f}s wall "
        f"(compile {out['front_door']['compile_s']}s), 0 kernel fallbacks")
    del sched, engine, cache
    gc.collect()

    # kernel against dense, token for token, at "highest"
    compare = work[:16]
    with highest():
        got, _, engine = _run_streams(model, _serve_config(), compare)
        _check_kernel_engine(engine, "auto", "kernel engine")
        del engine
        gc.collect()
        want, _, engine = _run_streams(
            model, _serve_config(decode_kernel="dense"), compare
        )
        del engine
        gc.collect()
    _same_streams("flagship paged decode", got, want)
    out["dense_agreement"] = {
        "requests": len(compare),
        "tokens": sum(len(s) for s in want.values()),
        "comparison": "token-identical greedy streams at "
        "jax_default_matmul_precision=highest",
        **compiles.take(),
    }
    say(f"serve: {len(compare)} greedy streams "
        f"({out['dense_agreement']['tokens']} tokens) identical to the "
        "dense engine")
    del model
    gc.collect()
    return out


def phase_families(compiles: Compiles) -> dict:
    """Every kernel family the serving path can select, through
    build_scheduler, on a 2-layer cut at the flagship width (the kernels
    see the same geometry; only the number of compiles shrinks)."""
    from flexflow_tpu.ops import pallas

    model = _decoder(2)
    work = _workload(12, seed=1)
    families = {
        "paged/verify": dict(spec_draft="ngram"),
        "paged/tree": dict(spec_draft="ngram", spec_branch=3),
        "paged-int8/decode": dict(kv_dtype="int8", kv_page_size=32),
        "paged-int8/verify": dict(
            kv_dtype="int8", kv_page_size=32, spec_draft="ngram"
        ),
        # the draft model decodes on a paged cache of its own
        "paged/model-verify": dict(spec_draft="model"),
    }
    draft = _decoder(1)
    out = {}
    with highest():
        # greedy speculation is token-identical to plain greedy decode, so
        # one dense plain engine per cache dtype is every family's witness
        dense, _, _ = _run_streams(
            model, _serve_config(decode_kernel="dense"), work
        )
        dense8, _, _ = _run_streams(
            model,
            _serve_config(
                decode_kernel="dense", kv_dtype="int8", kv_page_size=32
            ),
            work,
        )
        gc.collect()
        compiles.take()
        for name, kw in families.items():
            before = pallas.TRACE_MODES["compiled"]
            got, stats, engine = _run_streams(
                model, _serve_config(**kw), work,
                draft_model=draft if kw.get("spec_draft") == "model" else None,
            )
            _check_kernel_engine(engine, "auto", name)
            check(
                pallas.TRACE_MODES["compiled"] > before,
                f"{name}: no compiled kernel was traced",
            )
            if name.endswith("verify"):
                check(stats.verify_steps > 0, f"{name}: no verify step ran")
            if name.endswith("tree"):
                check(
                    stats.tree_verify_steps > 0,
                    f"{name}: no tree verify step ran",
                )
            _same_streams(name, got, dense8 if "int8" in name else dense)
            out[name] = {
                "decode_steps": int(stats.decode_steps),
                "verify_steps": int(stats.verify_steps),
                "tree_verify_steps": int(stats.tree_verify_steps),
                **compiles.take(),
            }
            say(f"families: {name} ok {out[name]}")
            del engine
            gc.collect()
    del model, draft
    gc.collect()
    return out


# -- four chips ---------------------------------------------------------------


def _on_all(devices, arrays, what: str) -> None:
    """Every array has a shard on each of `devices`."""
    want = {d.id for d in devices}
    for name, a in arrays:
        have = {s.device.id for s in a.addressable_shards}
        check(
            have == want,
            f"{what}: {name} lives on devices {sorted(have)}, "
            f"not {sorted(want)}",
        )


def _all_in_use(devices, what: str) -> list:
    used = [
        int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices
    ]
    check(all(u > 0 for u in used), f"{what}: bytes_in_use per device {used}")
    return used


def phase_four_chip(compiles: Compiles) -> dict:
    import jax

    from examples.transformer import synthetic_batch
    from flexflow_tpu.ops import pallas
    from flexflow_tpu.parallel.strategy import (
        sequence_parallel_strategy,
        site_strategy,
    )
    from flexflow_tpu.search.rewrites import find_tp_sites

    four = jax.devices()[:4]
    say("four_chip: devices " + ", ".join(
        f"{d.id}@{getattr(d, 'coords', None)}" for d in four
    ))
    out = {}

    def dp2_tp2(model):
        # what the strategy search lowers a (data=2, model=2) winner to:
        # every tensor-parallel rewrite site that 2 divides
        sites = [
            s for s in find_tp_sites(model.graph)
            if s.divisible_by(model.graph, 2)
        ]
        return site_strategy(model.graph, 4, 2, sites, "chip_smoke")

    def ring(model):
        return sequence_parallel_strategy(1, 4, model.graph)

    # (name, batch, seq, layers, devices, strategy, kernel expected):
    # the flagship at global batch 32, then the long-sequence attention
    # paths only a real mesh runs — the tiled flash kernel under
    # shard_map over the batch axis, and the ring's Pallas body
    legs = (
        ("one_chip", 32, SEQ, LAYERS, four[:1], None, False),
        ("data_parallel", 32, SEQ, LAYERS, four, None, False),
        ("dp2_tp2", 32, SEQ, LAYERS, four, dp2_tp2, False),
        ("long_one_chip", 4, FLASH_SEQ, 2, four[:1], None, True),
        ("long_data_parallel", 4, FLASH_SEQ, 2, four, None, True),
        ("long_ring", 4, FLASH_SEQ, 2, four, ring, True),
    )
    first = {}
    for name, batch, seq, layers, devices, strategy, kernel in legs:
        before = pallas.TRACE_MODES["compiled"]
        model = _transformer(batch, seq, layers, devices, strategy)
        data = synthetic_batch(batch, seq, HIDDEN)
        losses, first_s, step_s = _fit_losses(model, data, 3)
        _check_losses(name, losses)
        first[name] = losses[0]
        check(
            (pallas.TRACE_MODES["compiled"] > before) == kernel,
            f"{name}: tiled flash kernel traced is not {kernel}",
        )
        used = None
        if len(devices) == 4:
            leaves = jax.tree_util.tree_leaves_with_path(model.params)
            _on_all(
                devices,
                [(jax.tree_util.keystr(p), a) for p, a in leaves],
                name,
            )
            _on_all(
                devices, model.executor.shard_batch(data).items(),
                f"{name} batch",
            )
            used = _all_in_use(devices, name)
        out[name] = {
            "strategy": model.strategy.name,
            "mesh": dict(zip(
                model.executor.mesh.axis_names,
                model.executor.mesh.devices.shape,
            )),
            "losses": [round(x, 5) for x in losses],
            "first_step_s": round(first_s, 2),
            "later_step_s": round(step_s, 4),
            "bytes_in_use": used,
            **compiles.take(),
        }
        say(f"four_chip: train {name} {out[name]}")
        del model
        gc.collect()
    for name, ref in (
        ("data_parallel", "one_chip"),
        ("dp2_tp2", "one_chip"),
        ("long_data_parallel", "long_one_chip"),
        ("long_ring", "long_one_chip"),
    ):
        # bf16 operands, f32 accumulation: another reduction order moves
        # the loss in the fourth digit at most
        rel = abs(first[name] - first[ref]) / abs(first[ref])
        check(
            rel < 2e-3,
            f"{name}: first-step loss {first[name]} vs {ref} "
            f"{first[ref]} (rel {rel:.2e})",
        )
        out[name]["first_loss_rel_diff_vs_one_chip"] = rel

    # serving: (1, 4) runs the kernels per head shard; (2, 2) shards pages
    # over data, which no kernel may gather — dense is selected and named
    work = _workload(12, seed=2)
    with highest():
        model = _decoder(LAYERS)
        want, _, _ = _run_streams(
            model, _serve_config(decode_kernel="dense"), work
        )
        del model
        gc.collect()
        compiles.take()
        for mesh, mode in (("1,4", "auto"), ("2,2", "dense")):
            model = _decoder(LAYERS)
            got, _, engine = _run_streams(
                model, _serve_config(serve_mesh=mesh), work
            )
            placement = model.serving_placement
            say(f"four_chip: {placement.describe()}")
            named = "dense XLA paths" in placement.describe()
            check(
                named == (mode == "dense"),
                f"serve_mesh {mesh}: describe() does not name the path",
            )
            _check_kernel_engine(engine, mode, f"serve_mesh {mesh}")
            _same_streams(f"serve_mesh {mesh}", got, want)
            # the pools came back from the steps sharded as placed: no
            # step replicated them
            for g, pool in engine.cache.k.items():
                check(
                    pool.sharding.is_equivalent_to(
                        placement.kv_sharding(), pool.ndim
                    ),
                    f"serve_mesh {mesh}: pool {g} sharding {pool.sharding}",
                )
                _on_all(four, [(f"k pool {g}", pool)], f"serve_mesh {mesh}")
            out[f"serve_mesh_{mesh}"] = {
                "placement": placement.describe(),
                "decode_kernel": engine.decode_kernel,
                "bytes_in_use": _all_in_use(four, f"serve_mesh {mesh}"),
                **compiles.take(),
            }
            del engine, model
            gc.collect()
    return out


# -- main ---------------------------------------------------------------------


def main(argv) -> int:
    phases = [a for flag, a in zip(argv, argv[1:]) if flag == "--phase"]
    phases = phases or list(PHASES)
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        print(f"chip_smoke: unknown phase {unknown}; one of {PHASES}",
              file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            "chip_smoke: needs a TPU, and JAX found platform "
            f"{device.platform!r} (device_kind {device.device_kind!r}, "
            f"{len(jax.devices())} device(s)). Nothing was run.",
            file=sys.stderr,
        )
        return 2

    import jaxlib

    from flexflow_tpu import native
    from flexflow_tpu.core.machine import detect_chip
    from flexflow_tpu.ops import pallas
    from flexflow_tpu.utils.compile_cache import place_compile_cache

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not a separate package"
    cache_dir = place_compile_cache()
    chip = detect_chip()
    summary = {
        "ok": False,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
        "chip": chip,
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
        },
        "compile_cache_dir": cache_dir,
        "search_core": native.implementation(),
        "phases": {},
    }
    say(json.dumps({k: summary[k] for k in summary if k != "phases"}))
    compiles = Compiles()
    runners = {
        "sync": lambda: phase_sync(chip),
        "kernels": phase_kernels,
        "train": lambda: phase_train(chip, compiles),
        "serve": lambda: phase_serve(compiles),
        "families": lambda: phase_families(compiles),
        "four_chip": lambda: phase_four_chip(compiles),
    }
    for name in phases:
        if name == "four_chip" and len(jax.devices()) < 4:
            reason = (
                f"skipped: the four-chip leg needs 4 devices and JAX "
                f"reports {len(jax.devices())}"
            )
            say(f"four_chip: {reason}")
            summary["phases"][name] = {"skipped": reason}
            continue
        t0 = time.perf_counter()
        result = runners[name]()
        jax.block_until_ready(jax.live_arrays())
        result = dict(result)
        result["wall_s"] = round(time.perf_counter() - t0, 2)
        result["peak_bytes_in_use"] = peak_bytes()
        leftover = compiles.take()
        if leftover["compile_s"]:
            result["other_compile_s"] = leftover["compile_s"]
        summary["phases"][name] = result
        say(f"{name}: done in {result['wall_s']}s, peak bytes "
            f"{result['peak_bytes_in_use']}")
    check(
        pallas.TRACE_MODES["interpreted"] == 0,
        f"{pallas.TRACE_MODES['interpreted']} kernel trace(s) resolved to "
        "the Pallas interpreter on a TPU",
    )
    summary["kernel_traces"] = dict(pallas.TRACE_MODES)
    summary["wall_s"] = round(time.perf_counter() - t_start, 1)
    summary["ok"] = True
    summary["claim"] = None
    print(json.dumps(summary), flush=True)
    print(verdict(summary["device"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
