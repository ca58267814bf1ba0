"""The server family: a decoder LM behind `build_scheduler` and the
`FrontDoor`, driven open-loop by one asyncio loop.

What is the benchmark's and what is the program's: the program is the
model builder, `ServeConfig`, `build_scheduler`, the `FrontDoor` and
the engine's public `prefill` / `decode`. The benchmark's are the traffic,
the clock, the spans around the calls into each layer (`scheduler.step`
around every step, `door.pump` between steps, `door.submit`,
`gen.sleep`), the per-step counts read from `SchedulerStats` and the
allocator, and the comparison with the plain reference.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from benchmarks.lib import window
from benchmarks.lib.loading import load_module

SPANS = ("bench.trace", "scheduler.step", "door.pump", "door.submit", "gen.sleep")
DECODE_MODULE = "jit__decode_impl_paged"
PREFILL_MODULE = "jit__prefill_impl_paged"


def build(config: dict, devices, seed: int):
    """chip_smoke.py's `_decoder` and `_serve_config`, by copy, at the
    configuration's sizes."""
    from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_decoder_lm
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    serve = config["serve"]
    cfg = FFConfig(batch_size=serve["max_seqs"])
    cfg.seed = int(seed) % (2**31 - 1)
    model = FFModel(cfg)
    tokens = model.create_tensor(
        [serve["max_seqs"], serve["max_seq_len"]], dtype=DataType.INT32,
        name="tokens",
    )
    build_decoder_lm(
        model, tokens, vocab_size=config["vocab_size"], hidden=config["n_embd"],
        num_heads=config["n_head"], num_layers=config["n_layer"],
        ff_dim=config["n_inner"],
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=list(devices)[:1],
    )
    page = ServeConfig().kv_page_size or 16
    sc = ServeConfig(
        max_seqs=serve["max_seqs"],
        max_seq_len=serve["max_seq_len"],
        kv_pages=serve["kv_pool_tokens"] // page,
        prefill_buckets=tuple(serve.get("prefill_buckets", ())),
    )
    if sc.kv_layout != "paged" or sc.decode_kernel != "auto":
        raise RuntimeError("ServeConfig() defaults moved: the cell serves them")
    sched, engine, cache = build_scheduler(model, sc)
    return model, sched, engine, cache


class SteppedBackend:
    """The scheduler as the front door drives it, with the benchmark's
    spans and counts around each `step()`. Everything else is the
    scheduler's own."""

    def __init__(self, sched, cache):
        self._sched = sched
        self._cache = cache
        self.steps = []  # (t0, t1, decode_steps, prefill_batches, busy, slots, ctx_sum, pages, live)
        self._pump = None

    def __getattr__(self, name):
        return getattr(self._sched, name)

    def submit(self, request, *a, **kw):
        return self._sched.submit(request, *a, **kw)

    def cancel(self, rid):
        return self._sched.cancel(rid)

    def work_pending(self):
        return self._sched.work_pending()

    def close_pump_span(self):
        if self._pump is not None:
            self._pump.__exit__(None, None, None)
            self._pump = None

    def step(self):
        self.close_pump_span()
        t0 = time.perf_counter()
        with window.span("scheduler.step"):
            self._sched.step()
        t1 = time.perf_counter()
        st, cache = self._sched.stats, self._cache
        active = cache.active_slots()
        self.steps.append((
            t0, t1, st.decode_steps, st.prefill_batches, st.busy_slot_steps,
            st.slot_steps, int(cache.lengths[active].sum()) if active else 0,
            cache.pages_in_use, len(active),
        ))
        self._pump = window.span("door.pump")
        self._pump.__enter__()


@dataclass
class Served:
    index: int
    segment: str
    due: float
    prompt_len: int
    asked: int
    started: float = 0.0
    accepted: float = 0.0
    first: float = 0.0
    last: float = 0.0
    done: float = 0.0
    tokens: int = 0
    bad_tokens: int = 0
    status: Optional[str] = None
    error: Optional[str] = None
    rid: int = -1
    cancelled_by_harness: bool = False
    admit: float = 0.0
    admit_iter: int = -1


def warm_up(sched, cache, lengths: List[int], vocab: int, seed: int):
    """One request per prefill bucket the traffic will use, two tokens
    each, so that every prefill program and the decode program have run
    (and every small program beside them) before the window."""
    from flexflow_tpu.serving import Request

    rng = np.random.Generator(np.random.PCG64([int(seed), 2]))
    by_bucket = {}
    for n in lengths:
        by_bucket.setdefault(cache.spec.bucket(n), n)
    for i, (bucket, n) in enumerate(sorted(by_bucket.items())):
        # one at a time: a joint admission would pad all to the largest
        done = sched.run([Request(
            rid=10**6 + i, prompt=rng.integers(1, vocab, size=n).tolist(),
            max_new_tokens=3,
        )])
        bad = [r for r in done if r.rid == 10**6 + i and r.status != "finished"]
        if bad:
            raise RuntimeError(f"warm-up request failed: {bad[0].error}")
    # a joint admission of n prompts slices its n rows out of the padded
    # batch, one small program per n: run every n the slots allow
    short = min(lengths)
    rid = 2 * 10**6
    for n in range(2, cache.spec.max_seqs + 1):
        batch = [
            Request(rid=rid + j, max_new_tokens=2,
                    prompt=rng.integers(1, vocab, size=short).tolist())
            for j in range(n)
        ]
        rid += n
        sched.run(batch)
        if any(r.status != "finished" for r in batch):
            raise RuntimeError(f"warm-up admission of {n} failed")
    return sorted(by_bucket)


def check_logits(engine, cache, params, reference, prompts, steps, eps):
    """Prefill, then `steps` cached decode steps, through the engine's
    public `prefill` / `decode`, against the reference's full forward
    pass over the same tokens: the last position's logits each time.
    These are the programs the window runs, at the TPU's default matmul
    precision. (Run once under `jax.default_matmul_precision("highest")`,
    which compiles the engine's programs again with float32 matmuls, the
    gap read 1.3e-6 on the chip in PR 23; the CPU rehearsal, where the
    default is exact float32, holds that every time.) Returns the worst
    max|got - want| / max|want|."""
    weights = [list(params[guid]) for guid in sorted(params)]
    slots = cache.spec.max_seqs
    gap = 0.0
    for prompt in prompts:
        slot = cache.alloc(len(prompt), len(prompt) + steps)
        if slot is None:
            raise RuntimeError("no free slot for the correctness sample")
        try:
            nxt, last = engine.prefill(params, [prompt], [slot])
            seq, got, tok = list(prompt), [np.asarray(last[0])], int(nxt[0])
            for _ in range(steps):
                seq.append(tok)
                tokens = np.zeros(slots, np.int32)
                active = np.zeros(slots, bool)
                tokens[slot], active[slot] = tok, True
                nxt, logits = engine.decode(params, tokens, active)
                got.append(np.asarray(logits[slot]))
                tok = int(nxt[slot])
        finally:
            cache.free(slot)
        positions = [len(prompt) - 1 + i for i in range(steps + 1)]
        pad_to = -(-len(seq) // 128) * 128
        want = reference.logits_at(weights, seq, positions, pad_to, eps)
        got = np.stack(got)
        gap = max(gap, float(
            np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
        ))
    return gap


async def drive(ctx, door, backend, plan, traffic, records, vocab):
    """The open loop: one generator task that sleeps until each request
    is due and starts a client for it; clients stamp what they see."""
    t0 = time.perf_counter()
    for r, p in zip(records, plan["plan"]):
        r.due = t0 + p.due_s
    w0, w1 = t0 + plan["window"][0], t0 + plan["window"][1]
    clients = []

    async def client(p, rec):
        with window.span("door.submit"):
            rec.rid = await door.submit(p.prompt, max_new_tokens=p.max_new_tokens)
        rec.accepted = time.perf_counter()
        async for ev in door.stream(rec.rid):
            now = time.perf_counter()
            if ev.kind == "token":
                if not rec.tokens:
                    rec.first = now
                rec.last = now
                rec.tokens += 1
                if not 0 <= ev.token < vocab:
                    rec.bad_tokens += 1
            else:
                rec.status, rec.error, rec.done = ev.status, ev.error, now
        req = door.request(rec.rid)
        if req is not None:
            rec.admit_iter = req.admit_iter
            rec.admit = next((t for t, what, _ in req.events if what == "admit"), 0.0)

    async def generator():
        for p, rec in zip(plan["plan"], records):
            delay = rec.due - time.perf_counter()
            if delay > 0:
                with window.span("gen.sleep"):
                    await asyncio.sleep(delay)
            rec.started = time.perf_counter()
            clients.append(asyncio.ensure_future(client(p, rec)))

    async def tracer():
        # the traced part is the window's last trace_s seconds. The session
        # is stopped only after the last client has ended (`run`): stopping
        # it stalls the loop for seconds, and inside the window that stall
        # was a backlog (traced TTFT p50 1036 ms against 277; PR 23)
        start = max(w0, w1 - float(traffic["trace_s"]))
        await asyncio.sleep(max(0.0, start - time.perf_counter()))
        ctx.tracer.start()
        with window.span("bench.trace"):
            await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
        ctx.tracer.t_stop = time.perf_counter()

    async def mark_window():
        await asyncio.sleep(max(0.0, w0 - time.perf_counter()))
        ctx.compiles.reset()
        await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
        return ctx.compiles.snapshot()

    gen_task = asyncio.ensure_future(generator())
    mark_task = asyncio.ensure_future(mark_window())
    trace_task = asyncio.ensure_future(tracer()) if ctx.trace else None
    await gen_task
    window_compiles = await mark_task
    counted = [r for r in records if r.segment == "window"]
    if traffic["mode"] == "latency":
        # follow the window's requests to their end, up to the stated time
        deadline = w1 + float(traffic["finish_timeout_s"])
        while time.perf_counter() < deadline and any(not r.done for r in counted):
            await asyncio.sleep(0.05)
    else:
        await asyncio.sleep(max(0.0, w1 - time.perf_counter()))
    for r in records:
        if r.rid >= 0 and not r.done:
            r.cancelled_by_harness = True
            await door.cancel(r.rid)
    await door.drain()
    await asyncio.gather(*clients)
    if trace_task is not None:
        await trace_task
    backend.close_pump_span()
    return t0, w0, w1, window_compiles


def observe(records, w0, w1) -> dict:
    """What a sweep reads to find the knee: not metrics, observations."""
    from benchmarks.lib import readers, stats

    win = [r for r in records if r.segment == "window"]
    ok = [r for r in win if readers.served_whole(r)]
    ttft = [1e3 * (r.first - r.due) for r in ok]
    tpot = [v for v in readers.tpots_of(ok) if v != float("inf")]
    half = w0 + 0.5 * (w1 - w0)
    return {
        "window_requests": len(win),
        "finished_whole": len(ok),
        "not_done_at_window_end": sum(1 for r in win if not r.done or r.done > w1),
        "output_tokens_per_s": sum(r.tokens for r in ok) / (w1 - w0),
        "ttft_ms_p50_p90_p99": [stats.percentile(ttft, p, beyond=0) for p in (50, 90, 99)],
        "tpot_ms_p50_p90_p99": [stats.percentile(tpot, p, beyond=0) for p in (50, 90, 99)],
        "ttft_ms_p50_first_half_second_half": [
            stats.median([1e3 * (r.first - r.due) for r in ok if r.due < half]),
            stats.median([1e3 * (r.first - r.due) for r in ok if r.due >= half]),
        ],
    }


def run(ctx) -> dict:
    config, traffic = ctx.config, ctx.traffic
    vocab = config["vocab_size"]
    model, sched, engine, cache = build(config, ctx.devices, ctx.seed)
    ctx.mark("model_and_scheduler_built")
    plan = load_module("generators", traffic["kind"]).generate(
        traffic, ctx.seed, ctx.seconds, vocab
    )
    buckets = warm_up(sched, cache, plan["lengths"], vocab, ctx.seed)
    ctx.mark("warmed_up")

    # correctness, outside the window
    reference = load_module("reference", config["family"])
    window_plan = [p for p in plan["plan"] if p.segment == "window"]
    # the request of median prompt length: the same length, bucket and
    # programs for every seed (the seed picks its token ids)
    by_length = sorted(window_plan, key=lambda p: (len(p.prompt), p.index))
    sample = [by_length[len(by_length) // 2].prompt]
    gap = check_logits(
        engine, cache, model.params, reference, sample, 3,
        config["layer_norm_epsilon"],
    )

    ctx.mark("logits_checked")

    from flexflow_tpu.serving.frontend.server import FrontDoor

    backend = SteppedBackend(sched, cache)
    records = [
        Served(p.index, p.segment, 0.0, len(p.prompt), p.max_new_tokens)
        for p in plan["plan"]
    ]
    window.settle(ctx)

    async def main():
        door = FrontDoor(backend)
        return await drive(ctx, door, backend, plan, traffic, records, vocab)

    t0, w0, w1, window_compiles = asyncio.run(main())
    traced = (ctx.tracer.t_start, ctx.tracer.t_stop)
    ctx.mark("window_and_drain_driven")
    ctx.tracer.stop()
    ctx.mark("trace_stopped")

    tol = config["tolerance"]
    counted = [r for r in records if r.segment == "window"]
    latency = traffic["mode"] == "latency"
    if latency:
        judged = counted
        failed = [
            r for r in judged
            if r.status != "finished" or r.tokens != r.asked or r.bad_tokens
        ]
    else:
        # above the knee a backlog stands by design: judged are the
        # requests that reached a verdict of their own inside the run
        judged = [r for r in records if r.done and not r.cancelled_by_harness]
        failed = [
            r for r in judged
            if r.status != "finished" or r.tokens != r.asked or r.bad_tokens
        ]
    checks = {
        "logits_rel_gap": gap,
        "logits_within_tolerance": gap <= (
            tol["logits_exact_f32_rel"] if ctx.rehearse else tol["logits_default_rel"]
        ),
        "kernel_fallbacks": int(engine.kernel_fallbacks),
        "decode_kernel": str(engine.decode_kernel),
        "every_judged_request_finished_whole": not failed,
        "first_failure": (
            f"{failed[0].status}: {failed[0].error} ({failed[0].tokens}/"
            f"{failed[0].asked} tokens)" if failed else None
        ),
        "prefill_buckets_warmed": buckets,
    }
    return {
        "observed": observe(records, w0, w1),
        "kind": "serve",
        "spans": SPANS,
        "correct": bool(
            checks["logits_within_tolerance"]
            and checks["kernel_fallbacks"] == 0
            and not failed
            and judged
        ),
        "checks": checks,
        "attempted": len(judged),
        "failed": len(failed),
        "window_start": w0,
        "window": (w0, w1),
        "trace_window": traced,
        "requests": records,
        "steps": backend.steps,
        "compiles": window_compiles,
        "mode": traffic["mode"],
        "max_seqs": cache.spec.max_seqs,
        "num_pages": cache.spec.num_pages,
        "page_size": cache.spec.page_size,
        "bucket_of": cache.spec.bucket,
        "decode_module": DECODE_MODULE,
        "prefill_module": PREFILL_MODULE,
        "kv": {
            "layers": config["n_layer"], "heads": config["n_head"],
            "head_dim": config["n_embd"] // config["n_head"],
            "itemsize": cache.spec.itemsize,
        },
    }
