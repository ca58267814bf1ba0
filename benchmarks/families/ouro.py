"""The `ouro` family (a stack of layers run several times over ONE set of
weights, each pass with cache layers of its own, an exit gate after every
pass): `build_ouro` behind `build_scheduler` and the `FrontDoor`, built,
warmed, driven and judged as `families/olmoe.py` does it, by import: the
open loop, warm-up, spans and counts are `families/decoder_lm.py`'s.

`correct` is decided at the cell's load (`at_load`), outside the window, as
`families/deepseek_v3.py` decides its own and with its choice of samples
(`load_samples`, `kept_positions`, by import): every slot live, on requests
of the window's own plan with the longest prompts among them, each
prefilled through its bucket's program and decoded together,
teacher-forced, for hundreds of steps (contexts crossing many page
boundaries in every pass's cache layers, slots that end and are freed
while others go on), against the reference's full forward pass over the
same tokens. It is made twice: with the engine's step functions traced at
`highest` (this pass decides) and with the programs the window runs, at
the chip's default precision (held to a limit that garbage fails). The exit
distribution, which no step program returns, is read from the graph's own
gate nodes (`Executor.forward_values`, the plain lowering) at `highest` on
the longest sample and held to the reference's.

With the traffic parameter `load_controls` (`--override
load_controls=true`; the driver never passes it) the two controls behind
the limits are computed too and printed under `checks`: the reference in
bfloat16, and a reference that keeps one cache a layer for all passes.

What this family adds to the record, under `loop`: the engine's
`weight_walk` (bytes stored and applied, cache and weight layers, passes)
and, from a traced run, the decode program's device time by PCG node
(`flexflow_tpu.utils.profiling.fold_step` over the trace and the program's
compiled text: a compiler-made prefetch is charged to the node that uses
it). A program without either (the parent commit's) leaves them None.
"""

from __future__ import annotations

import asyncio

import numpy as np

from benchmarks.lib import window
from benchmarks.lib.loading import load_module

_lm = load_module("families", "decoder_lm")
_ds = load_module("families", "deepseek_v3")
SPANS, DECODE_MODULE, PREFILL_MODULE = _lm.SPANS, _lm.DECODE_MODULE, _lm.PREFILL_MODULE


def sizes_of(config: dict):
    """(eps, theta, loops) as the reference takes them."""
    return (
        config["rms_norm_eps"], float(config["rope_theta"]),
        config["total_ut_steps"],
    )


def build(config: dict, devices, seed: int):
    from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_ouro
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise RuntimeError("grouped key heads are not built")
    serve = config["serve"]
    cfg = FFConfig(batch_size=serve["max_seqs"])
    cfg.seed = int(seed) % (2**31 - 1)
    model = FFModel(cfg)
    tokens = model.create_tensor(
        [serve["max_seqs"], serve["max_seq_len"]], dtype=DataType.INT32,
        name="tokens",
    )
    head = build_ouro(
        model, tokens, vocab_size=config["vocab_size"],
        hidden=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        ff_dim=config["intermediate_size"], loops=config["total_ut_steps"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=list(devices)[:1],
        logits=head,
    )
    draw_gains(model, seed)
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


def draw_gains(model, seed: int):
    """The norms' gains (one from the builder) and the exit gate's bias
    (zero) are trained values of the checkpoint. Here they are drawn from
    the run's seed, gains uniform in [0.5, 1.5] and the bias in
    [-0.5, 0.5]: not one and not zero, so that a gain applied twice, left
    out, or taken from another layer shows."""
    import jax

    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    for guid in sorted(model.params):
        ws = model.params[guid]
        for i, w in enumerate(ws):
            if w.ndim != 1:
                continue
            lo = 0.5 if w.shape[0] > 1 else -0.5
            ws[i] = jax.device_put(
                jax.random.uniform(
                    jax.random.fold_in(key, 8 * guid + i), w.shape, w.dtype,
                    lo, lo + 1.0,
                ),
                w.sharding,
            )


def at_load(engine, cache, params, samples, page: int):
    """Serve `samples` together through the engine's public `prefill` and
    `decode`: admitted bucket by bucket (each bucket's own program), then
    decoded in one batch, every slot fed its own continuation and freed
    when that ends. Returns for each sample (positions, logits at them):
    the prefill's last position and the decode steps of
    `deepseek_v3.kept_positions`."""
    spec = cache.spec
    kept = [set(_ds.kept_positions(*sample, page)) for sample in samples]
    live, logits = {}, {}
    by_bucket = {}
    for i, (prompt, more) in enumerate(samples):
        by_bucket.setdefault(spec.bucket(len(prompt)), []).append(i)
        live[i] = cache.alloc(len(prompt), len(prompt) + len(more))
        if live[i] is None:
            raise RuntimeError("no free slot for the correctness samples")
    try:
        for _, group in sorted(by_bucket.items()):
            _, last = engine.prefill(
                params, [samples[i][0] for i in group], [live[i] for i in group],
            )
            for row, i in enumerate(group):
                logits[i] = {len(samples[i][0]) - 1: np.array(last[row])}
        for j in range(max(len(more) for _, more in samples)):
            tokens = np.zeros(spec.max_seqs, np.int32)
            active = np.zeros(spec.max_seqs, bool)
            for i, slot in live.items():
                tokens[slot], active[slot] = samples[i][1][j], True
            _, out = engine.decode(params, tokens, active)
            for i, slot in list(live.items()):
                prompt, more = samples[i]
                if len(prompt) + j in kept[i]:
                    logits[i][len(prompt) + j] = np.array(out[slot])
                if j + 1 == len(more):
                    cache.free(live.pop(i))
    finally:
        for slot in live.values():
            cache.free(slot)
    return [
        (sorted(logits[i]), np.stack([logits[i][p] for p in sorted(logits[i])]))
        for i in range(len(samples))
    ]


def exit_distribution(model, seq, pad_to: int, loops: int):
    """[len(seq), loops]: the exit distribution of the graph's own gate
    nodes (`p<t>.exit`) over `seq`, through the executor's plain lowering
    of the whole graph (no cache)."""
    import jax
    import jax.numpy as jnp

    ex = model.executor
    gates = [g for g in ex.topo if model.graph.nodes[g].name.endswith(".exit")]
    if len(gates) != loops:
        raise RuntimeError(f"{len(gates)} exit gates in the graph, {loops} passes")

    def forward(params, tokens):
        values = ex.forward_values(
            params, {"tokens": tokens}, rng=None, train=False, constrain=False
        )
        return jnp.stack([values[(g, 0)][0, :, 0] for g in gates], axis=-1)

    padded = np.zeros((1, pad_to), np.int32)
    padded[0, : len(seq)] = seq
    lam = np.asarray(jax.jit(forward)(model.params, jnp.asarray(padded)))[: len(seq)]
    stay = np.cumprod(1.0 - lam, axis=-1)
    return np.concatenate(
        [lam[:, :1], lam[:, 1:-1] * stay[:, :-2], stay[:, -2:-1]], axis=-1
    )


def decode_program_text(engine, cache, params):
    """The decode program's compiled text, scopes and all, from one more
    compile of it outside the persistent cache
    (`profiling.step_program_texts`), around one decode step of one slot."""
    from flexflow_tpu.utils import profiling

    spec = cache.spec
    slot = cache.alloc(1, 2)
    try:
        engine.prefill(params, [[1]], [slot])
        with profiling.step_program_texts(engine) as texts:
            tokens = np.zeros(spec.max_seqs, np.int32)
            active = np.zeros(spec.max_seqs, bool)
            tokens[slot], active[slot] = 1, True
            engine.decode(params, tokens, active)
    finally:
        cache.free(slot)
    return next(
        (text for name, text in texts.items() if name.startswith(DECODE_MODULE)),
        None,
    )


def decode_by_node(trace_path: str, text: str):
    """{"device_ms", "executions", "rows": [(scope, ms, of which charged
    ms)]} of the decode program in the trace, per execution."""
    from flexflow_tpu.utils import profiling

    profile = profiling.fold_step(
        profiling.read_device_events(trace_path), text, DECODE_MODULE
    )
    return {
        "device_ms": profile.device_ms,
        "executions": profile.executions,
        "accounted": profile.accounted,
        "rows": [(r.scope, r.total_ms, r.charged_ms) for r in profile.rows],
    }


def run(ctx) -> dict:
    config, traffic = ctx.config, ctx.traffic
    vocab = config["vocab_size"]
    model, sched, engine, cache = build(config, ctx.devices, ctx.seed)
    ctx.mark("model_and_scheduler_built")
    plan = load_module("generators", traffic["kind"]).generate(
        traffic, ctx.seed, ctx.seconds, vocab
    )
    buckets = _lm.warm_up(sched, cache, plan["lengths"], vocab, ctx.seed)
    ctx.mark("warmed_up")

    # correctness, outside the window and at its load (module docstring)
    import jax
    import jax.numpy as jnp

    reference = load_module("reference", config["family"])
    sizes, tol = sizes_of(config), config["tolerance"]
    page = cache.spec.page_size
    samples = _ds.load_samples(
        [p for p in plan["plan"] if p.segment == "window"],
        cache.spec.max_seqs, config["load_check"]["decode_steps"], vocab,
        ctx.seed,
    )
    seqs = [prompt + more for prompt, more in samples]
    pad_to = -(-max(len(q) for q in seqs) // 128) * 128
    weights = [list(model.params[guid]) for guid in sorted(model.params)]
    kept = [_ds.kept_positions(*sample, page) for sample in samples]
    wanted = [
        reference.run(weights, q, pad_to, *sizes, positions=at)
        for q, at in zip(seqs, kept)
    ]
    longest = max(range(len(seqs)), key=lambda i: len(seqs[i]))
    want_exits = reference.run(weights, seqs[longest], pad_to, *sizes)[1]

    def compare():
        got = at_load(engine, cache, model.params, samples, page)
        return max(
            _ds.rel_gap(logits, want) for (_, logits), (want, _) in zip(got, wanted)
        )

    with jax.default_matmul_precision("highest"):
        exact = compare()
        exits_gap = float(np.max(np.abs(
            exit_distribution(model, seqs[longest], pad_to, sizes[2]) - want_exits
        )))
    served = exact if ctx.rehearse else compare()
    controls = None
    if traffic.get("load_controls"):
        # what the limits are set between; decides nothing
        at = [longest, min(range(len(seqs)), key=lambda i: len(seqs[i]))]
        controls = {
            name: max(
                _ds.rel_gap(
                    reference.run(
                        weights, seqs[i], pad_to, *sizes, positions=kept[i], **kw
                    )[0],
                    wanted[i][0],
                )
                for i in at
            )
            for name, kw in (
                ("reference_in_bfloat16", {"dtype": jnp.bfloat16}),
                ("reference_with_one_cache_a_layer", {"one_cache": True}),
            )
        }
    ctx.mark("logits_checked")

    text = None
    if ctx.trace and not (ctx.may_start_over and ctx.compiles.cache_misses):
        try:
            text = decode_program_text(engine, cache, model.params)
        except Exception as e:  # a program without the hook: no table
            print(f"families/ouro.py: no decode program text: {e!r}", flush=True)
        ctx.mark("decode_program_text")

    from flexflow_tpu.serving.frontend.server import FrontDoor

    backend = _lm.SteppedBackend(sched, cache)
    records = [
        _lm.Served(p.index, p.segment, 0.0, len(p.prompt), p.max_new_tokens)
        for p in plan["plan"]
    ]
    window.settle(ctx)

    async def main():
        door = FrontDoor(backend)
        return await _lm.drive(ctx, door, backend, plan, traffic, records, vocab)

    t0, w0, w1, window_compiles = asyncio.run(main())
    traced = (ctx.tracer.t_start, ctx.tracer.t_stop)
    ctx.mark("window_and_drain_driven")
    ctx.tracer.stop()
    ctx.mark("trace_stopped")
    by_node = None
    if text and ctx.tracer.path:
        try:
            by_node = decode_by_node(ctx.tracer.path, text)
        except Exception as e:  # no device plane, no execution in the trace
            print(f"families/ouro.py: no decode table: {e!r}", flush=True)
        ctx.mark("decode_folded_by_node")

    judged = [r for r in records if r.segment == "window"]
    failed = [
        r for r in judged
        if r.status != "finished" or r.tokens != r.asked or r.bad_tokens
    ]
    checks = {
        "load": {
            "slots": len(samples),
            "prompts": sorted(len(prompt) for prompt, _ in samples),
            "contexts": sorted(len(q) for q in seqs),
            "rows": sum(len(at) for at in kept),
        },
        "logits_rel_gap_at_highest": exact,
        "logits_rel_gap": served,
        "logits_within_tolerance": bool(
            exact <= tol["logits_highest_rel"]
            and served <= tol["logits_default_rel"]
        ),
        "exit_distribution_abs_gap_at_highest": exits_gap,
        "exits_within_tolerance": bool(exits_gap <= tol["exits_highest_abs"]),
        "controls": controls,
        "kernel_fallbacks": int(engine.kernel_fallbacks),
        "decode_kernel": str(engine.decode_kernel),
        "kernel_block": engine.kernel_block and list(engine.kernel_block),
        "every_judged_request_finished_whole": not failed,
        "first_failure": (
            f"{failed[0].status}: {failed[0].error} ({failed[0].tokens}/"
            f"{failed[0].asked} tokens)" if failed else None
        ),
        "prefill_buckets_warmed": buckets,
    }
    spec = cache.spec
    return {
        "observed": _lm.observe(records, w0, w1),
        "kind": "serve",
        "spans": SPANS,
        "correct": bool(
            checks["logits_within_tolerance"]
            and checks["exits_within_tolerance"]
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
        "max_seqs": spec.max_seqs,
        "num_pages": spec.num_pages,
        "page_size": spec.page_size,
        "bucket_of": spec.bucket,
        "decode_module": DECODE_MODULE,
        "prefill_module": PREFILL_MODULE,
        # a cache layer for every (pass, layer): what a decode step reads
        "kv": {
            "layers": len(spec.layer_guids), "heads": spec.num_heads,
            "head_dim": spec.head_dim, "itemsize": spec.itemsize,
        },
        "loop": {
            "passes": sizes[2],
            "walk": getattr(engine, "weight_walk", None),
            "decode_by_node": by_node,
        },
    }
