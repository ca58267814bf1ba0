"""The OLMoE family: `build_olmoe` behind `build_scheduler` and the
`FrontDoor`, driven and judged exactly as `families/decoder_lm.py` drives
its decoder (the same open loop, warm-up, spans, counts and logits
comparison, by import), plus what the expert layer adds: the share of
(position, layer) top-k expert sets that equal the reference's, the
engine's `moe_*` counters per step, and the device time under the
program's `moe.*` named scopes, read from the trace here because
`lib/trace.py` keeps no scope.
"""

from __future__ import annotations

import asyncio
import types

import numpy as np

from benchmarks.lib import scopes, window
from benchmarks.lib.loading import load_module

_lm = load_module("families", "decoder_lm")
SPANS, DECODE_MODULE, PREFILL_MODULE = _lm.SPANS, _lm.DECODE_MODULE, _lm.PREFILL_MODULE
MOE_SCOPES = ("moe.route", "moe.sort", "moe.experts", "moe.combine")


def build(config: dict, devices, seed: int):
    from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_olmoe
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    serve = config["serve"]
    cfg = FFConfig(batch_size=serve["max_seqs"])
    cfg.seed = int(seed) % (2**31 - 1)
    model = FFModel(cfg)
    tokens = model.create_tensor(
        [serve["max_seqs"], serve["max_seq_len"]], dtype=DataType.INT32,
        name="tokens",
    )
    build_olmoe(
        model, tokens, vocab_size=config["vocab_size"],
        hidden=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        expert_hidden=config["intermediate_size"],
        num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
        renormalise=config["norm_topk_prob"],
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


class MoeBackend(_lm.SteppedBackend):
    """`SteppedBackend`, and after each step the engine's expert-layer
    counters: (step end, decode steps, rows and experts touched in
    decode, prefill batches, rows and experts touched in prefill), all
    cumulative."""

    def __init__(self, sched, cache, engine):
        super().__init__(sched, cache)
        self._engine = engine
        self.moe_steps = []

    def step(self):
        super().step()
        e = self._engine
        self.moe_steps.append((
            self.steps[-1][1], self._sched.stats.decode_steps,
            e.moe_rows_decode, e.moe_experts_touched_decode,
            self._sched.stats.prefill_batches,
            e.moe_rows_prefill, e.moe_experts_touched_prefill,
        ))


def routing_agreement(model, chosen_ref, seq, pad_to):
    """The share of (position, layer) top-k expert SETS that the program
    picks as the reference does, over `seq`: the program's choice is read
    from its own lowering (`executor.forward_values`, the chip's default
    matmul precision, the router in float32 at `highest` as it is served)
    through `sparse_moe_route` on each expert layer's input."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.core.types import OperatorType
    from flexflow_tpu.ops import moe

    ex = model.executor

    def forward(params, tokens):
        picked = []

        def hook(node, ins, ws, ctx):
            x2 = ins[0].reshape(-1, ins[0].shape[-1])
            picked.append(moe.sparse_moe_route(
                x2, ws[0], node.params["k"], node.params["renormalise"]
            )[1])
            return [moe.sparse_moe(ins[0], ws, node.params, ctx)[0]]

        ex.forward_values(
            params, {"tokens": tokens}, rng=None, train=False,
            op_hooks={OperatorType.SPARSE_MOE: hook}, constrain=False,
        )
        return jnp.stack(picked)

    padded = np.zeros((1, pad_to), np.int32)
    padded[0, : len(seq)] = seq
    got = np.asarray(jax.jit(forward)(model.params, jnp.asarray(padded)))
    got = np.sort(got[:, : len(seq)], axis=-1)
    want = np.sort(np.asarray(chosen_ref)[:, : len(seq)], axis=-1)
    same = np.all(got == want, axis=-1)
    return float(np.mean(same)), int(same.size - same.sum()), int(same.size)


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

    # correctness, outside the window: prefill then three cached decode
    # steps against the reference's full forward pass, on the request of
    # median prompt length (the same length, bucket and programs for
    # every seed; the seed picks its token ids)
    reference = load_module("reference", config["family"])
    eps, theta, k = (
        config["rms_norm_eps"], float(config["rope_theta"]),
        config["num_experts_per_tok"],
    )
    bound = types.SimpleNamespace(
        logits_at=lambda w, seq, pos, pad, eps_: reference.logits_at(
            w, seq, pos, pad, eps_, theta, k
        )
    )
    window_plan = [p for p in plan["plan"] if p.segment == "window"]
    by_length = sorted(window_plan, key=lambda p: (len(p.prompt), p.index))
    sample = by_length[len(by_length) // 2].prompt
    pad_to = -(-(len(sample) + 3) // 128) * 128  # as check_logits pads
    weights = [list(model.params[guid]) for guid in sorted(model.params)]
    _, chosen = reference.run(weights, sample, pad_to, eps, theta, k)

    def compare():
        return (
            _lm.check_logits(engine, cache, model.params, bound, [sample], 3, eps),
            *routing_agreement(model, chosen, sample, pad_to),
        )

    # twice: the engine's own step functions traced at `highest` (the
    # precision is part of jit's key, so these are other executables of
    # the same code: the comparison that is tight), and the programs the
    # window runs, at the chip's default precision, where the router's
    # near-ties flip (the tolerance in the configuration file says how
    # many and what they cost)
    import jax

    with jax.default_matmul_precision("highest"):
        exact = compare()
    served = exact if ctx.rehearse else compare()
    ctx.mark("logits_checked")

    from flexflow_tpu.serving.frontend.server import FrontDoor

    backend = MoeBackend(sched, cache, engine)
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
    scope_times = None
    if ctx.trace and ctx.tracer.path:
        scope_times = scopes.scope_seconds(
            ctx.tracer.path, MOE_SCOPES, (DECODE_MODULE, PREFILL_MODULE),
            span="bench.trace", compiler_ops={"ragged-dot": "moe.experts"},
        )
        ctx.mark("scopes_read")

    tol = config["tolerance"]
    judged = [r for r in records if r.segment == "window"]
    failed = [
        r for r in judged
        if r.status != "finished" or r.tokens != r.asked or r.bad_tokens
    ]
    checks = {
        "logits_rel_gap": served[0],
        "logits_rel_gap_at_highest": exact[0],
        "logits_within_tolerance": (
            exact[0] <= tol["logits_highest_rel"]
            and served[0] <= tol["logits_default_rel"]
        ),
        "routing_sets_equal_share": served[1],
        "routing_sets_equal_share_at_highest": exact[1],
        "routing_sets_flipped_of": [served[2], served[3]],
        "routing_within_tolerance": (
            exact[1] >= tol["routing_highest_share_min"]
            and served[1] >= tol["routing_default_share_min"]
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
        "observed": _lm.observe(records, w0, w1),
        "kind": "serve",
        "spans": SPANS,
        "correct": bool(
            checks["logits_within_tolerance"]
            and checks["routing_within_tolerance"]
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
            "layers": config["num_hidden_layers"],
            "heads": config["num_attention_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "itemsize": cache.spec.itemsize,
        },
        "moe": {
            "layers": config["num_hidden_layers"],
            "experts": config["num_experts"],
            "k": k,
            "hidden": config["hidden_size"],
            "expert_hidden": config["intermediate_size"],
            "itemsize": 4,
            "steps": backend.moe_steps,
            "scope_seconds": scope_times,
        },
    }
