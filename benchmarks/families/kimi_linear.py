"""The `kimi_linear` family (Kimi Delta Attention layers, whose fixed-size
per-slot state lives beside the latent paged pool of the latent-attention
layers without positions; a leading dense layer; the `deepseek_v3` expert
layer of which this chip holds a share): `build_kimi_linear` behind
`build_scheduler` and the `FrontDoor`, driven and judged as
`families/deepseek_v3.py` drives and judges its model, by import: the
samples, the load check (`at_load`), the comparison's parts and the
latent counters are that file's, the open loop, warm-up, spans and counts
`families/decoder_lm.py`'s. That file's `run` reads its own config keys
and calls its own builder and reference, and no file that is there may be
edited, so this file has a `build` and a `run` of its own around them.

`correct` is decided at the cell's load, twice (at `highest`, and the
programs the window runs), each pass held to the configuration's
`tolerance` exactly as the `deepseek_v3` family's: the logits against the
reference under the program's own choice of experts, and the share of its
top-k sets equal to the reference's. What is new in kind is checked by
the same comparison: a slot's state after a packed prefill, advanced by
hundreds of decode steps beside slots that end and are freed, gives the
reference's logits, whose recurrence runs token by token.

With the traffic parameter `load_controls` (`--override
load_controls=true`; the driver never passes it) the controls behind the
limits are computed too and printed under `checks.controls`, on the
longest and the shortest sample: the reference whose every KDA layer
forgets its state where decode starts (a program that began decode from
the zero state instead of the prefill's), the reference whose decode
tokens see zeros for the convolution inputs of the prompt (a program that
dropped the tails), and the reference with every weight rounded to
bfloat16 (the precision below the configuration's), each as the logits
gap against the reference proper; the share of top-k sets equal under the
bfloat16 weights beside it.

What this family adds to the record: under `kda` the sizes of the
recurrent layers, the engine's counters per step (`state_rows_decode`,
`state_resets_prefill`, the prefill programs and the tokens they were
given) and the device time under the program's `kda.*` named scopes;
`moe` and `mla` as the `deepseek_v3` family leaves them.
"""

from __future__ import annotations

import asyncio

import numpy as np

from benchmarks.lib import scopes, window
from benchmarks.lib.loading import load_module

_ds = load_module("families", "deepseek_v3")
_lm = _ds._lm
SPANS, DECODE_MODULE, PREFILL_MODULE = _lm.SPANS, _lm.DECODE_MODULE, _lm.PREFILL_MODULE
MOE_SCOPES, MLA_SCOPES = _ds.MOE_SCOPES, _ds.MLA_SCOPES
KDA_SCOPES = ("kda.project", "kda.conv", "kda.scan", "kda.step", "kda.out")
KDA_CHUNK = 64


def sizes_of(config: dict):
    """(eps, rope, k, scale, held) as the reference takes them."""
    return (
        config["rms_norm_eps"], config["qk_rope_head_dim"],
        config["num_experts_per_token"], config["routed_scaling_factor"],
        (config["experts_held_first"], config["num_experts"]),
    )


def pattern_of(config: dict):
    """(kda layers, full-attention layers) up to the depth that is built,
    from the published lists (numbered from 1)."""
    lin, n = config["linear_attn_config"], config["num_hidden_layers"]
    return (
        tuple(i for i in lin["kda_layers"] if i <= n),
        tuple(i for i in lin["full_attn_layers"] if i <= n),
    )


def build(config: dict, devices, seed: int):
    from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_kimi_linear
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    if config["q_lora_rank"] is not None or config["num_expert_group"] != 1:
        raise RuntimeError("query compression and grouped routing are not built")
    if not config["mla_use_nope"]:
        raise RuntimeError("the family's latent attention has no positions")
    serve, lin = config["serve"], config["linear_attn_config"]
    cfg = FFConfig(batch_size=serve["max_seqs"])
    cfg.seed = int(seed) % (2**31 - 1)
    model = FFModel(cfg)
    tokens = model.create_tensor(
        [serve["max_seqs"], serve["max_seq_len"]], dtype=DataType.INT32,
        name="tokens",
    )
    kda_layers, full_layers = pattern_of(config)
    build_kimi_linear(
        model, tokens, vocab_size=config["vocab_size"],
        hidden=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        kda_layers=kda_layers, full_attn_layers=full_layers,
        kda_head_dim=lin["head_dim"],
        kda_conv_kernel=lin["short_conv_kernel_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_hidden=config["intermediate_size"],
        dense_layers=config["first_k_dense_replace"],
        expert_hidden=config["moe_intermediate_size"],
        num_experts=config["router_width"],
        experts_per_token=config["num_experts_per_token"],
        shared_experts=config["num_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"], renormalise=config["moe_renormalize"],
        experts_held=(config["experts_held_first"], config["num_experts"]),
        kda_chunk=config.get("kda_chunk", KDA_CHUNK),
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=list(devices)[:1],
    )
    _ds.draw_choice_bias(model, seed)
    draw_kda_buffers(model, seed)
    page = ServeConfig().kv_page_size or 16
    sc = ServeConfig(
        max_seqs=serve["max_seqs"],
        max_seq_len=serve["max_seq_len"],
        kv_pages=serve["kv_pool_tokens"] // page,
        prefill_buckets=tuple(serve.get("prefill_buckets", ())),
    )
    if sc.kv_layout != "paged" or sc.decode_kernel != "auto" or not sc.serve_async:
        raise RuntimeError("ServeConfig() defaults moved: the cell serves them")
    sched, engine, cache = build_scheduler(model, sc)
    return model, sched, engine, cache


def draw_kda_buffers(model, seed: int):
    """Each KDA layer's trained buffers (zero from the builder), drawn
    from the run's seed so that the recurrence shows (the configuration's
    `assumed.kda_buffers`): dt_bias uniform in [-4.5, -2.5], A_log uniform
    in [log 0.5, log 2], and the decay's second matrix scaled by 3."""
    import jax

    from flexflow_tpu.core.types import OperatorType

    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    for guid in sorted(model.params):
        if model.graph.nodes[guid].op_type == OperatorType.LINEAR_ATTENTION:
            ws, at = model.params[guid], jax.random.fold_in(key, guid)
            ws[7] = 3.0 * ws[7]
            ws[8] = jax.random.uniform(
                jax.random.fold_in(at, 1), ws[8].shape, ws[8].dtype, -4.5, -2.5
            )
            ws[9] = jax.random.uniform(
                jax.random.fold_in(at, 2), ws[9].shape, ws[9].dtype,
                float(np.log(0.5)), float(np.log(2.0)),
            )


class StateBackend(_ds.LatentBackend):
    """`LatentBackend`, and after each step the engine's counters of the
    recurrent layers, all cumulative: (step end, decode steps, (live slot,
    layer) state rows advanced in decode, prefill programs, the tokens
    their rows held, (request, layer) rows written by prefills)."""

    def __init__(self, sched, cache, engine):
        super().__init__(sched, cache, engine)
        self.kda_steps = []

    def step(self):
        super().step()
        e, st = self._engine, self._sched.stats
        self.kda_steps.append((
            self.steps[-1][1], st.decode_steps,
            getattr(e, "state_rows_decode", 0), e.prefill_programs,
            e.prefill_tokens_padded, getattr(e, "state_resets_prefill", 0),
        ))


def controls_of(reference, weights, seqs, samples, wanted, kept, pad_to, sizes):
    """What the limits are set between; decides nothing. On the longest
    and the shortest sample, against the reference proper at the same
    rows: {name: worst logits gap}, and the share of top-k sets that stay
    equal under bfloat16 weights."""
    at = sorted({
        max(range(len(seqs)), key=lambda i: len(seqs[i])),
        min(range(len(seqs)), key=lambda i: len(seqs[i])),
    })
    out, same = {}, [0, 0]
    for name, kw in (
        ("reference_decoding_from_the_zero_state", "zero_state"),
        ("reference_decoding_without_the_tails", "no_tails"),
        ("reference_with_bfloat16_weights", None),
    ):
        gaps = []
        for i in at:
            fault = None if kw is None else (kw, len(samples[i][0]))
            logits, chosen = reference.run(
                weights, seqs[i], pad_to, *sizes, positions=kept[i],
                fault=fault, bf16=kw is None,
            )
            gaps.append(_ds.rel_gap(logits, wanted[i][0]))
            if kw is None:
                same = np.add(same, _ds.sets_equal(chosen, wanted[i][1]))
        out[name] = max(gaps)
    out["routing_sets_equal_share_with_bfloat16_weights"] = same[0] / same[1]
    return out


def run(ctx) -> dict:
    config, traffic = ctx.config, ctx.traffic
    vocab = config["vocab_size"]
    model, sched, engine, cache = build(config, ctx.devices, ctx.seed)
    ctx.mark("model_and_scheduler_built")
    plan = load_module("generators", traffic["kind"]).generate(
        traffic, ctx.seed, ctx.seconds, vocab
    )
    import jax

    buckets = _lm.warm_up(sched, cache, plan["lengths"], vocab, ctx.seed)
    if not ctx.rehearse:
        # the load check's first pass runs the engine's programs traced at
        # `highest`: other executables, and other small programs beside
        # them (an admission of n prompts slices n rows). Which n its
        # groups have follows the seed's samples; warmed here for every n,
        # no later seed finds one to compile and starts over for it
        with jax.default_matmul_precision("highest"):
            _lm.warm_up(sched, cache, plan["lengths"], vocab, ctx.seed)
    ctx.mark("warmed_up")

    # correctness, outside the window and at its load (module docstring)

    reference = load_module("reference", config["family"])
    sizes, tol = sizes_of(config), config["tolerance"]
    page = cache.spec.page_size
    samples = _ds.load_samples(
        [p for p in plan["plan"] if p.segment == "window"],
        cache.spec.max_seqs, config["load_check"]["decode_steps"], vocab,
        ctx.seed,
    )
    seqs = [prompt + more for prompt, more in samples]
    kept = [_ds.kept_positions(*sample, page) for sample in samples]
    pad_to = -(-max(len(q) for q in seqs) // 128) * 128
    weights = [list(model.params[guid]) for guid in sorted(model.params)]
    unbiased = [
        ws[:4] + [0 * ws[4]] if len(ws) == 5 and ws[1].ndim == 3 else ws
        for ws in weights
    ]
    wanted = [
        reference.run(weights, q, pad_to, *sizes, positions=rows)
        for q, rows in zip(seqs, kept)
    ]
    control = [
        reference.run(unbiased, q, pad_to, *sizes, positions=[0])[1]
        for q in seqs[: len(seqs) // 4]
    ]

    def compare():
        """`families/deepseek_v3.py`'s comparison: (logits gap under the
        program's own choice, share of sets equal, share equal to the
        unbiased reference's, sets, logits gap against the free choice)."""
        gap, free_gap, same, unlike = 0.0, 0.0, [0, 0], [0, 0]
        got = _ds.at_load(engine, cache, model.params, samples, page)
        for i, (q, (positions, logits, picked)) in enumerate(zip(seqs, got)):
            want, free = wanted[i]
            equal = _ds.sets_equal(picked, free)
            free_gap = max(free_gap, _ds.rel_gap(logits, want))
            if equal[0] < equal[1]:
                want, _ = reference.run(
                    weights, q, pad_to, *sizes, forced=picked,
                    positions=positions,
                )
            gap = max(gap, _ds.rel_gap(logits, want))
            same = np.add(same, equal)
            if i < len(control):
                unlike = np.add(unlike, _ds.sets_equal(picked, control[i]))
        return (
            gap, same[0] / same[1], unlike[0] / unlike[1], int(same[1]),
            free_gap,
        )

    with jax.default_matmul_precision("highest"):
        exact = compare()
    served = exact if ctx.rehearse else compare()
    controls = None
    if traffic.get("load_controls"):
        controls = controls_of(
            reference, weights, seqs, samples, wanted, kept, pad_to, sizes
        )
    ctx.mark("logits_checked")

    from flexflow_tpu.serving.frontend.server import FrontDoor

    backend = StateBackend(sched, cache, engine)
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
    moe_times = mla_times = kda_times = None
    if ctx.trace and ctx.tracer.path:
        times = scopes.scope_seconds(
            ctx.tracer.path, MOE_SCOPES + MLA_SCOPES + KDA_SCOPES,
            (DECODE_MODULE, PREFILL_MODULE), span="bench.trace",
            compiler_ops={"ragged-dot": "moe.experts"},
        )
        moe_times, mla_times, kda_times = (
            _ds.split_scopes(times, prefix) for prefix in ("moe.", "mla.", "kda.")
        )
        ctx.mark("scopes_read")

    judged = [r for r in records if r.segment == "window"]
    failed = [
        r for r in judged
        if r.status != "finished" or r.tokens != r.asked or r.bad_tokens
    ]
    stats = sched.stats
    checks = {
        "load": {
            "slots": len(samples),
            "prompts": sorted(len(prompt) for prompt, _ in samples),
            "contexts": sorted(len(q) for q in seqs),
            "sets": exact[3],
        },
        "logits_rel_gap_at_highest": exact[0],
        "logits_rel_gap_at_highest_free_choice": exact[4],
        "logits_rel_gap": served[0],
        "logits_rel_gap_free_choice": served[4],
        "logits_within_tolerance": bool(
            exact[0] <= tol["logits_highest_rel"]
            and served[0] <= tol["logits_default_rel"]
        ),
        "routing_sets_equal_share_at_highest": exact[1],
        "routing_sets_equal_share": served[1],
        "routing_sets_equal_share_without_bias": served[2],
        "routing_within_tolerance": bool(
            exact[1] >= tol["routing_highest_share_min"]
            and served[1] >= tol["routing_default_share_min"]
        ),
        "controls": controls,
        "kernel_fallbacks": int(engine.kernel_fallbacks),
        "decode_kernel": str(engine.decode_kernel),
        "kernel_block": engine.kernel_block and list(engine.kernel_block),
        "decode_steps_chained_share": (
            stats.decode_steps_chained / max(stats.decode_steps, 1)
        ),
        "every_judged_request_finished_whole": not failed,
        "first_failure": (
            f"{failed[0].status}: {failed[0].error} ({failed[0].tokens}/"
            f"{failed[0].asked} tokens)" if failed else None
        ),
        "prefill_buckets_warmed": buckets,
    }
    spec = cache.spec
    lin = config["linear_attn_config"]
    kda_layers, full_layers = pattern_of(config)
    expert_layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
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
        "max_seqs": spec.max_seqs,
        "num_pages": spec.num_pages,
        "page_size": spec.page_size,
        "bucket_of": spec.bucket,
        "decode_module": DECODE_MODULE,
        "prefill_module": PREFILL_MODULE,
        # one pool a latent layer, one head: the latent row
        "kv": {
            "layers": len(full_layers), "heads": spec.num_heads,
            "head_dim": spec.head_dim, "itemsize": spec.itemsize,
        },
        # the share: the experts HELD, over the expert layers
        "moe": {
            "layers": expert_layers,
            "experts": config["num_experts"],
            "k": sizes[2],
            "hidden": config["hidden_size"],
            "expert_hidden": config["moe_intermediate_size"],
            "itemsize": 4,
            "steps": backend.moe_steps,
            "scope_seconds": moe_times,
        },
        "mla": {
            "layers": len(full_layers),
            "heads": config["num_attention_heads"],
            "row": config["kv_lora_rank"] + config["qk_rope_head_dim"],
            "row_cached": spec.row_width,
            "value_width": config["kv_lora_rank"],
            "itemsize": spec.itemsize,
            "steps": backend.mla_steps,
            "scope_seconds": mla_times,
        },
        "kda": {
            "layers": len(kda_layers),
            "heads": lin["num_heads"],
            "head_dim": lin["head_dim"],
            "kernel": lin["short_conv_kernel_size"],
            "chunk": config.get("kda_chunk", KDA_CHUNK),
            "state_bytes_per_slot": getattr(spec, "state_bytes_per_slot", 0),
            "steps": backend.kda_steps,
            "scope_seconds": kda_times,
        },
    }
