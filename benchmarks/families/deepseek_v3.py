"""The `deepseek_v3` family (latent attention over a latent paged cache, a
leading dense layer, sigmoid-routed expert layers of which this chip holds
a share, a shared expert): `build_deepseek_v3` behind `build_scheduler`
and the `FrontDoor`, driven and judged as `families/olmoe.py` drives and
judges its model, by import: the open loop, warm-up, spans and counts are
`families/decoder_lm.py`'s and the per-step expert counters
`olmoe.MoeBackend`'s. `olmoe.run` itself reads OLMoE's own config keys and
calls its own builder and router, and no file that is there may be edited,
so this file has a `run` of its own around those parts.

`correct` is decided at the cell's load (`at_load`): every slot live, on
requests of the window's own plan with the longest prompts among them,
each prefilled by its own bucket's program and decoded together for
hundreds of steps (contexts past 1,024 positions: several kernel blocks a
slot, many page crossings, slots that end and are freed while others go
on), against the reference's full forward pass over the same tokens. It is
made twice, as olmoe's: with the engine's step functions traced at
`highest`, and with the programs the window runs at the chip's default
precision. Each pass is held to two limits of the configuration's
`tolerance`: the logits against the reference UNDER THE PROGRAM'S OWN
CHOICE of experts (`reference.run(forced=)`), and the share of its top-k
sets equal to the sets the reference chooses. The two are kept apart
because a near-tie falls otherwise under any reordering of float32 sums:
at `highest` one set in about 20,000 does, in some runs, and that one
position's logits then differ by 1e-4 and more with no fault in either
program (the gap against the reference left to choose is printed beside
the other). The choices are the engine's programs' own
(`engine.moe_choice`), not a second forward pass's.

What this family adds to the record: under `moe` the sizes of the share
(the experts HELD and the expert layers) and `moe.shared` among the
scopes; under `mla` the engine's latent-row and absent-row counters per
step and the device time under the program's `mla.*` named scopes.
"""

from __future__ import annotations

import asyncio

import numpy as np

from benchmarks.lib import scopes, window
from benchmarks.lib.loading import load_module

_olmoe = load_module("families", "olmoe")
_lm = _olmoe._lm
SPANS, DECODE_MODULE, PREFILL_MODULE = _lm.SPANS, _lm.DECODE_MODULE, _lm.PREFILL_MODULE
MOE_SCOPES = _olmoe.MOE_SCOPES + ("moe.shared",)
MLA_SCOPES = ("mla.project", "mla.absorb", "mla.attend", "mla.out")


def sizes_of(config: dict):
    """(eps, theta, rope, k, scale, held) as the reference takes them."""
    return (
        config["rms_norm_eps"], float(config["rope_theta"]),
        config["qk_rope_head_dim"], config["num_experts_per_tok"],
        config["routed_scaling_factor"],
        (config["experts_held_first"], config["n_routed_experts"]),
    )


def build(config: dict, devices, seed: int):
    from flexflow_tpu import DataType, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_deepseek_v3
    from flexflow_tpu.serving import ServeConfig, build_scheduler

    if config["q_lora_rank"] is not None or config["n_group"] != 1:
        raise RuntimeError("query compression and grouped routing are not built")
    serve = config["serve"]
    cfg = FFConfig(batch_size=serve["max_seqs"])
    cfg.seed = int(seed) % (2**31 - 1)
    model = FFModel(cfg)
    tokens = model.create_tensor(
        [serve["max_seqs"], serve["max_seq_len"]], dtype=DataType.INT32,
        name="tokens",
    )
    build_deepseek_v3(
        model, tokens, vocab_size=config["vocab_size"],
        hidden=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        dense_hidden=config["intermediate_size"],
        dense_layers=config["first_k_dense_replace"],
        expert_hidden=config["moe_intermediate_size"],
        num_experts=config["router_width"],
        experts_per_token=config["num_experts_per_tok"],
        shared_experts=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
        renormalise=config["norm_topk_prob"],
        experts_held=(config["experts_held_first"], config["n_routed_experts"]),
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
        devices=list(devices)[:1],
    )
    draw_choice_bias(model, seed)
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


class LatentBackend(_olmoe.MoeBackend):
    """`MoeBackend`, and after each step the engine's counters of what
    this family adds, all cumulative: (step end, decode steps, latent rows
    attended in decode, busy slot-steps, live rows absent in decode, live
    rows absent in prefill, prompt tokens prefilled). The live (token,
    choice) rows routed are the last one and busy slot-steps, times k and
    the expert layers."""

    def __init__(self, sched, cache, engine):
        super().__init__(sched, cache, engine)
        self.mla_steps = []

    def step(self):
        super().step()
        e, st = self._engine, self._sched.stats
        self.mla_steps.append((
            self.steps[-1][1], st.decode_steps, e.mla_rows_read_decode,
            st.busy_slot_steps, e.moe_rows_absent_decode,
            e.moe_rows_absent_prefill, e.prefill_tokens_real,
        ))


def draw_choice_bias(model, seed: int):
    """The routers' choice bias is a trained buffer of the checkpoint
    (zero from the builder). Here it is drawn from the run's seed, uniform
    in [-0.1, 0.1]: small, and not zero, so that leaving it out shows."""
    import jax

    from flexflow_tpu.core.types import OperatorType

    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    for guid in sorted(model.params):
        if model.graph.nodes[guid].op_type == OperatorType.SPARSE_MOE:
            ws = model.params[guid]
            ws[4] = jax.random.uniform(
                jax.random.fold_in(key, guid), ws[4].shape, ws[4].dtype,
                -0.1, 0.1,
            )


def load_samples(plan, slots: int, steps: int, vocab: int, seed: int):
    """One request a slot out of the window's own plan: the longest
    prompts (a quarter of the slots, the first two decoded for all of
    `steps`) and the rest spread evenly over the other lengths, each with
    its own answer length up to `steps`. The answer's tokens are drawn
    from the seed (teacher forcing: the same tokens whatever a program's
    rounding picks). Returns [(prompt, continuation)]."""
    rng = np.random.Generator(np.random.PCG64([int(seed), 3]))
    by_length = sorted(plan, key=lambda p: (-len(p.prompt), p.index))
    longest = slots // 4
    rest = by_length[longest:]
    picked = by_length[:longest] + [
        rest[(2 * i + 1) * len(rest) // (2 * (slots - longest))]
        for i in range(slots - longest)
    ]
    return [
        (
            list(p.prompt),
            rng.integers(
                1, vocab, size=steps if i < 2 else min(p.max_new_tokens, steps)
            ).tolist(),
        )
        for i, p in enumerate(picked)
    ]


def kept_positions(prompt, more, page: int):
    """The positions whose logits are compared: the prefill's last, and
    of the decode steps the first two, the last two, and the last row of
    every page with the first of the next."""
    n, steps = len(prompt), len(more)
    return [n - 1] + [
        n + j for j in range(steps)
        if j < 2 or j >= steps - 2 or (n + j) % page in (0, page - 1)
    ]


def at_load(engine, cache, params, samples, page: int):
    """Serve `samples` together through the engine's public `prefill` and
    `decode`: admitted bucket by bucket (each bucket's own program), then
    decoded in one batch, every slot fed its own continuation and freed
    when that ends. Returns for each sample (positions, logits at them,
    chosen [expert layers, tokens, k]): the prefill's last position and
    the decode steps of `kept_positions`, and the experts every token
    picked in every expert layer, from the programs' own `moe_choice`."""
    spec = cache.spec
    kept = [set(kept_positions(*sample, page)) for sample in samples]
    live, logits, chosen = {}, {}, {}
    by_bucket = {}
    for i, (prompt, more) in enumerate(samples):
        by_bucket.setdefault(spec.bucket(len(prompt)), []).append(i)
        live[i] = cache.alloc(len(prompt), len(prompt) + len(more))
        if live[i] is None:
            raise RuntimeError("no free slot for the correctness samples")
    try:
        for _, group in sorted(by_bucket.items()):
            _, last = engine.prefill(
                params, [samples[i][0] for i in group],
                [live[i] for i in group],
            )
            picked = np.asarray(engine.moe_choice["prefill"])
            for row, i in enumerate(group):
                n = len(samples[i][0])
                logits[i] = {n - 1: np.array(last[row])}
                chosen[i] = [picked[:, row, :n]]
        for j in range(max(len(more) for _, more in samples)):
            tokens = np.zeros(spec.max_seqs, np.int32)
            active = np.zeros(spec.max_seqs, bool)
            for i, slot in live.items():
                tokens[slot], active[slot] = samples[i][1][j], True
            _, out = engine.decode(params, tokens, active)
            picked = np.asarray(engine.moe_choice["decode"])
            for i, slot in list(live.items()):
                prompt, more = samples[i]
                chosen[i].append(picked[:, slot])
                if len(prompt) + j in kept[i]:
                    logits[i][len(prompt) + j] = np.array(out[slot])
                if j + 1 == len(more):
                    cache.free(live.pop(i))
    finally:
        for slot in live.values():
            cache.free(slot)
    return [
        (
            sorted(logits[i]),
            np.stack([logits[i][p] for p in sorted(logits[i])]),
            np.concatenate(chosen[i], axis=1),
        )
        for i in range(len(samples))
    ]


def rel_gap(got, want) -> float:
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def sets_equal(got, want):
    """(equal, all) over the (position, expert layer) top-k SETS."""
    same = np.all(np.sort(got, axis=-1) == np.sort(want, axis=-1), axis=-1)
    return int(same.sum()), int(same.size)


def split_scopes(times: dict, prefix: str) -> dict:
    """`scopes.scope_seconds`' result with only the scopes of one layer."""
    return {
        program: dict(rec, scopes={
            k: v for k, v in rec["scopes"].items() if k.startswith(prefix)
        })
        for program, rec in times.items()
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

    reference = load_module("reference", config["family"])
    sizes, tol = sizes_of(config), config["tolerance"]
    page = cache.spec.page_size
    samples = load_samples(
        [p for p in plan["plan"] if p.segment == "window"],
        cache.spec.max_seqs, config["load_check"]["decode_steps"], vocab,
        ctx.seed,
    )
    seqs = [prompt + more for prompt, more in samples]
    pad_to = -(-max(len(q) for q in seqs) // 128) * 128
    weights = [list(model.params[guid]) for guid in sorted(model.params)]
    # a program that left the choice bias out: the routing limits' control
    unbiased = [
        ws[:4] + [0 * ws[4]] if len(ws) == 5 and ws[1].ndim == 3 else ws
        for ws in weights
    ]
    wanted = [
        reference.run(
            weights, q, pad_to, *sizes,
            positions=kept_positions(*sample, page),
        )
        for q, sample in zip(seqs, samples)
    ]
    control = [
        reference.run(unbiased, q, pad_to, *sizes, positions=[0])[1]
        for q in seqs[: len(seqs) // 4]
    ]

    def compare():
        """The worst logits gap over the samples against the reference
        under the program's own choice of experts and against the
        reference left to choose (one near-tie that falls otherwise, one
        set in tens of thousands, moves a position's logits by 1e-4 and
        more with no fault anywhere), and the shares of the program's
        top-k sets equal to the reference's and to the unbiased
        reference's."""
        gap, free_gap, same, unlike = 0.0, 0.0, [0, 0], [0, 0]
        got = at_load(engine, cache, model.params, samples, page)
        for i, (q, (positions, logits, picked)) in enumerate(zip(seqs, got)):
            want, free = wanted[i]
            equal = sets_equal(picked, free)
            free_gap = max(free_gap, rel_gap(logits, want))
            if equal[0] < equal[1]:
                want, _ = reference.run(
                    weights, q, pad_to, *sizes, forced=picked,
                    positions=positions,
                )
            gap = max(gap, rel_gap(logits, want))
            same = np.add(same, equal)
            if i < len(control):
                unlike = np.add(unlike, sets_equal(picked, control[i]))
        return (
            gap, same[0] / same[1], unlike[0] / unlike[1], int(same[1]),
            free_gap,
        )

    with jax.default_matmul_precision("highest"):
        exact = compare()
    served = exact if ctx.rehearse else compare()
    ctx.mark("logits_checked")

    from flexflow_tpu.serving.frontend.server import FrontDoor

    backend = LatentBackend(sched, cache, engine)
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
    moe_times = mla_times = None
    if ctx.trace and ctx.tracer.path:
        times = scopes.scope_seconds(
            ctx.tracer.path, MOE_SCOPES + MLA_SCOPES,
            (DECODE_MODULE, PREFILL_MODULE), span="bench.trace",
            compiler_ops={"ragged-dot": "moe.experts"},
        )
        moe_times, mla_times = split_scopes(times, "moe."), split_scopes(times, "mla.")
        ctx.mark("scopes_read")

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
        # one pool a layer, one head: the latent row
        "kv": {
            "layers": config["num_hidden_layers"], "heads": spec.num_heads,
            "head_dim": spec.head_dim, "itemsize": spec.itemsize,
        },
        # the share: the experts HELD, over the expert layers
        "moe": {
            "layers": expert_layers,
            "experts": config["n_routed_experts"],
            "k": sizes[3],
            "hidden": config["hidden_size"],
            "expert_hidden": config["moe_intermediate_size"],
            "itemsize": 4,
            "steps": backend.moe_steps,
            "scope_seconds": moe_times,
        },
        "mla": {
            "layers": config["num_hidden_layers"],
            "heads": config["num_attention_heads"],
            "row": config["kv_lora_rank"] + config["qk_rope_head_dim"],
            "row_cached": spec.row_width,
            "value_width": config["kv_lora_rank"],
            "itemsize": spec.itemsize,
            "steps": backend.mla_steps,
            "scope_seconds": mla_times,
        },
    }
