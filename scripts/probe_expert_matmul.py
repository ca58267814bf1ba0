"""What an expert layer's three grouped matmuls cost on the chip, shape by
shape: XLA's `jax.lax.ragged_dot` against `ops/pallas/grouped_matmul.py`
(PERF.md section 6, PR 46).

    chiprun -- python3 scripts/probe_expert_matmul.py
    chiprun -- python3 scripts/probe_expert_matmul.py --config olmoe --tiles 16 64

For the three expert configurations' shapes (hidden, expert width, experts
held of the router's, choices a token) at every prefill bucket's row count
and at the decode step's, with group sizes drawn as a router draws them
(k distinct experts a token, uniformly; the rows of experts held elsewhere
behind the last group; `--decode-live n` adds decode steps of which only n
slots are live and the others repeat one token's choice), it times

  * one `ragged_dot` (the gate product) and the layer's whole chain
    (gate, up, `silu(gate) * up`, down: `sparse_moe`'s lines) at the
    ambient default precision and at `highest`,
  * the kernel's chain (`expert_mlp`) at each of `--tiles` row tiles
    and at the tile it picks itself, at both precisions,
  * `jax.experimental.pallas.ops.tpu.megablox.gmm` on the gate product at
    a few tilings,

and prints for each: ms a call, GB/s of the touched experts' weights, the
share of the byte roofline as `benchmarks/lib/moe_counts.py` reckons it
(touched weights once, rows in and out once, over 819 GB/s), and the
TFLOP/s of the rows multiplied when every (row tile, expert) visit
multiplies a whole tile. Each timed call alternates between two sets of
weights. The table is also written to `chiprun_out/probe_expert_matmul.json`.

It needs a TPU and fails without one; `--rehearse` runs tiny shapes through
the interpreter here to check the script, and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from flexflow_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

HBM_GBS = 819.0
# hidden, expert width, experts held, the router's experts, choices a
# token, the prefill buckets (tokens), the slots of a decode step
CONFIGS = {
    "olmoe": (2048, 1024, 64, 64, 8, (128, 256, 640), 16),
    "kanana": (2048, 768, 64, 128, 6, (128, 256, 640), 16),
    "kimi": (2304, 1024, 64, 256, 8, (256, 512, 1024, 1600), 32),
}


def draw(tokens, k, held, experts, seed, live=None):
    """Group sizes [held] as a router's choice gives them: k distinct
    experts of `experts` a token, the held ones are the first `held`.
    `live`: only so many tokens are someone's and the others repeat the
    first one's choice, as a decode step's idle slots do."""
    rng = np.random.default_rng(seed)
    choice = np.argsort(rng.random((tokens, experts)), axis=1)[:, :k]
    if live is not None:
        choice[live:] = choice[0]
    return np.bincount(choice[choice < held], minlength=held).astype(np.int32)


def xla_chain(rows, w_gate, w_up, w_down, sizes):
    mm = dict(preferred_element_type=jnp.float32)
    gate = jax.lax.ragged_dot(rows, w_gate, sizes, **mm)
    up = jax.lax.ragged_dot(rows, w_up, sizes, **mm)
    hidden = jax.nn.silu(gate) * up
    return jax.lax.ragged_dot(hidden, w_down, sizes, **mm)


def xla_one(rows, w_gate, w_up, w_down, sizes):
    return jax.lax.ragged_dot(rows, w_gate, sizes, preferred_element_type=jnp.float32)


def visits(sizes, rows, tile):
    return int(gm._schedule(jnp.asarray(sizes), rows, tile).upto[-1])


def timed(fn, sets, sizes, repeats, precision):
    """Seconds a call: `repeats` calls alternating between the weight
    sets, after one call of each, blocked on at the end."""
    with jax.default_matmul_precision(precision):
        f = jax.jit(fn)
        for s in sets:
            f(*s, sizes).block_until_ready()
        start = time.perf_counter()
        out = None
        for i in range(repeats):
            out = f(*sets[i % len(sets)], sizes)
        out.block_until_ready()
        return (time.perf_counter() - start) / repeats


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", nargs="*", default=list(CONFIGS))
    ap.add_argument("--tiles", nargs="*", type=int, default=[16, 32, 64, 128])
    ap.add_argument("--repeats", type=int, default=12)
    ap.add_argument("--seed", type=int, default=46)
    ap.add_argument("--skip-megablox", action="store_true")
    ap.add_argument("--skip-highest", action="store_true")
    ap.add_argument("--decode-live", nargs="*", type=int, default=[],
                    help="more decode draws, with so many slots live")
    ap.add_argument("--decode-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    d0 = jax.devices()[0]
    print(f"device: {d0.platform} {d0.device_kind}", flush=True)
    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit("probe_expert_matmul: no TPU here, and a time from anything "
                 "else is not a time (--rehearse checks the script)")
    table = []
    for name in args.config:
        d, f, held, experts, k, buckets, slots = CONFIGS[name]
        if args.rehearse:
            d, f, held, experts, buckets, slots = 256, 128, 8, 8 * (experts // held), buckets[:1], 2
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)
        sets = [
            (
                jax.random.normal(keys[4 * i], (held, d, f)) * d ** -0.5,
                jax.random.normal(keys[4 * i + 1], (held, d, f)) * d ** -0.5,
                jax.random.normal(keys[4 * i + 2], (held, f, d)) * f ** -0.5,
            )
            for i in range(2)
        ]
        shapes = [("prefill", t, None) for t in (() if args.decode_only else buckets)]
        shapes += [("decode", slots, n) for n in [None] + args.decode_live if n is None or n < slots]
        for kind, tokens, live_slots in shapes:
            rows = tokens * k
            sizes = draw(tokens, k, held, experts, args.seed + tokens, live_slots)
            if live_slots is not None:
                kind = f"decode with {live_slots} live slots,"
            x = jax.random.normal(keys[7], (rows, d))
            full = [(x,) + s for s in sets]
            touched, live = int((sizes > 0).sum()), int(sizes.sum())
            weights = touched * 3 * d * f * 4
            floor = (weights + 2 * rows * d * 4) / (HBM_GBS * 1e9)
            one = (weights / 3 + rows * (d + f) * 4) / (HBM_GBS * 1e9)
            picked = gm.tile_rows(rows, rows / experts)
            print(f"\n{name} {kind} tokens {tokens}: rows {rows} (live {live}), "
                  f"{touched} of {held} experts touched, {weights / 1e9:.3f} GB "
                  f"of weights, byte floor {floor * 1e3:.3f} ms; the kernel "
                  f"picks a tile of {picked}", flush=True)
            sizes = jnp.asarray(sizes)

            def report(what, precision, fn, tile, products):
                try:
                    s = timed(fn, full, sizes, args.repeats, precision)
                except Exception as e:  # a tiling Mosaic refuses: go on
                    print(f"  {what:<34} {precision:<8} FAILED "
                          f"{type(e).__name__}: {str(e)[:160]}")
                    return
                padded = visits(sizes, rows, tile) * tile * 2 * d * f * products
                rec = {
                    "config": name, "kind": kind, "tokens": tokens, "rows": rows,
                    "what": what, "precision": precision, "ms": s * 1e3,
                    "weights_gbs": weights * products / 3 / s / 1e9,
                    "roofline_pct": 100 * (floor if products == 3 else one) / s,
                    "padded_tflops": padded / s / 1e12,
                }
                table.append(rec)
                if args.rehearse:
                    print(f"  {what:<34} {precision:<8} ran (no time off a TPU)")
                    return
                print(f"  {what:<34} {precision:<8} {rec['ms']:8.3f} ms "
                      f"{rec['weights_gbs']:7.1f} GB/s {rec['roofline_pct']:6.1f}% "
                      f"{rec['padded_tflops']:7.2f} TFLOP/s at tiles of {tile}",
                      flush=True)

            precisions = ("default",) if args.skip_highest else ("default", "highest")
            for precision in precisions:
                report("ragged_dot, gate alone", precision, xla_one, 128, 1)
                report("ragged_dot x3 (sparse_moe's lines)", precision, xla_chain, 128, 3)
            tiles = sorted({t for t in args.tiles if rows % t == 0} | {picked})
            for tile in tiles:
                if args.rehearse and tile > 32:
                    continue

                def kernel(rows_, wg, wu, wd, sz, tile=tile):
                    return gm.expert_mlp(rows_, wg, wu, wd, sz, tile=tile)

                for precision in precisions if tile == picked else ("default",):
                    report(f"kernel, tile {tile}" + (" (picked)" if tile == picked else ""),
                           precision, kernel, tile, 3)
            if args.skip_megablox or args.rehearse:
                continue
            from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox

            for tiling in ((128, 512, 512), (64, 1024, 512), (32, d, 256)):
                if rows % tiling[0]:
                    continue

                def mega(rows_, wg, wu, wd, sz, tiling=tiling):
                    return megablox(rows_, wg, sz, jnp.float32, tiling)

                report(f"megablox gmm {tiling}, gate alone", "default", mega,
                       tiling[0], 1)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "probe_expert_matmul.json"), "w") as fh:
        json.dump({"device": d0.device_kind, "rows": table}, fh, indent=1)


if __name__ == "__main__":
    main()
