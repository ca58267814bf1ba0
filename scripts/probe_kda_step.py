"""What one decode step of the gated delta rule costs a layer on the chip:
XLA's form (`kda_step` over every slot's state, then the `where` that
hands the idle slots' rows back) against `ops/pallas/kda_step.py` (the
live rows only, in place) (PERF.md section 6, PR 48).

    chiprun -- python3 scripts/probe_kda_step.py
    chiprun -- python3 scripts/probe_kda_step.py --live 14 --heads-per-block 8 16 32

At a served layer's shape (32 slots, 32 heads of 128: a state of 64 MiB a
layer) and `--layers` layers a program, each with a state of its own,
donated, as the decode step program holds them, it times a program of
each form at `--live` live slots of the 32 (spread evenly over the slot
numbers) and prints for each: ms a layer, GB/s of the LIVE rows' state
once in and once out, and the share of the byte floor as
`benchmarks/lib/kda_counts.py:state_step_bytes` reckons it (without the
convolutions' tails: neither form here touches them) over 819 GB/s. The
kernel is also held to the XLA form on the same inputs: largest
difference of the outputs and of the live rows' new state, and whether
every idle row is still bit-equal to what the first call was handed. `--impl module:function` times
another function of `kda_step_rows`' signature. The table is also
written to `chiprun_out/probe_kda_step.json`.

It needs a TPU and fails without one; `--rehearse` runs a tiny shape
through the interpreter here to check the script, and prints no time.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import kda_counts  # noqa: E402
from flexflow_tpu.ops import linear_attention as L  # noqa: E402
from flexflow_tpu.ops.pallas import kda_step as K  # noqa: E402
from flexflow_tpu.utils import profiling  # noqa: E402

HBM_GBS = 819.0


def floor_bytes(live, heads, dim):
    """`kda_counts.state_step_bytes` less the convolutions' tails (a
    kernel of one tap keeps none): the live rows' state once in and once
    out, their q, k, v, g and beta in and their output back."""
    return kda_counts.state_step_bytes(live, heads, dim, 1)


def inputs(slots, heads, dim, layers, seed):
    """What a served layer hands the step, a layer: unit keys, queries
    scaled by d^-0.5, log decays near log 0.97, a state of unit scale."""
    out = []
    for key in jax.random.split(jax.random.PRNGKey(seed), layers):
        ks = jax.random.split(key, 6)
        shape = (slots, heads, dim)

        def unit(t):
            return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

        out.append((
            unit(jax.random.normal(ks[0], shape)) * dim ** -0.5,
            unit(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape),
            -0.045 * jax.nn.softplus(jax.random.normal(ks[3], shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:2])),
            jax.random.normal(ks[5], shape + (dim,)),
        ))
    return out


def xla_form(q, k, v, g, beta, state, active):
    o, new = L.kda_step(q, k, v, g, beta, state)
    with jax.named_scope("kda.step"):
        return o, jnp.where(active[:, None, None, None], new, state)


def program(step):
    """`step` on every layer's own state, the states donated."""

    def run(vectors, states, active):
        outs, news = [], []
        for (q, k, v, g, beta), state in zip(vectors, states):
            o, new = step(q, k, v, g, beta, state, active)
            outs.append(o)
            news.append(new)
        return outs, news

    return jax.jit(run, donate_argnums=(1,))


def timed(fn, vectors, states, active, repeats):
    """(device seconds a call, of which in the kernel's own call, host
    seconds a call; the last outputs; the last states) over `repeats`
    chained calls under the profiler, each on the states the one before
    returned. The device's time is the program's on the profile's `XLA
    Modules` line: four layers of 0.1 ms are less than a dispatch costs
    the host, so the host's clock reads the host. None off a TPU."""
    outs, states = fn(vectors, states, active)
    jax.block_until_ready(states)
    with tempfile.TemporaryDirectory() as profile_dir:
        with profiling.trace(profile_dir):
            start = time.perf_counter()
            for _ in range(repeats):
                outs, states = fn(vectors, states, active)
            jax.block_until_ready((outs, states))
            host = (time.perf_counter() - start) / repeats
        try:
            (dev,) = profiling.read_device_events(profile_dir)[:1]
        except profiling.NoDeviceOps:  # the CPU backend: a rehearsal
            return None, None, host, outs, states
    runs = [(s, e) for name, s, e in dev.modules if name.startswith("jit_run")]
    call = sum(
        e - s for name, s, e in profiling._inside(dev.ops, runs)
        if K.NAME in name
    )
    n = max(len(runs), 1)
    return sum(e - s for s, e in runs) * 1e-9 / n, call * 1e-9 / n, host, outs, states


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--live", type=int, nargs="+", default=[2, 4, 8, 14, 32])
    ap.add_argument("--heads-per-block", type=int, nargs="*", default=[],
                    help="more kernel rows, with so many heads a block")
    ap.add_argument("--impl", nargs="*", default=[],
                    help="module:function of kda_step_rows' signature")
    ap.add_argument("--repeats", type=int, default=100)
    ap.add_argument("--seed", type=int, default=48)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    d0 = jax.devices()[0]
    print(f"device: {d0.platform} {d0.device_kind}", flush=True)
    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit("probe_kda_step: no TPU here, and a time from anything "
                 "else is not a time (--rehearse checks the script)")
    if args.rehearse:
        args.slots, args.heads, args.layers, args.repeats = 4, 8, 2, 1
        args.live = [n for n in (1, 2, 4) if n <= args.slots]
    picked = K.heads_per_block(args.heads)
    forms = [("xla: kda_step + where", xla_form)]
    forms += [
        (f"kernel, {hb} heads a block" + (" (picked)" if hb == picked else ""),
         lambda *a, hb=hb: K.kda_step_rows(*a, heads=hb))
        for hb in sorted({picked, *args.heads_per_block})
    ]
    for name in args.impl:
        module, _, function = name.partition(":")
        forms.append((name, getattr(importlib.import_module(module), function)))
    programs = [(what, program(step)) for what, step in forms]
    table = []
    for live in args.live:
        mask = np.zeros(args.slots, bool)
        mask[np.linspace(0, args.slots - 1, live).round().astype(int)] = True
        active = jnp.asarray(mask)
        floor = floor_bytes(live, args.heads, args.head_dim) / (HBM_GBS * 1e9)
        print(f"\n{live} of {args.slots} slots live: byte floor "
              f"{floor * 1e3:.4f} ms a layer", flush=True)
        want = None
        for what, fn in programs:
            sets = inputs(args.slots, args.heads, args.head_dim, args.layers, args.seed)
            vectors = [s[:5] for s in sets]
            before = np.asarray(sets[0][5])
            try:
                s, call, host, outs, states = timed(
                    fn, vectors, [s[5] for s in sets], active, args.repeats
                )
            except Exception as e:  # a block Mosaic refuses: go on
                print(f"  {what:<36} FAILED {type(e).__name__}: {str(e)[:200]}")
                continue
            got = (np.asarray(outs[0])[mask], np.asarray(states[0]))
            row = {"live": live, "form": what}
            line = f"  {what:<36} "
            if s is not None and not args.rehearse:
                s, call, host = (t / args.layers for t in (s, call, host))
                row.update(ms=s * 1e3, kernel_call_ms=call * 1e3,
                           host_ms=host * 1e3, floor_share=floor / s)
                line += (f"{s * 1e3:8.4f} ms a layer ({call * 1e3:.4f} in the "
                         f"kernel's call; host {host * 1e3:.3f}) "
                         f"{floor * HBM_GBS / s:6.1f} GB/s "
                         f"{floor / s:6.1%} of the floor")
            if want is None:
                want = got
            else:
                row["outputs_gap"] = float(np.abs(got[0] - want[0]).max())
                row["state_gap"] = float(np.abs(got[1][mask] - want[1][mask]).max())
                line += (f"  against xla: outputs {row['outputs_gap']:.2e} of "
                         f"{float(np.abs(want[0]).max()):.2e}, state "
                         f"{row['state_gap']:.2e}")
            # the chained calls left the idle rows as they came
            row["idle_rows_untouched"] = bool((got[1][~mask] == before[~mask]).all())
            print(line + f"; idle rows untouched: {row['idle_rows_untouched']}",
                  flush=True)
            table.append(row)
    if not args.rehearse:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "probe_kda_step.json"), "w") as f:
            json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
