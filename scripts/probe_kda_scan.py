"""Where the chunked delta-rule scan's device time goes, instruction by
instruction, on the chip (PERF.md section 6, PR 41).

    chiprun -- python3 scripts/probe_kda_scan.py --tokens 768 1600
    python3 scripts/probe_kda_scan.py --fold chiprun_out/trace_serve_kimi_linear_longform

The first runs `kda_chunked` alone at a served layer's shape (one packed
row of `--tokens` tokens, 32 heads of 128, chunks of 64, a prompt
boundary every fourth chunk) under the profiler and lists the program's
instructions by self time; it also holds the result to `kda_step` token
by token at the ambient matmul precision (the chip's default, as served)
and at `highest`. The second reads a profile that `scripts/
profile_cells.py --keep-trace` left (here in the sandbox: it needs no
chip) and lists the prefill program's instructions under `kda.scan`.
`--impl module:function` times another function of the same signature
(the next form, before it replaces this one).

    chiprun -- python3 scripts/probe_kda_scan.py --rows --tokens 256 768 1600

`--rows` (PR 52) times what a served layer's prefill does for the
recurrence, `kda_chunked_rows`, both ways at once: the tree's fallback
(masks over the row, `kda_chunked`, each request's last state scattered
into its slot's row of a donated `[32, H, d, d]` state) and `ops/pallas/
kda_scan.py` (`--heads-per-block 8 16` sweeps its block), on a packed row
of requests of four chunks less 20 tokens with its last two chunks
padding; ms a layer from the profile, the kernel held to the fallback at
the default precision and at `highest`. `--rehearse` runs it tiny through
the interpreter and prints no time.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from flexflow_tpu.ops import linear_attention as L  # noqa: E402
from flexflow_tpu.utils import profiling  # noqa: E402

SCOPE = "kda.scan"
_SHAPE = re.compile(r"=\s*(\(?[a-z0-9]+\[[^ ]*)\s")


def _in_scan(instructions):
    """Names of the instructions under the scope: by their own `op_name`,
    by that of an instruction of the computation they call (a fusion's
    body), or by standing in the body of a `while` that is."""
    by_computation = {}
    for ins in instructions.values():
        by_computation.setdefault(ins.computation, []).append(ins)
    own = {
        name for name, ins in instructions.items()
        if SCOPE in ins.op_name or any(
            SCOPE in b.op_name for c in ins.calls for b in by_computation.get(c, ())
        )
    }
    inside, frontier = set(), [
        c for name in own if instructions[name].opcode == "while"
        for c in instructions[name].calls
    ]
    while frontier:
        comp = frontier.pop()
        if comp in inside:
            continue
        inside.add(comp)
        frontier += [c for ins in by_computation.get(comp, ()) for c in ins.calls]
    return own | {n for n, ins in instructions.items() if ins.computation in inside}


def table(events, hlo_text, program=None, everything=False, top=24):
    """Print the instructions of `hlo_text`'s program by self time a
    program execution; those under `kda.scan` only unless `everything`."""
    module, instructions = profiling.parse_hlo(hlo_text)
    program = program or module
    wanted = set(instructions) if everything else _in_scan(instructions)
    rows, executions, total = {}, 0, 0.0
    for dev in events:
        runs = sorted(
            (s, e) for name, s, e in dev.modules
            if program in (name, name.split("(")[0])
        )
        executions += len(runs)
        total += sum(e - s for s, e in runs)
        for text, own in profiling._self_times(profiling._inside(dev.ops, runs)):
            name = text.split("=", 1)[0].strip().lstrip("%")
            if name in wanted:
                row = rows.setdefault(name, [0.0, 0, text])
                row[0] += own
                row[1] += 1
    if not executions:
        raise profiling.NoDeviceOps(f"no execution of {program!r} in this trace")
    scale = 1e-6 / executions
    under = sum(r[0] for r in rows.values()) * scale
    print(f"{program}: {total * scale:.3f} ms a program over {executions} "
          f"executions; {under:.3f} ms in {len(rows)} instructions"
          f"{'' if everything else ' under ' + SCOPE}")
    print(f"{'ms':>8} {'share':>6} {'runs':>5}  instruction")
    for name, (ns, count, text) in sorted(rows.items(), key=lambda kv: -kv[1][0])[:top]:
        ins = instructions[name]
        shape = _SHAPE.search(text)
        where = "/".join(ins.op_name.split("/")[-2:]) or "(compiler's)"
        print(f"{ns * scale:8.4f} {ns * scale / max(under, 1e-12):6.1%} "
              f"{count / executions:5.0f}  {name} {ins.opcode} "
              f"{shape.group(1) if shape else ''} {where}")


def fold(trace_dir, top):
    events = profiling.read_device_events(trace_dir)
    for path in sorted(glob.glob(os.path.join(trace_dir, "jit__prefill*.hlo.txt"))):
        with open(path) as f:
            text = f.read()
        try:
            table(events, text, top=top)
        except (profiling.NoDeviceOps, ValueError) as e:
            print(f"{path}: {e}")


def _inputs(tokens, heads, dim, seed):
    """What a served layer hands the scan: unit keys, queries scaled by
    d^-0.5, per-channel log decays whose median is near log 0.97."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (1, tokens, heads, dim)

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], shape)) * dim ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -0.045 * jax.nn.softplus(jax.random.normal(ks[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return q, k, v, g, beta


def run(args):
    module, _, function = args.impl.partition(":")
    impl = getattr(importlib.import_module(module), function)
    d = jax.devices()[0]
    print(f"device: {d.platform} {d.device_kind}; {args.impl}")

    def by_steps(q, k, v, g, beta, state):
        def one(st, x):
            o, st = L.kda_step(*x, st)
            return st, o

        xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
        st, o = jax.lax.scan(one, state, xs)
        return jnp.moveaxis(o, 0, 1), st

    for tokens in args.tokens:
        ins = _inputs(tokens, args.heads, args.head_dim, args.seed)
        zero = jnp.zeros((1, args.heads, args.head_dim, args.head_dim))
        fresh = (jnp.arange(tokens // args.chunk) % 4 == 0)[None]

        def scan(*a):
            return impl(*a, zero, fresh, args.chunk)

        with profiling.fresh_compile():
            compiled = jax.jit(scan).lower(*ins).compile()
        print(f"== {tokens} tokens; temporaries "
              f"{compiled.memory_analysis().temp_size_in_bytes / 1e6:.1f} MB")
        jax.block_until_ready(compiled(*ins))
        with tempfile.TemporaryDirectory() as profile_dir:
            with profiling.trace(profile_dir):
                for _ in range(args.steps):
                    jax.block_until_ready(compiled(*ins))
            try:
                table(profiling.read_device_events(profile_dir),
                      compiled.as_text(), everything=True, top=args.top)
            except profiling.NoDeviceOps as e:  # the CPU backend: a rehearsal
                print(e)
        # against the one-step form: one prompt, no boundary inside
        want, last = jax.jit(by_steps)(*ins, zero)
        for precision in (None, "highest"):
            with jax.default_matmul_precision(precision or "default"):
                got, states = jax.jit(
                    lambda *a: impl(*a, zero, None, args.chunk)
                )(*ins)
            print(f"against kda_step, matmuls at {precision or 'the default'}: "
                  f"outputs {float(jnp.abs(got - want).max()):.3e} of "
                  f"{float(jnp.abs(want).max()):.3e}, last state "
                  f"{float(jnp.abs(states[:, -1] - last).max()):.3e} of "
                  f"{float(jnp.abs(last).max()):.3e}")


def _packed(tokens, chunk, slots):
    """A packed row's bookkeeping: requests of four chunks less 20 tokens
    (fewer chunks for the last), the row's last two chunks padding where
    it has more than four -> (live [T], fresh [1, n], slot ids and last
    token of each request [slots], padded with `slots` and 0)."""
    import numpy as np

    n = tokens // chunk
    used = n - 2 if n > 4 else n
    live, fresh = np.zeros(tokens, bool), np.zeros((1, n), bool)
    ids, last = np.full(slots, slots, np.int32), np.zeros(slots, np.int32)
    for r, c in enumerate(range(0, used, 4)):
        end = min(c + 4, used) * chunk - 20
        live[c * chunk: end] = True
        fresh[0, c] = True
        ids[r], last[r] = (5 * r + 3) % slots, end - 1
    return tuple(jnp.asarray(a) for a in (live, fresh, ids, last))


def run_rows(args):
    from flexflow_tpu.ops.pallas import kda_scan as kernel

    d = jax.devices()[0]
    print(f"device: {d.platform} {d.device_kind}; kda_chunked_rows")
    shape = (args.slots, args.heads, args.head_dim, args.head_dim)
    gate = kernel.use_kernel

    pick = kernel.heads_per_block

    def maker(take, heads=None):  # the choice is made when `fn` is traced
        def fn(*operands):
            kernel.use_kernel = lambda *a: take
            kernel.heads_per_block = (lambda h: heads) if heads else pick
            try:
                return L.kda_chunked_rows(*operands, args.chunk)[:2]
            finally:
                kernel.use_kernel, kernel.heads_per_block = gate, pick
        return fn

    forms = [("kda_chunked + scatter", maker(False))] + [
        (f"kernel, {hb} heads a block", maker(True, hb))
        for hb in args.heads_per_block
    ]
    for tokens in args.tokens:
        ins = _inputs(tokens, args.heads, args.head_dim, args.seed)
        book = _packed(tokens, args.chunk, args.slots)
        live = book[0]
        print(f"== {tokens} tokens, {int(live.sum())} live, "
              f"{int(book[1].sum())} requests")
        results = {}
        for name, fn in forms:
            for precision in (None, "highest"):
                rows = jax.random.normal(jax.random.PRNGKey(7), shape)
                with jax.default_matmul_precision(precision or "default"):
                    jitted = jax.jit(fn, donate_argnums=(5,))
                    if precision is None and not args.rehearse:
                        with profiling.fresh_compile():
                            compiled = jitted.lower(*ins, rows, *book).compile()
                        out = compiled(*ins, rows, *book)
                        jax.block_until_ready(out)
                        with tempfile.TemporaryDirectory() as profile_dir:
                            with profiling.trace(profile_dir):
                                for _ in range(args.steps):
                                    out = compiled(*ins, out[1], *book)
                                    jax.block_until_ready(out)
                            events = profiling.read_device_events(profile_dir)
                        print(f"-- {name}")
                        table(events, compiled.as_text(), everything=True,
                              top=args.top)
                    else:
                        out = jitted(*ins, rows, *book)
                results[name, precision] = jax.block_until_ready(out)
        base = forms[0][0]
        for name, _ in forms[1:]:
            for precision in (None, "highest"):
                (wo, wr), (go, gr) = results[base, precision], results[name, precision]
                print(f"{name} against {base}, matmuls at "
                      f"{precision or 'the default'}: outputs "
                      f"{float(jnp.abs(go - wo)[0, live].max()):.3e} of "
                      f"{float(jnp.abs(wo)[0, live].max()):.3e}, rows "
                      f"{float(jnp.abs(gr - wr).max()):.3e} of "
                      f"{float(jnp.abs(wr).max()):.3e}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fold", help="a kept profile of scripts/profile_cells.py")
    ap.add_argument("--impl", default="flexflow_tpu.ops.linear_attention:kda_chunked")
    ap.add_argument("--tokens", type=int, nargs="+", default=[768])
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top", type=int, default=24)
    ap.add_argument("--rows", action="store_true",
                    help="kda_chunked_rows: the fallback beside the kernel")
    ap.add_argument("--heads-per-block", type=int, nargs="+", default=[8])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.fold:
        fold(args.fold, args.top)
    elif args.rows:
        run_rows(args)
    else:
        run(args)


if __name__ == "__main__":
    main()
