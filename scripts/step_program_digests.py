#!/usr/bin/env python3
"""A digest of every step program a serving cell runs, to compare two trees.

    python3 scripts/step_program_digests.py --workload <serving cell> [...] [--rehearse]

Builds the cell's model and scheduler as `benchmarks/run.py` does (the
family's `build`), runs one prompt of every prefill bucket and a decode
step behind it, and prints one JSON line a cell: the sha256 of the
LOWERED text of each program the engine dispatched, by program
(`prefill/<bucket>`, `decode`). The text is StableHLO without debug
info; a Mosaic kernel's serialized body, which carries the checkout's
path and its callers' line numbers, is parsed and printed without them
before it is hashed. Two trees whose lines are equal run equal programs
(PR 54 held itself to its parent this way: the record of dispatched
programs is host code around the jitted calls).

On the chip the programs are lowered for the TPU at the cell's real
size; `--rehearse` lowers the tiny sizes for the CPU backend, which is
what the sandbox can do.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _kernel_digest(match) -> str:
    """A Mosaic body without its source locations, hashed."""
    from jax._src.lib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(match.group(2)))
        text = module.operation.get_asm(enable_debug_info=False)
    return match.group(1) + hashlib.sha256(text.encode()).hexdigest() + match.group(3)


def digest(lowered) -> str:
    text = _BODY.sub(_kernel_digest, lowered.as_text())
    return hashlib.sha256(text.encode()).hexdigest()


def cell_digests(workload: str, rehearse: bool) -> dict:
    from benchmarks.lib import loading, window

    bench = loading.load_benchmark()
    cell = loading.find_cell(bench, workload)
    config = loading.with_rehearsal(
        loading.load_config(bench, cell["config"]), rehearse
    )
    family = loading.load_module("families", config["family"])
    devices = window.devices_for(cell["chips"], rehearse)
    model, _, engine, cache = family.build(config, devices, 0)
    seen = {}
    run_step = engine._run_step

    def lowering(site, step_fn, params, inputs, adapter_args=(), **kw):
        name = site
        if site == "prefill":
            name = f"prefill/{inputs[0].shape[1]}"
        if name not in seen:
            seen[name] = digest(
                step_fn().lower(params, *inputs, *cache.pools, *adapter_args)
            )
        return run_step(site, step_fn, params, inputs, adapter_args, **kw)

    engine._run_step = lowering
    import numpy as np

    spec = cache.spec
    chunk = engine._state_chunk
    for bucket in spec.buckets:
        n = min(bucket, spec.max_len - 2)
        n = max(1, n // chunk * chunk) if chunk > 1 else n
        slot = cache.alloc(n, n + 2)
        engine.prefill(model.params, [[1 + i % 7 for i in range(n)]], [slot])
        tokens = np.zeros(spec.max_seqs, np.int32)
        active = np.zeros(spec.max_seqs, bool)
        active[slot] = True
        engine.decode(model.params, tokens, active)
        cache.free(slot)
    return {
        "workload": workload, "rehearse": rehearse,
        "platform": str(devices[0].platform), "kind": str(devices[0].device_kind),
        "programs": dict(sorted(seen.items())),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    for workload in args.workload:
        print(json.dumps(cell_digests(workload, args.rehearse)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
