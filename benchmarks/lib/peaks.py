"""The peaks table and the functions that count what a piece of work
needs. They live with the benchmark so that no PR that claims a gain can
change the yardstick. Counts are of the algorithm, from shapes: work the
program recomputes or pads does not count."""

from __future__ import annotations

import os

from benchmarks.lib.loading import BENCH_DIR, BenchmarkError, load_json


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json; "
            "add it with its source, never a default"
        )
    return table[device_kind]


def transformer_train_flops_per_token(
    layers: int, hidden: int, heads: int, seq: int, ff_dims=None
) -> float:
    """Forward + backward FLOPs per token of the flagship encoder stack
    (transformer.cc:33-45: MHA, dense(relu), dense, no norms), plus the
    final dense(1). A matmul of [m, k] x [k, n] is 2 m k n forward and
    twice that backward. Attention scores and weighted values are
    non-causal: 2 * 2 * seq * hidden per token forward."""
    del heads  # the head split does not change the count
    ff_dims = ff_dims or (hidden, hidden)
    proj = 4 * 2 * hidden * hidden  # q, k, v, o
    attn = 2 * 2 * seq * hidden  # q.k^T and p.v over the whole sequence
    width_in = hidden
    mlp = 0
    for width in ff_dims:
        mlp += 2 * width_in * width
        width_in = width
    forward = layers * (proj + attn + mlp) + 2 * hidden * 1
    return 3.0 * forward


def paged_decode_attention_bytes(
    context_lens, heads: int, head_dim: int, itemsize: int
) -> float:
    """Bytes one decode-attention call must move for ONE layer: the keys
    and values of every live sequence's context once, plus the query in
    and the output back. From shapes; page padding does not count."""
    row = heads * head_dim * itemsize
    kv = 2.0 * sum(context_lens) * row
    qo = 2.0 * len(context_lens) * row
    return kv + qo


def paged_decode_attention_flops(
    context_lens, heads: int, head_dim: int
) -> float:
    """FLOPs of the same call: q.k^T and p.v per context position."""
    return 2.0 * 2.0 * sum(context_lens) * heads * head_dim


def roofline_floor_s(flops: float, bytes_: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    t_flops = flops / (peaks["bf16_tflops"] * 1e12)
    t_bytes = bytes_ / (peaks["hbm_gbps"] * 1e9)
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
