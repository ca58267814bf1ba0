"""Serving demo: decoder LM + continuous-batching generate().

Builds a small GPT-style decoder (models.build_decoder_lm), compiles it,
and serves a mixed-length prompt stream through the continuous-batching
scheduler, printing generations and the scheduler's occupancy. Serving
flags ride FFConfig: `--max-seqs 4
--max-seq-len 128 --eos-token 0`. Telemetry flags ride along too — try
`--trace /tmp/serve_trace.json --metrics-out /tmp/serve_metrics.prom
--slo-ttft-ms 200` and load the trace at https://ui.perfetto.dev
(docs/observability.md).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexflow_tpu import (  # noqa: E402
    DataType,
    FFConfig,
    FFModel,
    LossType,
    SGDOptimizer,
)
from flexflow_tpu.models import build_decoder_lm  # noqa: E402
from flexflow_tpu.serving import Request, ServeConfig, build_scheduler  # noqa: E402

VOCAB = 512


def build_lm(cfg: FFConfig, vocab: int = VOCAB, hidden: int = 128,
             heads: int = 8, layers: int = 4):
    model = FFModel(cfg)
    tokens = model.create_tensor(
        [cfg.batch_size, cfg.serve_max_seq_len],
        dtype=DataType.INT32,
        name="tokens",
    )
    build_decoder_lm(
        model, tokens, vocab_size=vocab, hidden=hidden, num_heads=heads,
        num_layers=layers, ff_dim=4 * hidden,
    )
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[],
    )
    return model


def main():
    cfg = FFConfig.parse_args()
    model = build_lm(cfg)
    serve = ServeConfig.from_config(cfg)
    sched, _, cache = build_scheduler(model, serve)
    print(
        f"paged KV cache: {cache.spec.num_pages} pages of "
        f"{cache.spec.page_size} tokens (try --kv-page-size / --kv-pages)"
    )
    requests = [
        Request(
            rid=i,
            prompt=[(i * 13 + j) % VOCAB for j in range(1 + i % 7)],
            max_new_tokens=4 if i % 2 == 0 else 24,
            eos_token=serve.eos_token,
        )
        for i in range(3 * serve.max_seqs)
    ]
    done = sched.run(requests)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt {r.prompt} -> {r.generated}")
    s = sched.stats
    print(
        f"{s.tokens_generated} tokens, "
        f"{s.decode_steps} decode steps, occupancy {s.occupancy:.2f}, "
        f"peak in-flight {s.peak_in_flight}, {s.tokens_per_s:.0f} tokens/s"
    )
    if sched.telemetry is not None:
        slo = sched.telemetry.slo.snapshot()
        print(
            f"telemetry: p95 TTFT {slo['ttft_ms']['p95']:.1f}ms, "
            f"p95 ITL {slo['itl_ms']['p95']:.2f}ms, "
            f"violations {slo['violations']}"
            + (f", trace -> {serve.trace}" if serve.trace else "")
            + (f", metrics -> {serve.metrics_out}" if serve.metrics_out else "")
        )


if __name__ == "__main__":
    main()
