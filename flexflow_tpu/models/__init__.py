"""Model zoo: builders for the reference's example workloads
(reference: SURVEY §2.8, examples/cpp/* and examples/python/*).

Each builder takes an FFModel + config kwargs, adds layers, and returns the
logits Tensor; compilation/training stays with the caller (the examples/
scripts and benchmarks/families/)."""

from flexflow_tpu.models.vision import (
    build_alexnet,
    build_inception_v3,
    build_resnet50,
    build_resnext50,
)
from flexflow_tpu.models.nlp import (
    build_bert_proxy,
    build_decoder_lm,
    build_deepseek_v3,
    build_kimi_linear,
    build_mt5_encoder,
    build_olmoe,
    build_ouro,
    build_transformer_encoder,
)
from flexflow_tpu.models.recommender import build_candle_uno, build_dlrm, build_xdl
from flexflow_tpu.models.mixture import build_moe_mlp, build_moe_encoder
from flexflow_tpu.models.mlp import build_mlp_unify

__all__ = [
    "build_alexnet",
    "build_resnet50",
    "build_resnext50",
    "build_inception_v3",
    "build_transformer_encoder",
    "build_bert_proxy",
    "build_decoder_lm",
    "build_mt5_encoder",
    "build_deepseek_v3",
    "build_kimi_linear",
    "build_olmoe",
    "build_ouro",
    "build_dlrm",
    "build_xdl",
    "build_candle_uno",
    "build_moe_mlp",
    "build_moe_encoder",
    "build_mlp_unify",
]
