"""NLP workloads: Transformer encoder (the flagship bench), BERT proxy,
mT5-style encoder, and the served decoders (GPT-style `build_decoder_lm`,
`build_olmoe`, `build_deepseek_v3`, and `build_ouro`, whose layers run
several times over one set of weights)."""

from __future__ import annotations

from flexflow_tpu.core.types import ActiMode, DataType


def build_transformer_encoder(
    ff,
    input_tensor,
    hidden: int = 1024,
    num_heads: int = 16,
    num_layers: int = 12,
    dropout: float = 0.0,
):
    """reference: examples/cpp/Transformer/transformer.cc:33-45 — per layer:
    MHA then dense(relu)+dense, no residuals/LN in the reference benchmark;
    final dense(1)."""
    t = input_tensor
    for _ in range(num_layers):
        t = ff.multihead_attention(t, t, t, hidden, num_heads, dropout=dropout,
                                   bias=False)
        t = ff.dense(t, hidden, activation=ActiMode.RELU, use_bias=False)
        t = ff.dense(t, hidden, use_bias=False)
    return ff.dense(t, 1, use_bias=False)


def build_bert_proxy(
    ff,
    input_tensor,
    hidden: int = 768,
    num_heads: int = 12,
    num_layers: int = 12,
    ff_dim: int = 3072,
):
    """reference: examples/python/native/bert_proxy_native.py — BERT-base
    proxy blocks: pre-built embedding output [b, seq, hidden]; per layer
    MHA + add&norm + GELU MLP + add&norm."""
    t = input_tensor
    for _ in range(num_layers):
        a = ff.multihead_attention(t, t, t, hidden, num_heads)
        t = ff.layer_norm(ff.add(a, t))
        m = ff.dense(t, ff_dim, activation=ActiMode.GELU, use_bias=False)
        m = ff.dense(m, hidden, use_bias=False)
        t = ff.layer_norm(ff.add(m, t))
    return t


def build_mt5_encoder(
    ff,
    token_ids,
    vocab_size: int = 32128,
    hidden: int = 512,
    num_heads: int = 8,
    num_layers: int = 8,
    ff_dim: int = 1024,
):
    """reference: align/mt5_encoder/align_mt5_encoder_ff.py — embedding +
    pre-LN attention/MLP blocks (T5-style: RMS-ish LN approximated by LN,
    gated GELU feed-forward)."""
    t = ff.embedding(token_ids, vocab_size, hidden)
    for _ in range(num_layers):
        h = ff.layer_norm(t)
        a = ff.multihead_attention(h, h, h, hidden, num_heads, bias=False)
        t = ff.add(t, a)
        h = ff.layer_norm(t)
        wi0 = ff.dense(h, ff_dim, activation=ActiMode.GELU, use_bias=False)
        wi1 = ff.dense(h, ff_dim, use_bias=False)
        m = ff.multiply(wi0, wi1)
        m = ff.dense(m, hidden, use_bias=False)
        t = ff.add(t, m)
    return ff.layer_norm(t)


def build_decoder_lm(
    ff,
    token_ids,
    vocab_size: int = 256,
    hidden: int = 64,
    num_heads: int = 4,
    num_layers: int = 2,
    ff_dim: int = 128,
):
    """Decoder-only LM — the serving subsystem's workload (GPT-style
    pre-LN blocks with causal self-attention; flexflow_tpu.serving needs
    causal=True and a single token-id input to build its KV cache). Ends
    in vocab logits, not softmax, so generate() argmaxes raw logits."""
    t = ff.embedding(token_ids, vocab_size, hidden)
    for _ in range(num_layers):
        h = ff.layer_norm(t)
        a = ff.multihead_attention(
            h, h, h, hidden, num_heads, bias=False, causal=True
        )
        t = ff.add(t, a)
        h = ff.layer_norm(t)
        m = ff.dense(h, ff_dim, activation=ActiMode.GELU, use_bias=False)
        m = ff.dense(m, hidden, use_bias=False)
        t = ff.add(t, m)
    return ff.dense(ff.layer_norm(t), vocab_size, use_bias=False)


def build_olmoe(
    ff,
    token_ids,
    vocab_size: int = 50304,
    hidden: int = 2048,
    num_heads: int = 16,
    num_layers: int = 16,
    expert_hidden: int = 1024,
    num_experts: int = 64,
    experts_per_token: int = 8,
    rope_theta: float = 10000.0,
    eps: float = 1e-5,
    renormalise: bool = False,
):
    """OLMoE (allenai/OLMoE-1B-7B): pre-RMSNorm blocks of causal attention
    with QK-norm and rotary positions and as many key heads as query
    heads, then a dropless top-k expert layer of SiLU-gated MLPs (no
    shared expert, top-k weights not renormalised); a final RMSNorm and
    an untied head. No biases. Served like build_decoder_lm: vocab
    logits, one token-id input."""
    t = ff.embedding(token_ids, vocab_size, hidden)
    for _ in range(num_layers):
        h = ff.rms_norm(t, eps=eps)
        a = ff.multihead_attention(
            h, h, h, hidden, num_heads, bias=False, causal=True,
            rope_theta=rope_theta, qk_norm=True, qk_norm_eps=eps,
        )
        t = ff.add(t, a)
        m = ff.sparse_moe(
            ff.rms_norm(t, eps=eps), num_experts, experts_per_token,
            expert_hidden, renormalise=renormalise,
        )
        t = ff.add(t, m)
    return ff.dense(ff.rms_norm(t, eps=eps), vocab_size, use_bias=False)


def build_deepseek_v3(
    ff,
    token_ids,
    vocab_size: int = 129280,
    hidden: int = 7168,
    num_heads: int = 128,
    num_layers: int = 61,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    dense_hidden: int = 18432,
    dense_layers: int = 3,
    expert_hidden: int = 2048,
    num_experts: int = 256,
    experts_per_token: int = 8,
    shared_experts: int = 1,
    routed_scale: float = 2.5,
    rope_theta: float = 10000.0,
    eps: float = 1e-6,
    renormalise: bool = True,
    experts_held=None,
):
    """The `deepseek_v3` architecture (DeepSeek-V3 and the models that
    publish under its model_type, Kanana-2-30B-A3B among them) without
    query compression (q_lora_rank null): pre-RMSNorm blocks of latent
    attention, then a SiLU-gated MLP in the first `dense_layers` layers and
    after them a sigmoid-routed top-k expert layer (a per-expert bias moves
    the choice only; weights renormalised and scaled) plus the shared
    experts, which are ONE gated MLP of shared_experts x expert_hidden; a
    final RMSNorm and an untied head. No biases. `experts_held` (first,
    count): this chip's share of each expert layer (FFModel.sparse_moe);
    a sliced vocabulary is simply a smaller `vocab_size`. Served like
    build_decoder_lm: vocab logits, one token-id input."""
    t = ff.embedding(token_ids, vocab_size, hidden)
    for layer in range(num_layers):
        a = ff.latent_attention(
            ff.rms_norm(t, eps=eps), hidden, num_heads, kv_lora_rank,
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
            rope_theta=rope_theta, eps=eps,
        )
        t = ff.add(t, a)
        m = ff.rms_norm(t, eps=eps)
        if layer < dense_layers:
            t = ff.add(t, ff.gated_mlp(m, dense_hidden))
            continue
        routed = ff.sparse_moe(
            m, num_experts, experts_per_token, expert_hidden,
            renormalise=renormalise, scoring="sigmoid", choice_bias=True,
            scale=routed_scale, experts_held=experts_held,
        )
        shared = ff.gated_mlp(m, shared_experts * expert_hidden)
        t = ff.add(t, ff.add(routed, shared))
    return ff.dense(ff.rms_norm(t, eps=eps), vocab_size, use_bias=False)


def build_kimi_linear(
    ff,
    token_ids,
    vocab_size: int = 163840,
    hidden: int = 2304,
    num_heads: int = 32,
    num_layers: int = 27,
    kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
                23, 25, 26),
    full_attn_layers=(4, 8, 12, 16, 20, 24, 27),
    kda_head_dim: int = 128,
    kda_conv_kernel: int = 4,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    dense_hidden: int = 9216,
    dense_layers: int = 1,
    expert_hidden: int = 1024,
    num_experts: int = 256,
    experts_per_token: int = 8,
    shared_experts: int = 1,
    routed_scale: float = 2.446,
    eps: float = 1e-5,
    renormalise: bool = True,
    experts_held=None,
    kda_chunk: int = 64,
):
    """Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B, `kimi_linear`):
    pre-RMSNorm blocks whose attention is, by the published pattern
    (layers numbered from 1: `kda_layers`, `full_attn_layers`), either
    Kimi Delta Attention, a gated delta-rule linear attention over a
    fixed-size state (FFModel.linear_attention), or latent attention
    WITHOUT positional encoding (`mla_use_nope`: positions enter through
    the linear layers' recurrence and convolutions); then a SiLU-gated MLP
    in the first `dense_layers` layers and after them the `deepseek_v3`
    expert layer (sigmoid scores, a choice bias, renormalised and scaled
    weights) plus the shared experts as ONE gated MLP; a final RMSNorm and
    an untied head. No biases. Of the pattern the layers up to
    `num_layers` are built. `experts_held` (first, count): this chip's
    share of each expert layer; a sliced vocabulary is a smaller
    `vocab_size`. Served like build_decoder_lm: vocab logits, one
    token-id input."""
    kinds = {layer: "kda" for layer in kda_layers}
    kinds.update({layer: "full" for layer in full_attn_layers})
    missing = [n for n in range(1, num_layers + 1) if n not in kinds]
    if missing or len(kinds) != len(kda_layers) + len(full_attn_layers):
        raise ValueError(
            f"kda_layers and full_attn_layers must name every layer up to "
            f"{num_layers} once: {missing or 'a layer is in both'}"
        )
    t = ff.embedding(token_ids, vocab_size, hidden)
    for layer in range(1, num_layers + 1):
        h = ff.rms_norm(t, eps=eps)
        if kinds[layer] == "kda":
            a = ff.linear_attention(
                h, hidden, num_heads, kda_head_dim,
                conv_kernel=kda_conv_kernel, eps=eps, chunk=kda_chunk,
                name=f"l{layer}.kda",
            )
        else:
            a = ff.latent_attention(
                h, hidden, num_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rope_theta=None, eps=eps,
                name=f"l{layer}.mla",
            )
        t = ff.add(t, a)
        m = ff.rms_norm(t, eps=eps)
        if layer <= dense_layers:
            t = ff.add(t, ff.gated_mlp(m, dense_hidden))
            continue
        routed = ff.sparse_moe(
            m, num_experts, experts_per_token, expert_hidden,
            renormalise=renormalise, scoring="sigmoid", choice_bias=True,
            scale=routed_scale, experts_held=experts_held,
        )
        shared = ff.gated_mlp(m, shared_experts * expert_hidden)
        t = ff.add(t, ff.add(routed, shared))
    return ff.dense(ff.rms_norm(t, eps=eps), vocab_size, use_bias=False)


def build_ouro(
    ff,
    token_ids,
    vocab_size: int = 49152,
    hidden: int = 2048,
    num_heads: int = 16,
    num_layers: int = 48,
    ff_dim: int = 5632,
    loops: int = 4,
    rope_theta: float = 1000000.0,
    eps: float = 1e-6,
):
    """Ouro (ByteDance/Ouro-2.6B, arXiv:2510.25741): a stack of
    `num_layers` layers run `loops` times over the SAME weights. A layer
    is `u += N2(Attn(N1(u)))` then `u += N4(MLP(N3(u)))`: causal rotary
    attention with as many key heads as query heads, a SiLU-gated MLP, an
    RMSNorm before and after each. After every pass the one final norm,
    whose output is the next pass's input and feeds the exit gate
    `sigmoid(w_g . h + b_g)`; an untied head over the last pass. No
    biases but the gate's.

    Pass 1 makes the nodes that own the weights; passes 2.. apply them
    (`weights_of=`), so each is stored once while every pass's attention
    is a node, and a cache layer, of its own: pass t of layer i attends
    over what pass t of layer i wrote. Nodes are named by pass and layer
    (`p2.l5.attn`), which the step programs' scopes carry. The gates
    (`p<t>.exit`) are sinks beside the head: pass the returned head tensor
    as `compile(logits=)`. Served like build_decoder_lm: vocab logits of
    the last pass (the published early_exit_threshold of 1 never exits
    before it), one token-id input."""
    first = {}

    def part(build, key, *args, **kwargs):
        # the first call under `key` owns the weights, later ones borrow
        out = build(*args, weights_of=first.get(key), **kwargs)
        first.setdefault(key, out)
        return out

    t = ff.embedding(token_ids, vocab_size, hidden)
    for p in range(1, loops + 1):
        for layer in range(1, num_layers + 1):
            at = f"p{p}.l{layer}"
            h = part(ff.rms_norm, (layer, "n1"), t, eps=eps, name=f"{at}.n1")
            a = part(
                ff.multihead_attention, (layer, "attn"), h, h, h, hidden,
                num_heads, bias=False, causal=True, rope_theta=rope_theta,
                name=f"{at}.attn",
            )
            a = part(ff.rms_norm, (layer, "n2"), a, eps=eps, name=f"{at}.n2")
            t = ff.add(t, a, name=f"{at}.add1")
            h = part(ff.rms_norm, (layer, "n3"), t, eps=eps, name=f"{at}.n3")
            m = part(ff.gated_mlp, (layer, "mlp"), h, ff_dim, name=f"{at}.mlp")
            m = part(ff.rms_norm, (layer, "n4"), m, eps=eps, name=f"{at}.n4")
            t = ff.add(t, m, name=f"{at}.add2")
        t = part(ff.rms_norm, "norm", t, eps=eps, name=f"p{p}.norm")
        gate = part(ff.dense, "gate", t, 1, name=f"p{p}.gate")
        ff.sigmoid(gate, name=f"p{p}.exit")
    return ff.dense(t, vocab_size, use_bias=False, name="head")
