"""LFM2-MoE: a decoder-only language model of gated short convolutions,
grouped-query attention and sparse experts (LiquidAI, `model_type:
lfm2_moe`; the published config of LFM2-24B-A2B is `Lfm2MoeConfig()`).

    block:      h = h + mixer(rms(h));  h = h + ffn(rms(h))
    conv mixer: B, C, x = split3(h W_in);  y = (C * conv(B * x)) W_out,
                conv a causal depthwise filter of `conv_L_cache` taps
    attention:  q = rope(rms_head(h W_q)), k = rope(rms_head(h W_k)),
                v = h W_v; `num_attention_heads` query heads over
                `num_key_value_heads` key-value heads, causal; then W_o
    ffn:        SwiGLU of width `intermediate_size` in the first
                `num_dense_layers` layers; after them a router over
                `num_experts` (sigmoid, top `num_experts_per_tok` by score
                + bias, weights renormalised) and SwiGLU experts of width
                `moe_intermediate_size`
    model:      embedding -> blocks -> rms -> logits (the embedding, tied)

Expert parallelism is in the configuration: a program holds
`experts_held` experts of every layer, from `first_expert` on. The router
scores all `num_experts`; the expert layer computes its own experts' part
of the sum and leaves out what the absent ones would have added. With
`experts_held == num_experts` that is the whole model.

Built from `fluid.layers` only; one Fluid op type per new mechanism
(`rms_norm`, `rotary_embedding`, `short_conv`, `moe_route`,
`moe_expert_ffn`), so a device trace names each by its scope.
"""
from .. import layers
from ..core.framework import default_main_program
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["Lfm2MoeConfig", "build_program"]

_PERIOD = ["full_attention", "conv", "conv", "conv"]


class Lfm2MoeConfig:
    """The published keys, with LFM2-24B-A2B's values as defaults, plus
    the share of the experts this program holds."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 layer_types=None, num_dense_layers=2, num_experts=64,
                 num_experts_per_tok=4, num_attention_heads=32,
                 num_key_value_heads=8, conv_L_cache=3, norm_eps=1e-5,
                 rope_theta=1000000.0, norm_topk_prob=True,
                 use_expert_bias=True, routed_scaling_factor=1.0,
                 experts_held=None, first_expert=0,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        # 40 layers: conv, conv, then (full_attention, conv, conv, conv)
        # ten times less the last two
        self.layer_types = list(layer_types) if layer_types is not None \
            else (["conv", "conv"] + _PERIOD * 10)[:40]
        self.num_dense_layers = num_dense_layers
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.conv_L_cache = conv_L_cache
        self.norm_eps = norm_eps
        self.rope_theta = rope_theta
        self.norm_topk_prob = norm_topk_prob
        self.use_expert_bias = use_expert_bias
        self.routed_scaling_factor = routed_scaling_factor
        self.experts_held = num_experts if experts_held is None \
            else experts_held
        self.first_expert = first_expert
        self.initializer_range = initializer_range
        if self.first_expert + self.experts_held > num_experts:
            raise ValueError("experts held beyond num_experts")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def _init(cfg):
    return ParamAttr(initializer=NormalInitializer(0.0,
                                                   cfg.initializer_range))


def _linear(x, size, cfg, name):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False, name=name,
                     param_attr=_init(cfg))


def _conv_mixer(x, cfg, name):
    bcx = _linear(x, 3 * cfg.hidden_size, cfg, f"{name}_conv_in")
    b, c, u = layers.split(bcx, 3, dim=2)
    u = layers.short_conv(layers.elementwise_mul(b, u), cfg.conv_L_cache,
                          name=f"{name}_conv")
    return _linear(layers.elementwise_mul(c, u), cfg.hidden_size, cfg,
                   f"{name}_conv_out")


def _attention(x, cfg, name):
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

    def heads(y, n, norm):
        y = layers.reshape(y, [0, 0, n, D])
        if norm:
            y = layers.rms_norm(y, cfg.norm_eps, name=f"{name}_{norm}_norm")
            y = layers.rotary_embedding(y, cfg.rope_theta)
        return y

    q = heads(_linear(x, H * D, cfg, f"{name}_q"), H, "q")
    k = heads(_linear(x, KV * D, cfg, f"{name}_k"), KV, "k")
    v = heads(_linear(x, KV * D, cfg, f"{name}_v"), KV, None)
    out = layers.flash_attention(q, k, v, causal=True, name=f"{name}_attn")
    return _linear(layers.reshape(out, [0, 0, H * D]), cfg.hidden_size, cfg,
                   f"{name}_o")


def _dense_ffn(x, cfg, name):
    gate = _linear(x, cfg.intermediate_size, cfg, f"{name}_ffn_w1")
    up = _linear(x, cfg.intermediate_size, cfg, f"{name}_ffn_w3")
    return _linear(layers.swiglu(gate, up), cfg.hidden_size, cfg,
                   f"{name}_ffn_w2")


def _expert_ffn(x, cfg, name):
    idx, w = layers.moe_route(
        x, cfg.num_experts, cfg.num_experts_per_tok,
        use_expert_bias=cfg.use_expert_bias,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        param_attr=_init(cfg), name=f"{name}_router")
    return layers.moe_expert_ffn(
        x, idx, w, cfg.experts_held, cfg.first_expert,
        cfg.moe_intermediate_size, param_attr=_init(cfg),
        name=f"{name}_experts")


def build_program(cfg, seq_len):
    """Declare the training forward in the default main program: feeds
    `ids` and `labels` ([B, seq_len] int64, the label the next id), mean
    cross-entropy over every position. Returns ({name: Variable}, loss).
    The expert layers' load is marked for the executor to count
    (`moe.local_pairs`, `moe.max_expert_pairs`, summed over the layers)."""
    ids = layers.data("ids", shape=[seq_len], dtype="int64")
    labels = layers.data("labels", shape=[seq_len], dtype="int64")
    h = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size], name="embed",
        param_attr=_init(cfg))
    table = default_main_program().global_block().var("embed.w_0")
    pairs = fullest = None
    for i, kind in enumerate(cfg.layer_types):
        name = f"l{i}"
        x = layers.rms_norm(h, cfg.norm_eps, name=f"{name}_operator_norm")
        mixer = _conv_mixer if kind == "conv" else _attention
        h = layers.elementwise_add(h, mixer(x, cfg, name))
        x = layers.rms_norm(h, cfg.norm_eps, name=f"{name}_ffn_norm")
        if i < cfg.num_dense_layers:
            y = _dense_ffn(x, cfg, name)
        else:
            y, p, f = _expert_ffn(x, cfg, name)
            pairs = p if pairs is None else layers.elementwise_add(pairs, p)
            fullest = f if fullest is None \
                else layers.elementwise_add(fullest, f)
        h = layers.elementwise_add(h, y)
    h = layers.rms_norm(h, cfg.norm_eps, name="final_norm")
    logits = layers.matmul(h, table, transpose_y=True, name="lm_head")
    # the mean over the tokens in float32 whatever the logits are run in
    loss = layers.mean(layers.cast(layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2])), "float32"))
    if pairs is not None:
        program = default_main_program()
        program.mark_counter(pairs, "moe.local_pairs")
        program.mark_counter(fullest, "moe.max_expert_pairs")
    return {"ids": ids, "labels": labels}, loss
