"""Mellum 2: a decoder-only language model whose attention layers differ in
mask and positions, not in kind, over sparse experts (JetBrains,
`model_type: mellum`; the published config of Mellum2-12B-A2.5B is
`Mellum2Config()`).

    block:   h = h + attn_i(rms(h));  h = h + moe(rms(h))
    attn_i:  q = rope_i(rms_head(h W_q)), k = rope_i(rms_head(h W_k)),
             v = h W_v: `num_attention_heads` query heads over
             `num_key_value_heads` key-value heads of `head_dim`, causal
             `sliding_attention` layer: query t sees the `sliding_window`
             keys t - window < s <= t (its own among them); plain rotary
             positions (`rope_parameters.sliding_attention`)
             `full_attention` layer: every key s <= t; YaRN's positions
             (`rope_parameters.full_attention`: the slow pairs' frequency
             divided by `factor`, cos and sin times `attention_factor`)
             then W_o
    moe:     a router over `num_experts` (the softmax over all of them,
             top `num_experts_per_tok`, the chosen weights renormalised)
             and SwiGLU experts of width `moe_intermediate_size`; no
             shared expert, no dense layer
    model:   embedding -> blocks -> rms -> logits (an untied head)

Expert parallelism is in the configuration, as `lfm2_moe`: a program
holds `experts_held` experts of every layer, from `first_expert` on. The
router scores all `num_experts`; the expert layer computes its own
experts' part of the sum and leaves out what the absent ones would have
added. Attention, router and norms are whole on every rank. With
`experts_held == num_experts` that is the whole model.

Built from `fluid.layers` only. The window is an attribute of the one
`flash_attention` op, YaRN's parameters attributes of `rotary_embedding`
and the scoring function one of `moe_route`, so a device trace names each
by its scope, and the band's kernels by their own names
(`flash_attention_win_fwd`, `flash_attention_win_bwd`).
"""
from .. import layers
from ..core.framework import default_main_program
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["Mellum2Config", "build_program"]

_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}


class Mellum2Config:
    """The published keys, with Mellum2-12B-A2.5B's values as defaults,
    plus the share of the experts this program holds."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 intermediate_size=7168, moe_intermediate_size=896,
                 num_hidden_layers=28, layer_types=None,
                 mlp_layer_types=None, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, num_experts=64,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 rms_norm_eps=1e-6, rope_parameters=None,
                 sliding_window=1024, use_sliding_window=True,
                 attention_bias=False, tie_word_embeddings=False,
                 hidden_act="silu", experts_held=None, first_expert=0,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # the width of a dense FFN: no layer of the published model is one
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        # (sliding, sliding, sliding, full) seven times
        self.layer_types = list(layer_types) if layer_types is not None \
            else (_PERIOD * num_hidden_layers)[:num_hidden_layers]
        mlp = ["sparse"] * len(self.layer_types) if mlp_layer_types is None \
            else list(mlp_layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_parameters = {**_ROPE, **(rope_parameters or {})}
        self.sliding_window = sliding_window if use_sliding_window else None
        self.experts_held = num_experts if experts_held is None \
            else experts_held
        self.first_expert = first_expert
        self.initializer_range = initializer_range
        if set(mlp) != {"sparse"} or len(mlp) != len(self.layer_types) \
                or attention_bias or tie_word_embeddings \
                or hidden_act != "silu":
            raise NotImplementedError(
                "this file builds the published variant: every FFN sparse, "
                "SwiGLU experts, no bias in the attention's projections, "
                "an untied head")
        if set(self.layer_types) - set(self.rope_parameters):
            raise ValueError("a layer type without rope_parameters: "
                             f"{sorted(set(self.layer_types))}")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("query heads are whole groups a key-value head")
        if first_expert + self.experts_held > num_experts:
            raise ValueError("experts held beyond num_experts")


def _init(cfg):
    return ParamAttr(initializer=NormalInitializer(0.0,
                                                   cfg.initializer_range))


def _linear(x, size, cfg, name):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False, name=name,
                     param_attr=_init(cfg))


def _rope(x, rope):
    """Rotary positions by a `rope_parameters` block: its keys are the
    layer's arguments, `rope_theta` under the name `theta`."""
    rope = dict(rope)
    return layers.rotary_embedding(x, theta=rope.pop("rope_theta"), **rope)


def _attention(x, cfg, name, kind):
    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    rope = cfg.rope_parameters[kind]

    def heads(y, n, norm):
        y = layers.reshape(y, [0, 0, n, D])
        if norm:
            y = layers.rms_norm(y, cfg.rms_norm_eps,
                                name=f"{name}_{norm}_norm")
            y = _rope(y, rope)
        return y

    q = heads(_linear(x, H * D, cfg, f"{name}_q"), H, "q")
    k = heads(_linear(x, KV * D, cfg, f"{name}_k"), KV, "k")
    v = heads(_linear(x, KV * D, cfg, f"{name}_v"), KV, None)
    out = layers.flash_attention(
        q, k, v, causal=True, name=f"{name}_attn",
        window=cfg.sliding_window if kind == "sliding_attention" else None)
    return _linear(layers.reshape(out, [0, 0, H * D]), cfg.hidden_size, cfg,
                   f"{name}_o")


def _moe(x, cfg, name):
    idx, w = layers.moe_route(
        x, cfg.num_experts, cfg.num_experts_per_tok, use_expert_bias=False,
        norm_topk_prob=cfg.norm_topk_prob, scoring="softmax",
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
    pairs = fullest = None
    for i, kind in enumerate(cfg.layer_types):
        name = f"l{i}"
        x = layers.rms_norm(h, cfg.rms_norm_eps, name=f"{name}_attn_norm")
        h = layers.elementwise_add(h, _attention(x, cfg, name, kind))
        x = layers.rms_norm(h, cfg.rms_norm_eps, name=f"{name}_ffn_norm")
        y, p, f = _moe(x, cfg, name)
        pairs = p if pairs is None else layers.elementwise_add(pairs, p)
        fullest = f if fullest is None else layers.elementwise_add(fullest, f)
        h = layers.elementwise_add(h, y)
    h = layers.rms_norm(h, cfg.rms_norm_eps, name="final_norm")
    logits = _linear(h, cfg.vocab_size, cfg, "lm_head")
    # the mean over the tokens in float32 whatever the logits are run in
    loss = layers.mean(layers.cast(layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2])), "float32"))
    program = default_main_program()
    program.mark_counter(pairs, "moe.local_pairs")
    program.mark_counter(fullest, "moe.max_expert_pairs")
    return {"ids": ids, "labels": labels}, loss
