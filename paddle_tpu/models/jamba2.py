"""Jamba 2: a decoder-only language model of Mamba-1 mixers with a few
attention layers between them and a dense SwiGLU after every mixer (AI21,
`model_type: jamba`; the published config of Jamba2-3B is
`Jamba2Config()`).

    block:  h = h + mixer_i(rms(h));  h = h + mlp(rms(h))
            mixer_i = attention where i % attn_layer_period ==
            attn_layer_offset, else mamba
    mamba:  [x | z] = h W_in;  x = silu(conv(x) + b_conv), conv a causal
            depthwise filter of `mamba_d_conv` taps;
            [dt_r | B | C] = x W_x;  dt_r, B, C = rms(dt_r), rms(B), rms(C)
            (Jamba's three extra norms); dt = softplus(dt_r W_dt + b_dt);
            y = selective_scan(x, dt, A_log, B, C, D)
            (`layers.selective_scan`);  out = (y * silu(z)) W_out
    attention: `num_attention_heads` query heads over
            `num_key_value_heads` key-value heads of hidden / heads,
            causal, no positions, no biases
    mlp:    (silu(h W_gate) * (h W_up)) W_down   (num_experts is 1)
    model:  embedding -> blocks -> rms -> logits = h E^T (tied)

The share of a deployment is in the configuration. Tensor parallelism: a
program holds `channels_held` of a mamba mixer's inner channels from
`first_channel` on (W_in's x and z columns, the conv's taps and bias, W_x's
rows, W_dt's columns and bias, A_log, D, W_out's rows), `heads_held` query
heads from `first_head` on with every key-value head whole, and
`intermediate_held` of the MLP's width; what the absent shares would add
to W_x's product and to the sums after W_out, W_o and W_down is left out
(held, not exchanged). With everything held that is the whole model.

Built from `fluid.layers` only; one Fluid op type per mechanism
(`selective_scan`, `short_conv`, `softplus`, `rms_norm`, `swiglu`,
`flash_attention`, `mul`, the tied head's `matmul`), and every product
names its site, so a device trace names each.
"""
import numpy as np

from .. import layers
from ..core.framework import default_main_program
from ..initializer import ConstantInitializer, NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["Jamba2Config", "build_program"]


class Jamba2Config:
    """The published keys, with Jamba2-3B's values as defaults, plus the
    share of the inner channels, the query heads and the MLP's width this
    program holds."""

    def __init__(self, vocab_size=65536, hidden_size=2560,
                 intermediate_size=8192, num_hidden_layers=28,
                 num_attention_heads=20, num_key_value_heads=1,
                 attn_layer_period=14, attn_layer_offset=7,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=160, mamba_conv_bias=True,
                 mamba_proj_bias=False, num_experts=1,
                 tie_word_embeddings=True, rms_norm_eps=1e-6,
                 channels_held=None, first_channel=0, heads_held=None,
                 first_head=0, intermediate_held=None,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.layer_types = [
            "attention" if i % attn_layer_period == attn_layer_offset
            else "mamba" for i in range(num_hidden_layers)]
        self.d_state = mamba_d_state
        self.d_conv = mamba_d_conv
        self.d_inner = mamba_expand * hidden_size
        self.dt_rank = mamba_dt_rank
        self.rms_norm_eps = rms_norm_eps
        self.channels_held = self.d_inner if channels_held is None \
            else channels_held
        self.first_channel = first_channel
        self.heads_held = num_attention_heads if heads_held is None \
            else heads_held
        self.first_head = first_head
        self.intermediate_held = intermediate_size if intermediate_held \
            is None else intermediate_held
        self.initializer_range = initializer_range
        if (mamba_conv_bias, mamba_proj_bias, num_experts,
                tie_word_embeddings) != (True, False, 1, True):
            raise NotImplementedError(
                "this file builds the published variant: a conv bias, no "
                "projection biases, a dense MLP and a tied head")
        if first_channel + self.channels_held > self.d_inner \
                or first_head + self.heads_held > num_attention_heads \
                or self.intermediate_held > intermediate_size:
            raise ValueError("a share beyond the published width")


def _init(cfg):
    return ParamAttr(initializer=NormalInitializer(0.0,
                                                   cfg.initializer_range))


def _linear(x, size, cfg, name):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False, name=name,
                     param_attr=_init(cfg))


def _mamba(h, cfg, name):
    Ch, N, R = cfg.channels_held, cfg.d_state, cfg.dt_rank
    x, z = layers.split(_linear(h, 2 * Ch, cfg, f"{name}_in"), 2, dim=2)
    x = layers.silu(layers.short_conv(x, cfg.d_conv, name=f"{name}_conv",
                                      bias_attr=True))
    dt, B, C = layers.split(_linear(x, R + 2 * N, cfg, f"{name}_x"),
                            [R, N, N], dim=2)
    dt = layers.rms_norm(dt, cfg.rms_norm_eps, name=f"{name}_dt_norm")
    B = layers.rms_norm(B, cfg.rms_norm_eps, name=f"{name}_b_norm")
    C = layers.rms_norm(C, cfg.rms_norm_eps, name=f"{name}_c_norm")
    # b_dt to start: the inverse softplus of a step of 0.01
    step = ParamAttr(initializer=ConstantInitializer(
        float(np.log(np.expm1(0.01)))))
    dt = layers.fc(dt, Ch, num_flatten_dims=2, name=f"{name}_dt",
                   param_attr=_init(cfg), bias_attr=step, act="softplus")
    y = layers.selective_scan(x, dt, None, B, C, None, name=f"{name}_scan")
    return _linear(layers.swiglu(z, y), cfg.hidden_size, cfg, f"{name}_out")


def _attention(x, cfg, name):
    H, KV, D = cfg.heads_held, cfg.num_key_value_heads, cfg.head_dim
    q = layers.reshape(_linear(x, H * D, cfg, f"{name}_q"), [0, 0, H, D])
    k = layers.reshape(_linear(x, KV * D, cfg, f"{name}_k"), [0, 0, KV, D])
    v = layers.reshape(_linear(x, KV * D, cfg, f"{name}_v"), [0, 0, KV, D])
    out = layers.flash_attention(q, k, v, causal=True, name=f"{name}_attn")
    return _linear(layers.reshape(out, [0, 0, H * D]), cfg.hidden_size, cfg,
                   f"{name}_o")


def _mlp(x, cfg, name):
    F = cfg.intermediate_held
    gate = _linear(x, F, cfg, f"{name}_mlp_gate")
    up = _linear(x, F, cfg, f"{name}_mlp_up")
    return _linear(layers.swiglu(gate, up), cfg.hidden_size, cfg,
                   f"{name}_mlp_down")


def build_program(cfg, seq_len):
    """Declare the training forward in the default main program: feeds
    `ids` and `labels` ([B, seq_len] int64, the label the next id), mean
    cross-entropy over every position. Returns ({name: Variable}, loss)."""
    ids = layers.data("ids", shape=[seq_len], dtype="int64")
    labels = layers.data("labels", shape=[seq_len], dtype="int64")
    h = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size], name="embed",
        param_attr=_init(cfg))
    table = default_main_program().global_block().var("embed.w_0")
    for i, kind in enumerate(cfg.layer_types):
        name = f"l{i}"
        x = layers.rms_norm(h, cfg.rms_norm_eps, name=f"{name}_mixer_norm")
        mixer = _mamba if kind == "mamba" else _attention
        h = layers.elementwise_add(h, mixer(x, cfg, name))
        x = layers.rms_norm(h, cfg.rms_norm_eps, name=f"{name}_mlp_norm")
        h = layers.elementwise_add(h, _mlp(x, cfg, name))
    h = layers.rms_norm(h, cfg.rms_norm_eps, name="final_norm")
    logits = layers.matmul(h, table, transpose_y=True, name="lm_head")
    # the mean over the tokens in float32 whatever the logits are run in
    loss = layers.mean(layers.cast(layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2])), "float32"))
    return {"ids": ids, "labels": labels}, loss
