"""Solar Open 2: a decoder-only language model of gated delta-rule linear
attention, gated softmax attention without positions and sparse experts
beside a shared one (upstage, `model_type: solar_open2`; the published
config of Solar-Open2-250B is `SolarOpen2Config()`).

    block:  h = h + mixer_i(rms(h));  h = h + ffn(rms(h))
            mixer_i = gqa where i is in `gqa_layers`, else kda
    gqa:    q = h W_q, k = h W_k, v = h W_v: `num_attention_heads` query
            heads over `num_key_value_heads` key-value heads of
            `head_dim`, causal, no positions (`use_rope: false`), no q/k
            norm;  y = (sigmoid(h W_g) * attention) W_o
    kda:    q, k = l2(silu(conv(h W_q))), l2(silu(conv(h W_k))),
            v = silu(conv(h W_v)), conv a causal depthwise filter of
            `short_conv_kernel_size` taps; the log-decay g = -exp(A_log) *
            softplus(W_a_up (W_a_down h) + dt_bias) per key channel
            (float32), the step beta = 2 sigmoid(h w_b) per head
            (`kda_allow_neg_eigval`); the gated delta rule over them
            (`layers.kda_attention`);  y = (sigmoid(W_g_up (W_g_down h)) *
            rms_head(o)) W_o
    ffn:    a router over `n_routed_experts` (sigmoid, top
            `num_experts_per_tok` by score + bias, weights renormalised)
            and SwiGLU experts of width `moe_intermediate_size`, beside
            `n_shared_experts` shared experts of the same width that every
            token passes
    model:  embedding -> blocks -> rms -> logits (an untied head)

The share of a deployment is in the configuration. Tensor parallelism: a
program holds `heads_held` of the mixers' query heads from `first_head`
on with the `kv_heads_held` key-value heads they read; it is then a model
of that many heads whose W_o takes the held heads' outputs, and what the
absent heads would add to the sum is left out (heads are held, not
exchanged). Expert parallelism: `experts_held` experts of every layer
from `first_expert` on, as `lfm2_moe`. The router, the shared expert,
the low-rank gate maps' inner side and the norms are whole on every
rank. With everything held that is the whole model.

Built from `fluid.layers` only; one Fluid op type per mechanism
(`kda_attention`, `kda_gate`, `l2_norm`, `short_conv`, `flash_attention`,
`rms_norm`, `swiglu`, `moe_route`, `moe_expert_ffn`), so a device trace
names each by its scope.
"""
from .. import layers
from ..core.framework import default_main_program
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr

__all__ = ["SolarOpen2Config", "build_program"]


class SolarOpen2Config:
    """The published keys, with Solar-Open2-250B's values as defaults,
    plus the share of the heads and the experts this program holds."""

    def __init__(self, vocab_size=196608, hidden_size=4096,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128, gqa_layers=None,
                 layer_types=None, linear_attn_config=None, gate_rank=None,
                 moe_intermediate_size=1280, n_routed_experts=320,
                 n_shared_experts=1, num_experts_per_tok=8,
                 first_k_dense_replace=0, norm_topk_prob=True,
                 routed_scaling_factor=1.0, use_expert_bias=True,
                 rms_norm_eps=1e-5, use_rope=False, use_gqa_gate=True,
                 kda_use_full_proj=False, kda_allow_neg_eigval=True,
                 heads_held=None, kv_heads_held=None, first_head=0,
                 experts_held=None, first_expert=0,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        # every fourth layer is softmax attention: 0, 4, ..., 44
        if layer_types is None:
            gqa = set(range(0, num_hidden_layers, 4)) if gqa_layers is None \
                else set(gqa_layers)
            layer_types = ["gqa" if i in gqa else "kda"
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        linear = {"short_conv_kernel_size": 4, "head_dim": 128,
                  "num_heads": 64, **(linear_attn_config or {})}
        self.linear_head_dim = linear["head_dim"]
        self.short_conv_kernel_size = linear["short_conv_kernel_size"]
        # the rank of the two low-rank gate maps: the head size
        self.gate_rank = self.linear_head_dim if gate_rank is None \
            else gate_rank
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.use_expert_bias = use_expert_bias
        self.rms_norm_eps = rms_norm_eps
        self.heads_held = num_attention_heads if heads_held is None \
            else heads_held
        self.kv_heads_held = num_key_value_heads if kv_heads_held is None \
            else kv_heads_held
        self.first_head = first_head
        self.experts_held = n_routed_experts if experts_held is None \
            else experts_held
        self.first_expert = first_expert
        self.initializer_range = initializer_range
        if (first_k_dense_replace, use_rope, use_gqa_gate, kda_use_full_proj,
                kda_allow_neg_eigval) != (0, False, True, False, True):
            raise NotImplementedError(
                "this file builds the published variant: no leading dense "
                "layer, no rotary positions, a gated softmax attention, "
                "low-rank gate maps and a step in (0, 2)")
        if linear["num_heads"] != num_attention_heads:
            raise ValueError("one share of heads serves both mixers: "
                             "linear num_heads != num_attention_heads")
        group = num_attention_heads // num_key_value_heads
        if self.heads_held != self.kv_heads_held * group \
                or first_head % group \
                or first_head + self.heads_held > num_attention_heads:
            raise ValueError("the heads held are whole groups of "
                             f"{group} query heads a key-value head")
        if first_expert + self.experts_held > n_routed_experts:
            raise ValueError("experts held beyond n_routed_experts")


def _init(cfg):
    return ParamAttr(initializer=NormalInitializer(0.0,
                                                   cfg.initializer_range))


def _linear(x, size, cfg, name):
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False, name=name,
                     param_attr=_init(cfg))


def _gqa(x, cfg, name):
    H, KV, D = cfg.heads_held, cfg.kv_heads_held, cfg.head_dim
    q = layers.reshape(_linear(x, H * D, cfg, f"{name}_q"), [0, 0, H, D])
    k = layers.reshape(_linear(x, KV * D, cfg, f"{name}_k"), [0, 0, KV, D])
    v = layers.reshape(_linear(x, KV * D, cfg, f"{name}_v"), [0, 0, KV, D])
    out = layers.flash_attention(q, k, v, causal=True, name=f"{name}_attn")
    gate = layers.sigmoid(_linear(x, H * D, cfg, f"{name}_g"))
    out = layers.elementwise_mul(gate, layers.reshape(out, [0, 0, H * D]))
    return _linear(out, cfg.hidden_size, cfg, f"{name}_o")


def _kda(x, cfg, name):
    H, D, R = cfg.heads_held, cfg.linear_head_dim, cfg.gate_rank

    def heads(y):
        return layers.reshape(y, [0, 0, H, D])

    def mixed(which):
        y = layers.short_conv(_linear(x, H * D, cfg, f"{name}_{which}"),
                              cfg.short_conv_kernel_size,
                              name=f"{name}_{which}_conv")
        return heads(layers.silu(y))

    q = layers.l2_norm(mixed("q"))
    k = layers.l2_norm(mixed("k"))
    v = mixed("v")
    g = layers.kda_gate(
        heads(_linear(_linear(x, R, cfg, f"{name}_a_down"), H * D, cfg,
                      f"{name}_a_up")), name=f"{name}_decay")
    beta = layers.scale(layers.sigmoid(_linear(x, H, cfg, f"{name}_b")), 2.0)
    o = layers.kda_attention(q, k, v, g, beta, name=f"{name}_kda")
    o = layers.rms_norm(o, cfg.rms_norm_eps, name=f"{name}_o_norm")
    gate = layers.sigmoid(
        _linear(_linear(x, R, cfg, f"{name}_g_down"), H * D, cfg,
                f"{name}_g_up"))
    out = layers.elementwise_mul(gate, layers.reshape(o, [0, 0, H * D]))
    return _linear(out, cfg.hidden_size, cfg, f"{name}_o")


def _ffn(x, cfg, name):
    """(y, local_pairs, max_expert_pairs): the shared expert, whole on
    every rank, and this rank's part of the routed sum."""
    idx, w = layers.moe_route(
        x, cfg.n_routed_experts, cfg.num_experts_per_tok,
        use_expert_bias=cfg.use_expert_bias,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        param_attr=_init(cfg), name=f"{name}_router")
    y, pairs, fullest = layers.moe_expert_ffn(
        x, idx, w, cfg.experts_held, cfg.first_expert,
        cfg.moe_intermediate_size, param_attr=_init(cfg),
        name=f"{name}_experts")
    if cfg.n_shared_experts:
        F = cfg.n_shared_experts * cfg.moe_intermediate_size
        gate = _linear(x, F, cfg, f"{name}_shared_w1")
        up = _linear(x, F, cfg, f"{name}_shared_w3")
        y = layers.elementwise_add(
            y, _linear(layers.swiglu(gate, up), cfg.hidden_size, cfg,
                       f"{name}_shared_w2"))
    return y, pairs, fullest


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
        x = layers.rms_norm(h, cfg.rms_norm_eps, name=f"{name}_mixer_norm")
        mixer = _gqa if kind == "gqa" else _kda
        h = layers.elementwise_add(h, mixer(x, cfg, name))
        x = layers.rms_norm(h, cfg.rms_norm_eps, name=f"{name}_ffn_norm")
        y, p, f = _ffn(x, cfg, name)
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
