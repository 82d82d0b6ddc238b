"""Model `mellum2`: Mellum 2 (sliding-window and full attention layers
mixed, rotary positions by layer type with YaRN on the full layers, a
softmax top-k router over sparse experts) as one expert-parallel rank
trains it, for the training driver (`entries/train.py`, which finds this
file through the configuration's `model` key). What a model file says is
listed in `models/nmt.py`.

The configuration is the published `config.json` cut to one chip's share
(PERF.md section 4): `experts_held` experts of every layer from
`first_expert` on, `vocab_size` rows of the vocabulary, and the layers of
`layer_types`. The router keeps its `num_experts` outputs; attention,
router and norms are whole.

Nothing of the program is imported until `build` is called, so the
benchmark's other cells load this file on a program that has no such
model (a parent commit), where this model's cell fails at once.
"""
import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.models import solar_open2 as solar
from chipbench.models.lfm2_moe import (  # noqa: F401  (the driver's API)
    make_batches, tokens_per_step)
from chipbench.reference import mellum2 as reference
from chipbench.weights import seed_key

reference_steps = reference.train_steps
tree_norms = reference.tree_norms


# ------------------------------------------------------------ the program
def build(cfg, traffic, fluid):
    from paddle_tpu.models import mellum2
    model = mellum2.Mellum2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        layer_types=cfg["layer_types"],
        mlp_layer_types=cfg["mlp_layer_types"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_parameters=cfg["rope_parameters"],
        sliding_window=cfg["sliding_window"],
        use_sliding_window=cfg["use_sliding_window"],
        attention_bias=cfg["attention_bias"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        hidden_act=cfg["hidden_act"],
        experts_held=cfg["experts_held"], first_expert=cfg["first_expert"])
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            _feeds, loss = mellum2.build_program(model, traffic["length"])
            fluid.optimizer.Adam(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["epsilon"]).minimize(loss)
    return main, startup, loss


def param_specs(cfg):
    """[(name, shape, kind)] in the order the program declares them."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, F = cfg["experts_held"], cfg["moe_intermediate_size"]
    specs = [("embed.w_0", (V, H), "matrix")]
    for i in range(len(cfg["layer_types"])):
        n = f"l{i}"
        specs += [(f"{n}_attn_norm.w_0", (H,), "norm"),
                  (f"{n}_q.w_0", (H, nh * D), "matrix"),
                  (f"{n}_q_norm.w_0", (D,), "norm"),
                  (f"{n}_k.w_0", (H, kv * D), "matrix"),
                  (f"{n}_k_norm.w_0", (D,), "norm"),
                  (f"{n}_v.w_0", (H, kv * D), "matrix"),
                  (f"{n}_o.w_0", (nh * D, H), "matrix"),
                  (f"{n}_ffn_norm.w_0", (H,), "norm"),
                  (f"{n}_router.w_0", (H, cfg["num_experts"]), "router"),
                  (f"{n}_experts.w_0", (E, H, F), "matrix"),
                  (f"{n}_experts.w_1", (E, H, F), "matrix"),
                  (f"{n}_experts.w_2", (E, F, H), "matrix")]
    specs += [("final_norm.w_0", (H,), "norm"),
              ("lm_head.w_0", (H, V), "matrix")]
    return specs


def bias_names(cfg):
    """The router has no selection bias: no persistable variable that is
    no Parameter."""
    return []


def make_params(cfg, seed, dtype):
    """{name: array} on the default device, one jitted call: every
    parameter of `param_specs`. Matrices (embedding, head and experts too)
    N(0, 0.02) in `dtype`; RMSNorm weights 1 and the router's weight
    N(0, 0.02), float32. The router has no selection bias."""
    specs = param_specs(cfg)
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            if kind == "norm":
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * 0.02
            out[name] = w if kind == "router" else w.astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))


# -------------------------------------------------------------- the counts
def _window(cfg, kind, length):
    """The window of a layer of `kind` over `length` positions: None on a
    full layer, and where it covers every key anyway."""
    W = cfg["sliding_window"] if kind == "sliding_attention" else None
    return None if W is None or W >= length else W


def _layer_keys(cfg, kind, length):
    """Score elements a query head and row of one attention layer: the
    causal half, or with a window the band alone (the first `window`
    queries see 1 .. window keys, every later one `window`)."""
    W = _window(cfg, kind, length)
    if W is None:
        return length * length // 2
    return W * (W + 1) // 2 + (length - W) * W


def forward_flops_per_token(cfg, length):
    """Needed FLOPs of one token's forward pass, 2 a multiply-add, matrix
    products and attention only (counts.py's rule). A full layer's
    attention counts the causal half, a sliding layer's the band. The
    experts count the EXPECTED pairs of a token on this rank,
    `num_experts_per_tok * experts_held / num_experts` (2 in the
    benchmark's cut)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    proj = 2 * H * (nh + 2 * kv) * D + 2 * nh * D * H
    pairs = cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["num_experts"]
    moe = 2 * H * cfg["num_experts"] \
        + pairs * 6 * H * cfg["moe_intermediate_size"]
    total = 2 * H * V
    for kind in cfg["layer_types"]:
        total += proj + 4 * nh * D * _layer_keys(cfg, kind, length) \
            / length + moe
    return total


def step_flops(cfg, traffic):
    """Forward and backward (twice the forward), no recomputation."""
    return 3 * tokens_per_step(traffic) * forward_flops_per_token(
        cfg, traffic["length"])


def attention_work(cfg, traffic, kind):
    """{which: (FLOPs, bytes)} of one attention layer of `kind` over the
    batch, as the algorithm needs them whatever implements it: a product
    is 2 D FLOPs a score element, over the causal half or the band alone
    (`_layer_keys`). Forward two of them, q and out at the query heads
    and k, v at the key-value heads once each. Backward five (the scores
    again, dp, dv, dk, dq), reading q, k, v, out, dout and writing dq,
    dk, dv."""
    B, T = traffic["rows"], traffic["length"]
    nh, kv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    size = counts.ITEMSIZE[cfg["precision"]["activations"]]
    product = 2 * B * nh * D * _layer_keys(cfg, kind, T)
    q_bytes, kv_bytes = B * T * nh * D * size, B * T * kv * D * size
    return {"fwd": (2 * product, 2 * q_bytes + 2 * kv_bytes),
            "bwd": (5 * product, 4 * q_bytes + 4 * kv_bytes)}


def kernel_work(cfg, traffic):
    """{kernel: [(FLOPs, bytes, calls)]} a step, for `<kernel>_roofline`:
    the band's kernels over the sliding layers, the full causal kernels
    over the others, the grouped expert products and `moe_combine` as
    `models/solar_open2.py` counts them."""
    work = {}
    for kind in sorted(set(cfg["layer_types"])):
        n = sum(k == kind for k in cfg["layer_types"])
        tag = "" if _window(cfg, kind, traffic["length"]) is None else "win_"
        for which, w in attention_work(cfg, traffic, kind).items():
            work.setdefault(f"flash_attention_{tag}{which}", []).append(
                w + (n,))
    # under the key `models/solar_open2.py` names the router's width by
    for k, calls in solar.expert_work(
            dict(cfg, n_routed_experts=cfg["num_experts"]), traffic).items():
        work[k] = [c + (len(cfg["layer_types"]),) for c in calls]
    return work
