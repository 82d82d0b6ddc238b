"""Model `solar_open2`: Solar Open 2 (gated delta-rule linear attention
with a per-channel decay, gated softmax attention without positions, a
shared expert beside a no-drop top-k router over sparse experts) as one
rank of an expert- and tensor-parallel deployment trains it, for the
training driver (`entries/train.py`, which finds this file through the
configuration's `model` key). What a model file says is listed in
`models/nmt.py`.

The configuration is the published `config.json` cut to one chip's share
(PERF.md section 4): `heads_held` of the mixers' query heads with the
`kv_heads_held` key-value heads they read, `experts_held` experts of
every layer from `first_expert` on, `vocab_size` rows of the vocabulary,
and the layers of `layer_types`. The router keeps its `n_routed_experts`
outputs; the shared expert, the norms and the inner side of the low-rank
gate maps are whole.

Nothing of the program is imported until `build` is called, so the
benchmark's other cells load this file on a program that has no such
model (a parent commit), where this model's cell fails at once.
"""
import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.models import lfm2_moe as lfm2
from chipbench.models.lfm2_moe import (  # noqa: F401  (the driver's API)
    EXPERT_BIAS_STD, make_batches, tokens_per_step)
from chipbench.reference import solar_open2 as reference
from chipbench.weights import seed_key

reference_steps = reference.train_steps
tree_norms = reference.tree_norms

# the public implementation's ranges: A_log = log U(1, 16) a head, dt_bias
# the inverse softplus of a step drawn log-uniformly from [0.001, 0.1]
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


# ------------------------------------------------------------ the program
def build(cfg, traffic, fluid):
    from paddle_tpu.models import solar_open2 as solar
    model = solar.SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], layer_types=cfg["layer_types"],
        linear_attn_config=cfg["linear_attn_config"],
        gate_rank=cfg["gate_rank"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        use_expert_bias=cfg["use_expert_bias"],
        rms_norm_eps=cfg["rms_norm_eps"], use_rope=cfg["use_rope"],
        use_gqa_gate=cfg["use_gqa_gate"],
        kda_use_full_proj=cfg["kda_use_full_proj"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        heads_held=cfg["heads_held"], kv_heads_held=cfg["kv_heads_held"],
        first_head=cfg["first_head"], experts_held=cfg["experts_held"],
        first_expert=cfg["first_expert"])
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            _feeds, loss = solar.build_program(model, traffic["length"])
            fluid.optimizer.Adam(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["epsilon"]).minimize(loss)
    return main, startup, loss


def _linear(cfg):
    """(heads held, head size, taps, gate rank) of the kda mixers."""
    lin = cfg["linear_attn_config"]
    return (cfg["heads_held"], lin["head_dim"],
            lin["short_conv_kernel_size"], cfg["gate_rank"])


def param_specs(cfg):
    """[(name, shape, kind)] in the order the program declares them."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv, D = cfg["heads_held"], cfg["kv_heads_held"], cfg["head_dim"]
    lh, LD, K, R = _linear(cfg)
    E, F = cfg["experts_held"], cfg["moe_intermediate_size"]
    specs = [("embed.w_0", (V, H), "matrix")]
    for i, kind in enumerate(cfg["layer_types"]):
        n = f"l{i}"
        specs.append((f"{n}_mixer_norm.w_0", (H,), "norm"))
        if kind == "gqa":
            specs += [(f"{n}_q.w_0", (H, nh * D), "matrix"),
                      (f"{n}_k.w_0", (H, kv * D), "matrix"),
                      (f"{n}_v.w_0", (H, kv * D), "matrix"),
                      (f"{n}_g.w_0", (H, nh * D), "matrix"),
                      (f"{n}_o.w_0", (nh * D, H), "matrix")]
        else:
            for which in "qkv":
                specs += [(f"{n}_{which}.w_0", (H, lh * LD), "matrix"),
                          (f"{n}_{which}_conv.w_0", (lh * LD, K), "filter")]
            specs += [(f"{n}_a_down.w_0", (H, R), "matrix"),
                      (f"{n}_a_up.w_0", (R, lh * LD), "matrix"),
                      (f"{n}_decay.w_0", (lh,), "a_log"),
                      (f"{n}_decay.w_1", (lh, LD), "dt_bias"),
                      (f"{n}_b.w_0", (H, lh), "matrix"),
                      (f"{n}_o_norm.w_0", (LD,), "norm"),
                      (f"{n}_g_down.w_0", (H, R), "matrix"),
                      (f"{n}_g_up.w_0", (R, lh * LD), "matrix"),
                      (f"{n}_o.w_0", (lh * LD, H), "matrix")]
        specs += [(f"{n}_ffn_norm.w_0", (H,), "norm"),
                  (f"{n}_router.w_0", (H, cfg["n_routed_experts"]),
                   "router"),
                  (f"{n}_experts.w_0", (E, H, F), "matrix"),
                  (f"{n}_experts.w_1", (E, H, F), "matrix"),
                  (f"{n}_experts.w_2", (E, F, H), "matrix")]
        if cfg["n_shared_experts"]:
            S = cfg["n_shared_experts"] * F
            specs += [(f"{n}_shared_w1.w_0", (H, S), "matrix"),
                      (f"{n}_shared_w3.w_0", (H, S), "matrix"),
                      (f"{n}_shared_w2.w_0", (S, H), "matrix")]
    specs += [("final_norm.w_0", (H,), "norm"),
              ("lm_head.w_0", (H, V), "matrix")]
    return specs


def bias_names(cfg):
    """The expert biases: persistable variables of the program that are no
    Parameters (no gradient, no Adam state)."""
    if not cfg["use_expert_bias"]:
        return []
    return [f"l{i}_router.bias" for i in range(len(cfg["layer_types"]))]


def make_params(cfg, seed, dtype):
    """{name: array} on the default device, one jitted call: every
    parameter of `param_specs` and the expert biases. Matrices (embedding,
    head and experts too) N(0, 0.02) in `dtype`; RMSNorm weights 1 and the
    router's weight N(0, 0.02), float32; a convolution's taps uniform in
    +-1/sqrt(K) (torch's default for a depthwise Conv1d); A_log = log U(1,
    16) and dt_bias the inverse softplus of a step drawn log-uniformly
    from [0.001, 0.1], float32; the expert bias N(0, EXPERT_BIAS_STD) over
    all `n_routed_experts`, float32."""
    specs = param_specs(cfg)
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "norm":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "filter":
                lim = shape[1] ** -0.5
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -lim, lim).astype(dtype)
            elif kind == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, *A_RANGE))
            elif kind == "dt_bias":
                lo, hi = jnp.log(DT_RANGE[0]), jnp.log(DT_RANGE[1])
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo,
                                                hi))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                w = jax.random.normal(k, shape, jnp.float32) * 0.02
                out[name] = w if kind == "router" else w.astype(dtype)
        for j, name in enumerate(bias_names(cfg)):
            k = jax.random.fold_in(key, len(specs) + j)
            out[name] = jax.random.normal(
                k, (cfg["n_routed_experts"],), jnp.float32) * EXPERT_BIAS_STD
        return out

    return jax.jit(make)(seed_key(seed))


# -------------------------------------------------------------- the counts
def forward_flops_per_token(cfg, length):
    """Needed FLOPs of one token's forward pass, 2 a multiply-add, matrix
    products, attention and the recurrence (counts.py's rule). Attention
    counts the causal half. The recurrence counts its three products with
    the state a head (S k, the rank-one update, S q: 6 D^2) and not the
    decay. The experts count the EXPECTED pairs of a token on this rank,
    `num_experts_per_tok * experts_held / n_routed_experts` (0.2 in the
    benchmark's cut); the shared expert sees every token."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv, D = cfg["heads_held"], cfg["kv_heads_held"], cfg["head_dim"]
    lh, LD, _, R = _linear(cfg)
    F = cfg["moe_intermediate_size"]
    gqa = 2 * H * (2 * nh + 2 * kv) * D + 2 * nh * D * H \
        + 4 * length * nh * D // 2
    kda = 4 * 2 * H * lh * LD + 2 * (2 * H * R + 2 * R * lh * LD) \
        + 2 * H * lh + 6 * lh * LD * LD
    pairs = cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["n_routed_experts"]
    ffn = 2 * H * cfg["n_routed_experts"] \
        + (pairs + cfg["n_shared_experts"]) * 6 * H * F
    total = 2 * H * V
    for kind in cfg["layer_types"]:
        total += (gqa if kind == "gqa" else kda) + ffn
    return total


def step_flops(cfg, traffic):
    """Forward and backward (twice the forward), no recomputation."""
    return 3 * tokens_per_step(traffic) * forward_flops_per_token(
        cfg, traffic["length"])


def attention_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of one causal grouped-query attention over
    the batch, as the algorithm needs them whatever implements it: a
    product is 2 B H T S D / 2 (the causal half). Forward two of them, q
    and out at the held query heads and k, v at the held key-value heads
    once each. Backward five (the scores again, dp, dv, dk, dq), reading
    q, k, v, out, dout and writing dq, dk, dv; where the dq of a
    key-value head's query heads does not stay resident the two kernels
    `_dq` (three products) and `_dkv` (four) run instead, and the trace
    shows which."""
    B, T = traffic["rows"], traffic["length"]
    nh, kv, D = cfg["heads_held"], cfg["kv_heads_held"], cfg["head_dim"]
    size = counts.ITEMSIZE[cfg["precision"]["activations"]]
    product = 2 * B * nh * T * T * D // 2
    q_bytes, kv_bytes = B * T * nh * D * size, B * T * kv * D * size
    return {"flash_attention_fwd": (2 * product, 2 * q_bytes + 2 * kv_bytes),
            "flash_attention_bwd": (5 * product, 4 * q_bytes + 4 * kv_bytes),
            "flash_attention_dq": (3 * product, 4 * q_bytes + 2 * kv_bytes),
            "flash_attention_dkv": (4 * product, 3 * q_bytes + 4 * kv_bytes)}


def _as_lfm2(cfg):
    """This configuration under the keys `models/lfm2_moe.py` counts an
    expert layer by: the same router and experts, every layer sparse."""
    return dict(cfg, num_experts=cfg["n_routed_experts"], num_dense_layers=0)


def local_pairs_per_layer(cfg, traffic):
    """Pairs (token, held expert) a step and expert layer: what the
    program counted, else the expected number."""
    return lfm2.local_pairs_per_layer(_as_lfm2(cfg), traffic)


def expert_work(cfg, traffic):
    """{kernel: [(FLOPs, bytes)]} of one expert layer's grouped products,
    one entry a call, as `models/lfm2_moe.py` counts them (a pair's
    product with one [H, F] matrix is 2 H F FLOPs; the rows of the pairs
    in and out, the held experts' matrices once a call), and of
    `moe_combine`, which moves rows and multiplies nothing by a matrix:
    the pairs' rows in and every token's row out, forward a multiply-add
    an element with the routing weights (k places and k float32 a token),
    backward an add with the places alone."""
    H = cfg["hidden_size"]
    size = counts.ITEMSIZE[cfg["precision"]["activations"]]
    P = local_pairs_per_layer(cfg, traffic)
    N, k = tokens_per_step(traffic), cfg["num_experts_per_tok"]
    rows = P * H * size + N * H * size
    return dict(lfm2.expert_work(_as_lfm2(cfg), traffic),
                moe_combine=[(2 * P * H, rows + N * k * 8),
                             (P * H, rows + N * k * 4)])


def kernel_work(cfg, traffic):
    """{kernel: [(FLOPs, bytes, calls)]} a step, for `<kernel>_roofline`."""
    n_gqa = sum(kind == "gqa" for kind in cfg["layer_types"])
    work = {k: [v + (n_gqa,)] for k, v in
            attention_work(cfg, traffic).items()}
    for k, calls in expert_work(cfg, traffic).items():
        work[k] = [c + (len(cfg["layer_types"]),) for c in calls]
    return work
