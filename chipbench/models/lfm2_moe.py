"""Model `lfm2_moe`: LFM2-MoE (gated short convolutions, grouped-query
attention, a no-drop top-k router over sparse experts) as one
expert-parallel rank trains it, for the training driver
(`entries/train.py`, which finds this file through the configuration's
`model` key). What a model file says is listed in `models/nmt.py`.

The configuration is the published `config.json` cut to one chip's share
(PERF.md section 4): `experts_held` experts of every layer from
`first_expert` on, `vocab_size` rows of the vocabulary, and the layers of
`layer_types`. The router keeps its `num_experts` outputs.

Nothing of the program is imported until `build` is called, so the
benchmark's other cells load this file on a program that has no such
model (a parent commit), where this model's cell fails at once.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts
from chipbench.reference import lfm2_moe as reference
from chipbench.weights import seed_key

reference_steps = reference.train_steps
tree_norms = reference.tree_norms

# the expert bias: drawn once from the seed and held fixed, so that the
# held experts' load is uneven as a trained router's is (PERF.md section 4)
EXPERT_BIAS_STD = 0.05


# ------------------------------------------------------------ the program
def build(cfg, traffic, fluid):
    from paddle_tpu.models import lfm2_moe as lfm2
    model = lfm2.Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        layer_types=cfg["layer_types"],
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"], norm_eps=cfg["norm_eps"],
        rope_theta=reference.rope_theta(cfg),
        norm_topk_prob=cfg["norm_topk_prob"],
        use_expert_bias=cfg["use_expert_bias"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=cfg["experts_held"], first_expert=cfg["first_expert"])
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            _feeds, loss = lfm2.build_program(model, traffic["length"])
            fluid.optimizer.Adam(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["epsilon"]).minimize(loss)
    return main, startup, loss


def _moe_layers(cfg):
    return len(cfg["layer_types"]) - cfg["num_dense_layers"]


def param_specs(cfg):
    """[(name, shape, kind)] in the order the program declares them."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = H // nh
    specs = [("embed.w_0", (V, H), "matrix")]
    for i, kind in enumerate(cfg["layer_types"]):
        n = f"l{i}"
        specs.append((f"{n}_operator_norm.w_0", (H,), "norm"))
        if kind == "conv":
            specs += [(f"{n}_conv_in.w_0", (H, 3 * H), "matrix"),
                      (f"{n}_conv.w_0", (H, cfg["conv_L_cache"]), "filter"),
                      (f"{n}_conv_out.w_0", (H, H), "matrix")]
        else:
            specs += [(f"{n}_q.w_0", (H, nh * D), "matrix"),
                      (f"{n}_q_norm.w_0", (D,), "norm"),
                      (f"{n}_k.w_0", (H, kv * D), "matrix"),
                      (f"{n}_k_norm.w_0", (D,), "norm"),
                      (f"{n}_v.w_0", (H, kv * D), "matrix"),
                      (f"{n}_o.w_0", (nh * D, H), "matrix")]
        specs.append((f"{n}_ffn_norm.w_0", (H,), "norm"))
        if i < cfg["num_dense_layers"]:
            F = cfg["intermediate_size"]
            specs += [(f"{n}_ffn_w1.w_0", (H, F), "matrix"),
                      (f"{n}_ffn_w3.w_0", (H, F), "matrix"),
                      (f"{n}_ffn_w2.w_0", (F, H), "matrix")]
        else:
            E, F = cfg["experts_held"], cfg["moe_intermediate_size"]
            specs += [(f"{n}_router.w_0", (H, cfg["num_experts"]), "router"),
                      (f"{n}_experts.w_0", (E, H, F), "matrix"),
                      (f"{n}_experts.w_1", (E, H, F), "matrix"),
                      (f"{n}_experts.w_2", (E, F, H), "matrix")]
    specs.append(("final_norm.w_0", (H,), "norm"))
    return specs


def bias_names(cfg):
    """The expert biases: persistable variables of the program that are no
    Parameters (no gradient, no Adam state)."""
    if not cfg["use_expert_bias"]:
        return []
    return [f"l{i}_router.bias" for i in range(len(cfg["layer_types"]))
            if i >= cfg["num_dense_layers"]]


def make_params(cfg, seed, dtype):
    """{name: array} on the default device, one jitted call: every
    parameter of `param_specs` and the expert biases. Matrices (embedding
    and experts too) N(0, 0.02) in `dtype`; RMSNorm weights 1 and the
    router's weight N(0, 0.02), float32; the convolution's taps uniform in
    +-1/sqrt(K) (torch's default for a depthwise Conv1d); the expert bias
    N(0, EXPERT_BIAS_STD) over all `num_experts`, float32."""
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
            else:
                w = jax.random.normal(k, shape, jnp.float32) * 0.02
                out[name] = w if kind == "router" else w.astype(dtype)
        for j, name in enumerate(bias_names(cfg)):
            k = jax.random.fold_in(key, len(specs) + j)
            out[name] = jax.random.normal(
                k, (cfg["num_experts"],), jnp.float32) * EXPERT_BIAS_STD
        return out

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------ the traffic
def make_batches(traffic, cfg, seed):
    """`pool` feeds {ids, labels} of `rows` x `length` ids, cut from ONE
    stream one after another: no padding, no document mask, the label the
    next id of the stream. The stream is a walk over the vocabulary slice
    (the next id is the last plus one of 64 seeded steps), so a model can
    learn it."""
    if traffic["kind"] != "lm_stream_batches":
        raise ValueError(f"lfm2_moe reads lm_stream_batches, not "
                         f"{traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 30])
    R, T, V = traffic["rows"], traffic["length"], cfg["vocab_size"]
    n = traffic["pool"] * R * T + 1
    steps = rng.integers(1, V, 64)[rng.integers(0, 64, n)]
    steps[0] = rng.integers(0, V)
    stream = np.cumsum(steps) % V
    out = []
    for b in range(traffic["pool"]):
        lo = b * R * T
        ids = stream[lo:lo + R * T].reshape(R, T)
        nxt = stream[lo + 1:lo + R * T + 1].reshape(R, T)
        out.append({"ids": ids.astype("int64"), "labels": nxt.astype("int64")})
    return out


def tokens_per_step(traffic):
    return traffic["rows"] * traffic["length"]


# -------------------------------------------------------------- the counts
def forward_flops_per_token(cfg, length):
    """Needed FLOPs of one token's forward pass, 2 a multiply-add, matrix
    products and attention only (counts.py's rule). Attention counts the
    causal half. The experts count the EXPECTED pairs of a token on this
    rank, `num_experts_per_tok * experts_held / num_experts` (0.5 in the
    benchmark's cut): what a uniform router sends here; the bias moves
    the real number a little (`moe_local_pairs_per_step`)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = H // nh
    conv = 2 * H * 3 * H + 2 * H * H
    attn = 2 * H * (nh + 2 * kv) * D + 2 * nh * D * H \
        + 4 * length * nh * D // 2
    dense = 6 * H * cfg["intermediate_size"]
    pairs = cfg["num_experts_per_tok"] * cfg["experts_held"] \
        / cfg["num_experts"]
    moe = 2 * H * cfg["num_experts"] \
        + pairs * 6 * H * cfg["moe_intermediate_size"]
    total = 2 * H * V
    for i, kind in enumerate(cfg["layer_types"]):
        total += conv if kind == "conv" else attn
        total += dense if i < cfg["num_dense_layers"] else moe
    return total


def step_flops(cfg, traffic):
    """Forward and backward (twice the forward), no recomputation."""
    return 3 * tokens_per_step(traffic) * forward_flops_per_token(
        cfg, traffic["length"])


def attention_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of one causal grouped-query attention over
    the batch, as the algorithm needs them whatever implements it: a
    product is 2 B H T S D / 2 (the causal half); forward two of them, q
    and out at H heads and k, v at KVH heads once each; backward, the one
    kernel `flash_attention_bwd`, five (the scores again, dp, dv, dk, dq)
    reading q, k, v, out, dout and writing dq, dk, dv."""
    B, T = traffic["rows"], traffic["length"]
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // nh
    size = counts.ITEMSIZE[cfg["precision"]["activations"]]
    product = 2 * B * nh * T * T * D // 2
    q_bytes, kv_bytes = B * T * nh * D * size, B * T * kv * D * size
    return {"flash_attention_fwd": (2 * product, 2 * q_bytes + 2 * kv_bytes),
            "flash_attention_bwd": (5 * product, 4 * q_bytes + 4 * kv_bytes)}


def local_pairs_per_layer(cfg, traffic):
    """Pairs (token, held expert) a step and expert layer: what the
    program counted (`moe.local_pairs` over `moe.steps`, summed over the
    layers), else the expected number."""
    try:
        from paddle_tpu import telemetry
        snap = telemetry.snapshot()
        return snap["moe.local_pairs"] / snap["moe.steps"] / _moe_layers(cfg)
    except (ImportError, KeyError, ZeroDivisionError):
        return tokens_per_step(traffic) * cfg["num_experts_per_tok"] \
            * cfg["experts_held"] / cfg["num_experts"]


def expert_work(cfg, traffic):
    """{kernel: [(FLOPs, bytes)]} of one expert layer's grouped products,
    one entry a call, for the pairs routed here (padding rows of the
    sorted buffer are no work). A pair's product with one [H, F] matrix
    is 2 H F FLOPs: 6 H F forward (W1, W3, W2), 12 H F backward; the
    backward's second pass over W1, W3 (`moe_swiglu_bwd` recomputes the
    gate's inputs and keeps none) is needed work of THAT kernel and no
    needed work of the step (`step_flops` does not count it). Bytes: the
    rows of the pairs in and out, and the held experts' matrices once."""
    H, F, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["experts_held"])
    size = counts.ITEMSIZE[cfg["precision"]["activations"]]
    P = local_pairs_per_layer(cfg, traffic)
    mm = 2 * H * F * P
    rows_h, rows_f, mat = P * H * size, P * F * size, E * H * F * size
    lane = P * 128 * 4          # a [rows, 128] float32 column of weights
    return {
        "moe_gmm_swiglu": [(2 * mm, rows_h + rows_f + 2 * mat)],
        "moe_gmm": [(mm, rows_f + rows_h + mat),          # y = g W2
                    (mm, rows_h + rows_f + mat),          # dg = dy W2^T
                    (2 * mm, 2 * rows_f + rows_h + 2 * mat)],   # dxs
        "moe_swiglu_bwd": [(2 * mm, rows_h + 4 * rows_f + 2 * mat
                            + 2 * lane)],
        "moe_tgmm": [(mm, rows_h + rows_f + mat)] * 3}


def kernel_work(cfg, traffic):
    """{kernel: [(FLOPs, bytes, calls)]} a step, for `<kernel>_roofline`."""
    n_attn = sum(kind != "conv" for kind in cfg["layer_types"])
    work = {k: [v + (n_attn,)] for k, v in
            attention_work(cfg, traffic).items()}
    for k, calls in expert_work(cfg, traffic).items():
        work[k] = [c + (_moe_layers(cfg),) for c in calls]
    return work
