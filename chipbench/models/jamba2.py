"""Model `jamba2`: Jamba 2 (Mamba-1 mixers with a NoPE attention layer a
period and a dense SwiGLU after every mixer) as one tensor-parallel rank
of four trains it, for the training driver (`entries/train.py`, which
finds this file through the configuration's `model` key). What a model
file says is listed in `models/nmt.py`.

The configuration is the published `config.json` cut to one chip's share
(PERF.md section 4): `channels_held` of a mamba mixer's inner channels
from `first_channel` on, `heads_held` of the query heads from `first_head`
on (the one key-value head whole), `intermediate_held` of the MLP's width,
`vocab_size` rows of the tied vocabulary, and `num_hidden_layers` layers of
the published pattern (attention where i % attn_layer_period ==
attn_layer_offset).

Nothing of the program is imported until `build` is called, so the
benchmark's other cells load this file on a program that has no such
model (a parent commit), where this model's cell fails at once.
"""
import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.models.lfm2_moe import (  # noqa: F401  (the driver's API)
    make_batches, tokens_per_step)
from chipbench.reference import jamba2 as reference
from chipbench.weights import seed_key

tree_norms = reference.tree_norms

# the public implementation's range of the first steps: b_dt is the inverse
# softplus of a step drawn log-uniformly from it
DT_RANGE = (0.001, 0.1)


def layer_types(cfg):
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def reference_steps(params, cfg, batches, opt, prec="float32", block_rows=1,
                    rows=None):
    """reference/jamba2.py's steps, handed the layer pattern."""
    return reference.train_steps(params, dict(cfg, layer_types=layer_types(
        cfg)), batches, opt, prec, block_rows, rows)


# ------------------------------------------------------------ the program
def build(cfg, traffic, fluid):
    from paddle_tpu.models import jamba2
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "attn_layer_period", "attn_layer_offset", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "mamba_conv_bias", "mamba_proj_bias", "num_experts",
            "tie_word_embeddings", "rms_norm_eps", "channels_held",
            "first_channel", "heads_held", "first_head", "intermediate_held")
    model = jamba2.Jamba2Config(**{k: cfg[k] for k in keys})
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            _feeds, loss = jamba2.build_program(model, traffic["length"])
            fluid.optimizer.Adam(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["epsilon"]).minimize(loss)
    return main, startup, loss


def _sizes(cfg):
    """(hidden, inner channels held, states, dt rank, taps, query heads
    held, key-value heads, head size, MLP width held, vocabulary rows)."""
    H = cfg["hidden_size"]
    return (H, cfg["channels_held"], cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"], cfg["heads_held"],
            cfg["num_key_value_heads"], H // cfg["num_attention_heads"],
            cfg["intermediate_held"], cfg["vocab_size"])


def param_specs(cfg):
    """[(name, shape, kind)] in the order the program declares them."""
    H, Ch, N, R, K, nh, kv, D, F, V = _sizes(cfg)
    specs = [("embed.w_0", (V, H), "matrix")]
    for i, kind in enumerate(layer_types(cfg)):
        n = f"l{i}"
        specs.append((f"{n}_mixer_norm.w_0", (H,), "norm"))
        if kind == "mamba":
            specs += [(f"{n}_in.w_0", (H, 2 * Ch), "matrix"),
                      (f"{n}_conv.w_0", (Ch, K), "matrix"),
                      (f"{n}_conv.b_0", (Ch,), "conv_bias"),
                      (f"{n}_x.w_0", (Ch, R + 2 * N), "matrix"),
                      (f"{n}_dt_norm.w_0", (R,), "norm"),
                      (f"{n}_b_norm.w_0", (N,), "norm"),
                      (f"{n}_c_norm.w_0", (N,), "norm"),
                      (f"{n}_dt.w_0", (R, Ch), "dt_proj"),
                      (f"{n}_dt.b_0", (Ch,), "dt_bias"),
                      (f"{n}_scan.w_0", (Ch, N), "a_log"),
                      (f"{n}_scan.w_1", (Ch,), "d"),
                      (f"{n}_out.w_0", (Ch, H), "matrix")]
        else:
            specs += [(f"{n}_q.w_0", (H, nh * D), "matrix"),
                      (f"{n}_k.w_0", (H, kv * D), "matrix"),
                      (f"{n}_v.w_0", (H, kv * D), "matrix"),
                      (f"{n}_o.w_0", (nh * D, H), "matrix")]
        specs += [(f"{n}_mlp_norm.w_0", (H,), "norm"),
                  (f"{n}_mlp_gate.w_0", (H, F), "matrix"),
                  (f"{n}_mlp_up.w_0", (H, F), "matrix"),
                  (f"{n}_mlp_down.w_0", (F, H), "matrix")]
    specs.append(("final_norm.w_0", (H,), "norm"))
    return specs


def bias_names(cfg):
    """No persistable variable beside the parameters (no router)."""
    return []


def make_params(cfg, seed, dtype):
    """{name: array} on the default device, one jitted call. Matrices (the
    embedding, the conv's taps) N(0, 0.02) in `dtype`, W_dt U(+-dt_rank^-0.5)
    in `dtype`, the conv bias 0 in `dtype`; float32: RMSNorm weights 1,
    A_log = log(1 .. N) on every channel, D = 1 (the Mamba initialisation)
    and b_dt the inverse softplus of a step drawn log-uniformly from
    DT_RANGE."""
    specs = param_specs(cfg)
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind in ("norm", "d"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "conv_bias":
                out[name] = jnp.zeros(shape, dtype)
            elif kind == "a_log":
                out[name] = jnp.log(jnp.broadcast_to(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32), shape))
            elif kind == "dt_bias":
                lo, hi = jnp.log(DT_RANGE[0]), jnp.log(DT_RANGE[1])
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo,
                                                hi))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "dt_proj":
                lim = shape[0] ** -0.5
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -lim, lim).astype(dtype)
            else:
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * 0.02).astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))


# -------------------------------------------------------------- the counts
def scan_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of one selective scan over the batch, as
    the algorithm needs them whatever implements it. Forward: 6 FLOP a
    (token, channel, state) (dt a, its exponential, the decay times the
    state, B times dt x, the sum over the states with C: a multiply-add)
    and 3 a (token, channel) (dt x, D x and its add); bytes x, dt, B, C and
    y once, A_log and D. Backward: twice the forward's FLOPs (each product
    gives two); bytes x, dt, B, C, dy read and dx, ddt, dB, dC written
    once, A_log and D read and their gradients written. x, y, B, C and
    their gradients at the activations' size, dt and ddt float32 (the
    configuration keeps dt float32)."""
    B, T = traffic["rows"], traffic["length"]
    _, Ch, N, *_ = _sizes(cfg)
    act = counts.ITEMSIZE[cfg["precision"]["activations"]]
    tc, tn = B * T * Ch, B * T * N
    flops = 6 * tc * N + 3 * tc
    params = 4 * (Ch * N + Ch)
    return {"selective_scan_fwd": (flops, tc * (2 * act + 4)
                                   + 2 * tn * act + params),
            "selective_scan_bwd": (2 * flops, tc * (3 * act + 8)
                                   + 4 * tn * act + 2 * params)}


def forward_flops_per_token(cfg, length):
    """Needed FLOPs of one token's forward pass, 2 a multiply-add: matrix
    products, attention (the causal half) and the selective scan (what
    `scan_work` counts a token: 6 a channel and state, 3 a channel)."""
    H, Ch, N, R, _, nh, kv, D, F, V = _sizes(cfg)
    mamba = 2 * H * 2 * Ch + 2 * Ch * (R + 2 * N) + 2 * R * Ch \
        + 2 * Ch * H + 6 * Ch * N + 3 * Ch
    attn = 2 * H * (nh + 2 * kv) * D + 2 * nh * D * H \
        + 4 * length * nh * D // 2
    total = 2 * H * V
    for kind in layer_types(cfg):
        total += (mamba if kind == "mamba" else attn) + 6 * H * F
    return total


def step_flops(cfg, traffic):
    """Forward and backward (twice the forward), no recomputation."""
    return 3 * tokens_per_step(traffic) * forward_flops_per_token(
        cfg, traffic["length"])


def kernel_work(cfg, traffic):
    """{kernel: [(FLOPs, bytes, calls)]} a step, for `<kernel>_roofline`:
    the two scan kernels, once a mamba layer. The one attention layer is
    left uncounted (the head-128 attention kernels have their rooflines in
    other cells)."""
    n = layer_types(cfg).count("mamba")
    return {k: [v + (n,)] for k, v in scan_work(cfg, traffic).items()}
