"""Plain Solar Open 2 (upstage, `model_type: solar_open2`), float32 jax.numpy.

The benchmark's yardstick for `correct` in the `solar_open2` cells. It
imports nothing of the program and takes nothing the program made: the
parameters and the batches come from `chipbench/models/solar_open2.py`.
Written from the layer equations (ISSUE 34: the public config, and for
the `kda_*` keys the gated delta rule with a per-channel decay of Kimi
Linear, arXiv:2510.26692, and its public implementation):

    block:  h = h + mixer_i(rms(h));  h = h + ffn(rms(h))
            rms(x) = x * rsqrt(mean(x^2) + eps) * w, in float32
            mixer_i = gqa where layer_types[i] == "gqa", else kda
    gqa:    q = h W_q [H heads x D];  k = h W_k, v = h W_v [KVH heads x D];
            no positions, no q/k norm; query head h reads key-value head
            h // (H / KVH); a = causal softmax(q k^T / sqrt(D)) v
            y = (sigmoid(h W_g) * a) W_o          one gate an output element
    kda:    per head, conv a causal depthwise filter of K taps (as
            reference/lfm2_moe.py), l2(x) = x / sqrt(sum x^2 + 1e-6):
            q_t = l2(silu(conv(h W_q)))_t, k_t = l2(silu(conv(h W_k)))_t,
            v_t = silu(conv(h W_v))_t
            g_t = -exp(A_log) * softplus(W_a_up (W_a_down h_t) + dt_bias),
                  in R^D per channel, float32, <= 0;  A_log one a head
            beta_t = 2 sigmoid(h_t . w_b)         one a head, in (0, 2)
            S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1}
                  + beta_t k_t v_t^T              S_0 = 0, [D, D], float32
            o_t = S_t^T q_t / sqrt(D)
            y = (sigmoid(W_g_up (W_g_down h)) * rms_head(o)) W_o
            The recurrence is walked TOKEN BY TOKEN (`lax.scan` over t,
            rematerialised every `SCAN_BLOCK` steps so that its backward
            keeps one state a block and not one a token): the definition,
            not the chunked algebra the program runs.
    ffn:    s = sigmoid(h W_r) over ALL experts, float32; chosen = top-k
            of (s + bias); w = s[chosen] / (sum s[chosen] + 1e-6) * scaling
            ffn(h) = shared(h) + sum over the chosen experts THAT ARE HELD
            HERE of w_e expert_e(h);  shared, expert_e: SwiGLU
    model:  embedding -> blocks -> rms -> logits = h W_head (untied)
    loss:   mean next-token cross-entropy over every position

The share of a deployment: the parameters ARE the share (`heads_held`
query heads over `kv_heads_held` key-value heads, `experts_held` experts
from `first_expert` on, `vocab_size` rows); what the absent heads and
experts would add is left out, here as in the program.

Departures, because the numbers compared depend on them: the expert bias
is a constant (it only selects); no auxiliary loss; Adam in Kingma & Ba's
efficient form (as reference/nmt.py).

`prec` is reference/lfm2_moe.py's: "float32" (the reference), "bfloat16"
(what the configuration states), "int8" (the control). RMSNorm, the
router, the softmax, the loss, the log-decay g and the recurrence's state
and products are float32 in all three, as the configuration states them.

Memory at the benchmark's size (840.5M parameters, 8192 positions): the
parameters and the gradient in float32 are 3.4 GB each beside the
caller's bfloat16 copy (1.7 GB), so Adam's two moments (6.7 GB) live on
the HOST between the steps and pass through the device a leaf at a time;
a layer is recomputed in the backward pass (`jax.checkpoint`), and the
attention walks its queries in blocks as reference/lfm2_moe.py does.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lfm2_moe import (  # noqa: F401  (tree_norms: API)
    HI, Q_BLOCK, _act, _attend_block, _ein, _swiglu, dwconv_causal,
    expert_share, rms_norm, route, tree_norms)

SCAN_BLOCK = 64


def l2_norm(x, eps=1e-6):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)


def delta_rule(q, k, v, g, beta):
    """q, k, g [B, T, H, D], v [B, T, H, Dv], beta [B, T, H] -> o [B, T, H,
    Dv]: the gated delta rule, one token after another, float32."""
    B, T, H, D = q.shape
    scale = jnp.float32(D ** -0.5)

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        r = vt - jnp.einsum("bhkv,bhk->bhv", S, kt, precision=HI)
        S = S + (bt[..., None] * kt)[..., None] * r[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=HI) * scale

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    pad = (-T) % SCAN_BLOCK          # g = 0, beta = 0: the state stands still

    def blocks(x):
        x = jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, SCAN_BLOCK) + x.shape[1:])

    S0 = jnp.zeros((B, H, D, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block, S0, tuple(blocks(x)
                                         for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:])[:T], 0, 1)


def _proj(p, name, x, prec):
    return _act(_ein("bth,hc->btc", x, p[f"{name}.w_0"], prec), prec)


def kda_mixer(p, name, x, cfg, prec):
    B, T, _ = x.shape
    H, D = cfg["heads_held"], cfg["linear_attn_config"]["head_dim"]

    def heads(y):
        return y.reshape(B, T, H, D)

    def mixed(which):
        y = _act(dwconv_causal(_proj(p, f"{name}_{which}", x, prec),
                               p[f"{name}_{which}_conv.w_0"]), prec)
        return heads(_act(jax.nn.silu(y), prec))

    q, k, v = _act(l2_norm(mixed("q")), prec), \
        _act(l2_norm(mixed("k")), prec), mixed("v")
    a = heads(_proj(p, f"{name}_a_up", _proj(p, f"{name}_a_down", x, prec),
                    prec))
    g = -jnp.exp(p[f"{name}_decay.w_0"])[:, None] * jax.nn.softplus(
        a.astype(jnp.float32) + p[f"{name}_decay.w_1"])
    beta = _act(2.0 * _act(jax.nn.sigmoid(_proj(p, f"{name}_b", x, prec)),
                           prec), prec)
    o = _act(delta_rule(q, k, v, g, beta), prec)
    o = _act(rms_norm(o, p[f"{name}_o_norm.w_0"], cfg["rms_norm_eps"]), prec)
    gate = _act(jax.nn.sigmoid(_proj(
        p, f"{name}_g_up", _proj(p, f"{name}_g_down", x, prec), prec)), prec)
    return _ein("bth,hc->btc", _act(gate * o.reshape(B, T, H * D), prec),
                p[f"{name}_o.w_0"], prec)


def gqa_mixer(p, name, x, cfg, prec):
    B, T, _ = x.shape
    H, KV, D = cfg["heads_held"], cfg["kv_heads_held"], cfg["head_dim"]
    q = _proj(p, f"{name}_q", x, prec).reshape(B, T, KV, H // KV, D)
    k = _proj(p, f"{name}_k", x, prec).reshape(B, T, KV, D)
    v = _proj(p, f"{name}_v", x, prec).reshape(B, T, KV, D)
    block = jax.checkpoint(_attend_block, static_argnums=(3, 4))
    outs = [block(q[:, lo:lo + Q_BLOCK], k[:, :lo + Q_BLOCK],
                  v[:, :lo + Q_BLOCK], lo, prec)
            for lo in range(0, T, Q_BLOCK)]
    out = _act(jnp.concatenate(outs, axis=1), prec).reshape(B, T, H * D)
    gate = _act(jax.nn.sigmoid(_proj(p, f"{name}_g", x, prec)), prec)
    return _ein("bth,hc->btc", _act(gate * out, prec), p[f"{name}_o.w_0"],
                prec)


def ffn(p, name, x, cfg, prec):
    bias = p.get(f"{name}_router.bias") if cfg["use_expert_bias"] else None
    chosen, w = route(x, p[f"{name}_router.w_0"], bias,
                      cfg["num_experts_per_tok"], cfg["norm_topk_prob"],
                      float(cfg["routed_scaling_factor"]))
    y = expert_share(x, chosen, w, p[f"{name}_experts.w_0"],
                     p[f"{name}_experts.w_1"], p[f"{name}_experts.w_2"],
                     cfg.get("first_expert", 0), prec)
    if cfg["n_shared_experts"]:
        y = _act(y, prec) + _act(_swiglu(
            x, p[f"{name}_shared_w1.w_0"], p[f"{name}_shared_w3.w_0"],
            p[f"{name}_shared_w2.w_0"], prec), prec)
    return y


def _layer(p, h, i, kind, cfg, prec):
    name, eps = f"l{i}", cfg["rms_norm_eps"]
    x = _act(rms_norm(h, p[f"{name}_mixer_norm.w_0"], eps), prec)
    mixer = gqa_mixer if kind == "gqa" else kda_mixer
    h = _act(h + _act(mixer(p, name, x, cfg, prec), prec), prec)
    x = _act(rms_norm(h, p[f"{name}_ffn_norm.w_0"], eps), prec)
    return _act(h + _act(ffn(p, name, x, cfg, prec), prec), prec)


def forward(p, cfg, ids, prec="float32"):
    """Logits [B, T, V] (float32) of the next id at every position."""
    h = _act(jnp.take(p["embed.w_0"], ids, axis=0).astype(jnp.float32), prec)
    for i, kind in enumerate(cfg["layer_types"]):
        layer = jax.checkpoint(
            functools.partial(_layer, i=i, kind=kind, cfg=cfg, prec=prec))
        h = layer({k: v for k, v in p.items()
                   if k.startswith(f"l{i}_")}, h)
    h = _act(rms_norm(h, p["final_norm.w_0"], cfg["rms_norm_eps"]), prec)
    return _ein("bth,hv->btv", h, p["lm_head.w_0"], prec).astype(jnp.float32)


def loss_sum(p, consts, cfg, batch, prec="float32"):
    """Sum over every position of the next-token cross-entropy. `consts`:
    what the model holds and does not train (the expert bias)."""
    logits = forward({**p, **consts}, cfg, batch["ids"], prec)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked[..., 0])


_SIZES = ("layer_types", "heads_held", "kv_heads_held", "head_dim",
          "linear_attn_config", "rms_norm_eps", "num_experts_per_tok",
          "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
          "first_expert", "n_shared_experts")


def _freeze(cfg):
    """What the forward pass reads of the configuration, as a hashable
    for jit's cache (the widths are in the parameters' shapes)."""
    return json.dumps({k: cfg[k] for k in _SIZES if k in cfg},
                      sort_keys=True)


@functools.lru_cache(maxsize=None)
def _block_grad(sizes, prec):
    cfg = json.loads(sizes)
    return jax.jit(jax.value_and_grad(
        lambda p, consts, blk: loss_sum(p, consts, cfg, blk, prec)))


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)


@functools.partial(jax.jit, static_argnames=("t", "lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def _adam_leaf(p, g, m, v, ntok, t, lr, b1, b2, eps):
    lr_t = lr * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
    g = g / ntok
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    return p - lr_t * m / (jnp.sqrt(v) + eps), m, v


def _half(batch, rows):
    """The planted fault "half of the batch left out": the rows of `rows`
    where that leaves any, else (a batch of one row) the first half of the
    row's positions."""
    cut = {k: x[rows] for k, x in batch.items()}
    if len(cut["ids"]):
        return cut
    return {k: x[:, :x.shape[1] // 2] for k, x in batch.items()}


def train_steps(params, cfg, batches, opt, prec="float32", block_rows=1,
                rows=None):
    """Follow `len(batches)` Adam steps from `params` in float32.

    `params` holds the trained leaves and the constants (`*.bias`, the
    expert bias), in whatever dtype the program runs them; it is read,
    never written. The batch is walked in blocks of `block_rows` rows
    (gradient of the summed loss, divided by the token count in the
    update). `rows` (a slice) is the planted fault of `_half`.

    Returns {"loss": [per step], "grad_norm": {leaf: norm at step 1},
    "delta_norm": {leaf: |p_after - p_before|}}, numbers on the host.
    """
    block_grad = _block_grad(_freeze(cfg), prec)
    consts = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
              if k.endswith(".bias")}
    f32 = jax.jit(lambda x: x.astype(jnp.float32))
    p = {k: f32(v) for k, v in params.items() if k not in consts}
    norms = jax.jit(lambda g, n: tree_norms({k: x / n for k, x in g.items()}))
    moments = {}                     # leaf -> (m, v), numpy, on the host
    out = {"loss": []}
    for t, batch in enumerate(batches, 1):
        if rows is not None:
            batch = _half(batch, rows)
        n = batch["ids"].shape[0]
        total = grad = None
        for lo in range(0, n, block_rows):
            blk = {name: np.asarray(x[lo:lo + block_rows], np.int32)
                   for name, x in batch.items()}
            got = block_grad(p, consts, blk)
            total, grad = got if grad is None \
                else (total + got[0], _add(grad, got[1]))
            del got
        ntok = jnp.float32(batch["ids"].size)
        out["loss"].append(float(total / ntok))
        if t == 1:
            out["grad_norm"] = {k: float(x) for k, x in
                                jax.device_get(norms(grad, ntok)).items()}
        for k in sorted(p):
            g = grad.pop(k)
            m, v = moments.pop(k, None) or (jnp.zeros_like(g),
                                            jnp.zeros_like(g))
            p[k], m, v = _adam_leaf(p[k], g, jnp.asarray(m), jnp.asarray(v),
                                    ntok, t, opt["lr"], opt["beta1"],
                                    opt["beta2"], opt["epsilon"])
            if t < len(batches):
                moments[k] = (np.asarray(m), np.asarray(v))
            del g, m, v
    delta = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a - b.astype(jnp.float32)))))
    out["delta_norm"] = {k: float(delta(p[k], params[k])) for k in p}
    return out
