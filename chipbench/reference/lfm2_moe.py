"""Plain LFM2-MoE (LiquidAI, `model_type: lfm2_moe`), float32 jax.numpy.

The benchmark's yardstick for `correct` in the `lfm2_moe` cells. It imports
nothing of the program and takes nothing the program made: the parameters
and the batches come from `chipbench/models/lfm2_moe.py`. Written from the
layer equations (ISSUE 30, from the public config and modelling code):

    block:      h = h + mixer(rms(h));  h = h + ffn(rms(h))
                rms(x) = x * rsqrt(mean(x^2) + eps) * w, in float32
    conv mixer: B, C, x = split3(h W_in);  y = (C * dwconv(B * x)) W_out
                dwconv(u)[t, c] = sum_j k[c, j] * u[t - (K-1) + j, c],
                zero before the sequence (K = conv_L_cache, no bias)
    attention:  q = rope(rms_head(h W_q)), k = rope(rms_head(h W_k)),
                v = h W_v; H query heads over KVH key-value heads (query
                head h reads key-value head h // (H / KVH)), causal
                softmax(q k^T / sqrt(D)) v, then W_o; rms_head is an
                RMSNorm over one head's D; rope is the rotate-half form
    dense ffn:  (silu(h W_1) * (h W_3)) W_2;  expert e: the same
    router:     s = sigmoid(h W_r) over ALL experts, float32; chosen =
                top-k of (s + bias), the lower id first among equals;
                w = s[chosen] / (sum s[chosen] + 1e-6) * scaling
                ffn(h) = sum over the chosen experts THAT ARE HELD HERE
                (`first_expert .. first_expert + experts_held - 1`) of
                w_e * expert_e(h): the share of one expert-parallel rank
    model:      embedding -> blocks -> rms -> logits = h E^T (tied)
    loss:       mean next-token cross-entropy over every position

Departures, because the numbers compared depend on them: the expert bias is
a constant (it only selects; nothing trains it here); no auxiliary loss;
Adam in Kingma & Ba's efficient form (as reference/nmt.py).

`prec` selects the arithmetic of the matrix products and the activations:
"float32" (the reference, every product at `highest`), "bfloat16" (what
the configuration states; the tests show that it passes the limits),
"int8" (the control: every product's operands rounded to int8 per
tensor). RMSNorm, the router, the softmaxes and the loss are float32 in
all three, as the configuration states them.

Memory at the benchmark's size (469M parameters, 8192 positions):
parameters, gradient and Adam's moments in float32 are 7.5 GB, so the
batch is walked a row at a time (`block_rows`), each layer is recomputed in
the backward pass (`jax.checkpoint`: only the layers' inputs are kept),
and the attention walks the queries in blocks of `Q_BLOCK` rows so that
the scores held at once are H x Q_BLOCK x T floats (1 GB at 1024) and
never the 8.6 GB of a whole row.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024


def _fake_int8(x):
    """Per-tensor symmetric int8 rounding, straight-through gradient."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _ein(spec, a, b, prec):
    if prec == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if prec == "int8":
        a, b = _fake_int8(a), _fake_int8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _act(x, prec):
    """An activation as the stated precision keeps it."""
    if prec == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, T, H, D]; the pair (x[i], x[i + D/2]) turned by t * theta^(-2i/D)."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(D // 2, dtype=jnp.float32) * 2.0 / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def dwconv_causal(u, k):
    """u [B, T, C], k [C, K] -> out[t, c] = sum_j k[c, j] u[t - (K-1) + j, c]."""
    K, T = k.shape[1], u.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(up[:, j:j + T, :] * k[:, j] for j in range(K))


def conv_mixer(p, name, x, prec):
    bcx = _act(_ein("bth,hc->btc", x, p[f"{name}_conv_in.w_0"], prec), prec)
    b, c, u = jnp.split(bcx, 3, axis=-1)
    u = _act(dwconv_causal(_act(b * u, prec), p[f"{name}_conv.w_0"]), prec)
    return _ein("bth,hc->btc", _act(c * u, prec),
                p[f"{name}_conv_out.w_0"], prec)


def _attend_block(q, k, v, lo, prec):
    """Queries lo .. lo + len(q) - 1 against the keys 0 .. lo + len(q) - 1.
    q [B, Tq, KV, G, D]; k, v [B, S, KV, D]."""
    Tq, S, D = q.shape[1], k.shape[1], q.shape[-1]
    s = _ein("bqkgd,bskd->bkgqs", q, k, prec) * jnp.float32(D ** -0.5)
    keep = jnp.arange(S)[None, :] <= (lo + jnp.arange(Tq))[:, None]
    w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return _ein("bkgqs,bskd->bqkgd", _act(w, prec), v, prec)


def rope_theta(cfg):
    """The published config nests it (`rope_parameters.rope_theta`)."""
    if "rope_theta" in cfg:
        return float(cfg["rope_theta"])
    return float(cfg["rope_parameters"]["rope_theta"])


def attention(p, name, x, cfg, prec):
    B, T, _ = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // H
    eps, theta = cfg["norm_eps"], rope_theta(cfg)

    def heads(w, n):
        return _ein("bth,hc->btc", x, p[w], prec).reshape(B, T, n, D)

    q = _act(rope(rms_norm(_act(heads(f"{name}_q.w_0", H), prec),
                           p[f"{name}_q_norm.w_0"], eps), theta), prec)
    k = _act(rope(rms_norm(_act(heads(f"{name}_k.w_0", KV), prec),
                           p[f"{name}_k_norm.w_0"], eps), theta), prec)
    v = _act(heads(f"{name}_v.w_0", KV), prec)
    q = q.reshape(B, T, KV, H // KV, D)
    block = jax.checkpoint(_attend_block, static_argnums=(3, 4))
    outs = [block(q[:, lo:lo + Q_BLOCK], k[:, :lo + Q_BLOCK],
                  v[:, :lo + Q_BLOCK], lo, prec)
            for lo in range(0, T, Q_BLOCK)]
    out = _act(jnp.concatenate(outs, axis=1), prec).reshape(B, T, H * D)
    return _ein("bth,hc->btc", out, p[f"{name}_o.w_0"], prec)


def _swiglu(x, w1, w3, w2, prec):
    h1 = _act(_ein("...h,hf->...f", x, w1, prec), prec)
    h3 = _act(_ein("...h,hf->...f", x, w3, prec), prec)
    g = _act(h1 * jax.nn.sigmoid(h1) * h3, prec)
    return _ein("...f,fh->...h", g, w2, prec)


def dense_ffn(p, name, x, prec):
    return _swiglu(x, p[f"{name}_ffn_w1.w_0"], p[f"{name}_ffn_w3.w_0"],
                   p[f"{name}_ffn_w2.w_0"], prec)


def route(x, w_r, bias, k, norm_topk_prob=True, scaling=1.0):
    """(chosen [..., k] int32, weights [..., k]) over all the experts;
    float32 whatever `prec` is."""
    s = jax.nn.sigmoid(jnp.einsum("...h,he->...e", x.astype(jnp.float32),
                                  w_r.astype(jnp.float32), precision=HI))
    sel = s if bias is None else s + bias
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(sel), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return chosen.astype(jnp.int32), w * scaling


def expert_share(x, chosen, w, w1, w3, w2, first_expert, prec):
    """The part of the expert layer that experts `first_expert ..
    first_expert + len(w1) - 1` give: every held expert over every token
    (one product over the expert axis), weighted by the token's weight
    for it (zero where it was not chosen)."""
    held = first_expert + jnp.arange(w1.shape[0])
    gate = jnp.sum(jnp.where(chosen[..., None] == held, w[..., None], 0.0),
                   axis=-2)                                   # [..., E]
    h1 = _act(_ein("...h,ehf->...ef", x, w1, prec), prec)
    h3 = _act(_ein("...h,ehf->...ef", x, w3, prec), prec)
    g = _act(h1 * jax.nn.sigmoid(h1) * h3, prec)
    y = _ein("...ef,efh->...eh", g, w2, prec)
    return jnp.sum(gate[..., None] * y, axis=-2)


def expert_ffn(p, name, x, cfg, prec):
    bias = p.get(f"{name}_router.bias") if cfg["use_expert_bias"] else None
    chosen, w = route(x, p[f"{name}_router.w_0"], bias,
                      cfg["num_experts_per_tok"], cfg["norm_topk_prob"],
                      float(cfg["routed_scaling_factor"]))
    return expert_share(x, chosen, w, p[f"{name}_experts.w_0"],
                        p[f"{name}_experts.w_1"], p[f"{name}_experts.w_2"],
                        cfg.get("first_expert", 0), prec)


def _layer(p, h, i, kind, cfg, prec):
    name, eps = f"l{i}", cfg["norm_eps"]
    x = _act(rms_norm(h, p[f"{name}_operator_norm.w_0"], eps), prec)
    mixer = conv_mixer(p, name, x, prec) if kind == "conv" \
        else attention(p, name, x, cfg, prec)
    h = _act(h + _act(mixer, prec), prec)
    x = _act(rms_norm(h, p[f"{name}_ffn_norm.w_0"], eps), prec)
    ffn = dense_ffn(p, name, x, prec) if i < cfg["num_dense_layers"] \
        else expert_ffn(p, name, x, cfg, prec)
    return _act(h + _act(ffn, prec), prec)


def forward(p, cfg, ids, prec="float32"):
    """Logits [B, T, V] (float32) of the next id at every position."""
    h = _act(jnp.take(p["embed.w_0"], ids, axis=0).astype(jnp.float32), prec)
    for i, kind in enumerate(cfg["layer_types"]):
        layer = jax.checkpoint(
            functools.partial(_layer, i=i, kind=kind, cfg=cfg, prec=prec))
        h = layer({k: v for k, v in p.items()
                   if k.startswith(f"l{i}_")}, h)
    h = _act(rms_norm(h, p["final_norm.w_0"], cfg["norm_eps"]), prec)
    return _ein("bth,vh->btv", h, p["embed.w_0"], prec).astype(jnp.float32)


def loss_sum(p, consts, cfg, batch, prec="float32"):
    """Sum over every position of the next-token cross-entropy. `consts`:
    what the model holds and does not train (the expert bias)."""
    logits = forward({**p, **consts}, cfg, batch["ids"], prec)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked[..., 0])


def tree_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


_SIZES = ("hidden_size", "layer_types", "num_dense_layers",
          "num_attention_heads", "num_key_value_heads", "norm_eps",
          "rope_theta", "num_experts_per_tok", "norm_topk_prob",
          "use_expert_bias", "routed_scaling_factor", "first_expert")


def _freeze(cfg):
    """What the forward pass reads of the configuration, as a hashable
    for jit's cache (the widths are in the parameters' shapes)."""
    cfg = dict(cfg, rope_theta=rope_theta(cfg))
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in _SIZES if k in cfg)


@functools.lru_cache(maxsize=None)
def _block_grad(sizes, prec):
    cfg = dict(sizes)

    def add(acc, p, consts, blk):
        total, grad = jax.value_and_grad(loss_sum)(p, consts, cfg, blk, prec)
        return acc[0] + total, jax.tree.map(jnp.add, acc[1], grad)
    return jax.jit(add, donate_argnums=0)


@functools.partial(jax.jit, static_argnames=("t", "lr", "b1", "b2", "eps"),
                   donate_argnums=(0, 2, 3))
def _adam(p, g, m, v, ntok, t, lr, b1, b2, eps):
    lr_t = lr * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
    g = {k: g[k] / ntok for k in p}
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in p}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in p}
    p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + eps) for k in p}
    return p, m, v


def _zeros(p):
    return jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))(p)


def train_steps(params, cfg, batches, opt, prec="float32", block_rows=1,
                rows=None):
    """Follow `len(batches)` Adam steps from `params` in float32.

    `params` holds the trained leaves and the constants (`*.bias`, the
    expert bias), in whatever dtype the program runs them; it is read,
    never written. The batch is walked in blocks of `block_rows` rows
    (gradient of the summed loss, divided by the token count in the
    update). `rows` (a slice) keeps only those rows of every batch and
    takes the mean over them: the planted fault "half of the batch left
    out".

    Returns {"loss": [per step], "grad_norm": {leaf: norm at step 1},
    "delta_norm": {leaf: |p_after - p_before|}}, numbers on the host.
    """
    block_grad = _block_grad(_freeze(cfg), prec)
    consts = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
              if k.endswith(".bias")}
    f32 = jax.jit(lambda t: {k: v.astype(jnp.float32) for k, v in t.items()})
    p = f32({k: v for k, v in params.items() if k not in consts})
    m, v = _zeros(p), _zeros(p)
    norms = jax.jit(lambda g, n: tree_norms({k: x / n for k, x in g.items()}))
    out = {"loss": []}
    for t, batch in enumerate(batches, 1):
        if rows is not None:
            batch = {k: x[rows] for k, x in batch.items()}
        n = batch["ids"].shape[0]
        acc = (jnp.float32(0.0), _zeros(p))
        for lo in range(0, n, block_rows):
            blk = {name: np.asarray(x[lo:lo + block_rows], np.int32)
                   for name, x in batch.items()}
            acc = block_grad(acc, p, consts, blk)
        total, grad = acc
        ntok = jnp.float32(batch["ids"].size)
        out["loss"].append(float(total / ntok))
        if t == 1:
            out["grad_norm"] = {k: float(x) for k, x in
                                jax.device_get(norms(grad, ntok)).items()}
        p, m, v = _adam(p, grad, m, v, ntok, t, opt["lr"], opt["beta1"],
                        opt["beta2"], opt["epsilon"])
        del grad, acc
    delta = jax.jit(lambda a, b: tree_norms(
        {k: a[k] - b[k].astype(jnp.float32) for k in a}))(p, params)
    out["delta_norm"] = {k: float(x)
                         for k, x in jax.device_get(delta).items()}
    return out
