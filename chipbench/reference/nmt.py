"""Plain transformer-base (Vaswani et al. 2017, section 3), float32 jax.numpy.

The benchmark's yardstick for `correct`. It imports nothing of the
program and takes nothing the program made: the parameters come from
`chipbench.weights`, the batches and prompts from `chipbench.loadgen`.

Written from the paper. What the paper leaves open follows the Fluid book
transformer this repository serves, and is noted here because the numbers
compared depend on it:
- post-LN residual blocks (the paper's), LayerNorm eps 1e-5, no biases on
  the attention projections, ReLU feed-forward with biases;
- embeddings scaled by sqrt(d_model); sinusoids laid out [sin | cos]
  (tensor2tensor's layout), not interleaved;
- separate source and target embeddings and an untied output projection;
- label smoothing 0.1 against the uniform distribution over the vocabulary,
  the loss a mean over real target tokens;
- Adam in the paper's efficient form (Kingma & Ba, end of section 2):
  lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t), p -= lr_t * m / (sqrt(v) + eps);
- q, k, v of self-attention come from one [d, 3d] matrix, k, v of
  cross-attention from one [d, 2d] matrix (column thirds / halves).

`prec` selects the arithmetic: "float32" (the reference; every product at
`highest`), "bfloat16" (the precision the training configuration states:
the tests show that it passes the limits) and "int8" (the control: every
matmul operand rounded to int8 per tensor; PERF.md "How correct is decided").
"""
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def _fake_int8(x):
    """Per-tensor symmetric int8 rounding, straight-through gradient."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _ein(spec, a, b, prec):
    if prec == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16))
    if prec == "int8":
        a, b = _fake_int8(a), _fake_int8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _act(x, prec):
    return x.astype(jnp.bfloat16) if prec == "bfloat16" else x


def _ln(x, w, b, prec):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return _act((xf - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b, prec)


def _sinusoid(T, d):
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _embed(table, ids, prec):
    d = table.shape[1]
    x = jnp.take(table, ids, axis=0).astype(jnp.float32) * jnp.sqrt(
        jnp.float32(d))
    return _act(x + _sinusoid(ids.shape[1], d)[None], prec)


def _attend(q, k, v, bias, n_head, prec):
    B, Tq, d = q.shape
    dh = d // n_head
    q = q.reshape(B, Tq, n_head, dh)
    k = k.reshape(B, k.shape[1], n_head, dh)
    v = v.reshape(B, v.shape[1], n_head, dh)
    s = _ein("bqhd,bkhd->bhqk", q, k, prec).astype(jnp.float32)
    s = s * jnp.float32(dh ** -0.5) + bias
    w = _act(jax.nn.softmax(s, axis=-1), prec)
    return _ein("bhqk,bkhd->bqhd", w, v, prec).reshape(B, Tq, d)


def _ffn(p, name, x, prec):
    h = _ein("btd,df->btf", x, p[f"{name}_fc1.w_0"], prec)
    h = jax.nn.relu(h + p[f"{name}_fc1.b_0"].astype(h.dtype))
    o = _ein("btf,fd->btd", h, p[f"{name}_fc2.w_0"], prec)
    return o + p[f"{name}_fc2.b_0"].astype(o.dtype)


def forward(p, cfg, src, src_len, trg, trg_len, prec="float32"):
    """Logits [B, T_trg, V] (float32) of `trg` given `src`, teacher-forced."""
    L, H, d = cfg["n_layer"], cfg["n_head"], cfg["d_model"]
    Ts, Tt = src.shape[1], trg.shape[1]
    neg = jnp.float32(-1e9)
    src_bias = jnp.where(jnp.arange(Ts)[None, :] < src_len[:, None],
                         0.0, neg)[:, None, None, :]
    causal = jnp.where(jnp.arange(Tt)[None, :] <= jnp.arange(Tt)[:, None],
                       0.0, neg)[None, None]
    trg_bias = causal + jnp.where(
        jnp.arange(Tt)[None, :] < trg_len[:, None], 0.0,
        neg)[:, None, None, :]
    ln = 0

    def norm(x):
        nonlocal ln
        y = _ln(x, p[f"layer_norm_{ln}.w_0"], p[f"layer_norm_{ln}.b_0"], prec)
        ln += 1
        return y

    x = _embed(p["src_emb.w_0"], src, prec)
    for i in range(L):
        qkv = _ein("btd,de->bte", x, p[f"enc{i}_qkv.w_0"], prec)
        a = _attend(qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:],
                    src_bias, H, prec)
        x = norm(x + _ein("btd,de->bte", a, p[f"enc{i}_o.w_0"], prec))
        x = norm(x + _ffn(p, f"enc{i}_ffn", x, prec))
    enc = x
    x = _embed(p["trg_emb.w_0"], trg, prec)
    for i in range(L):
        qkv = _ein("btd,de->bte", x, p[f"dec{i}_self_qkv.w_0"], prec)
        a = _attend(qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:],
                    trg_bias, H, prec)
        x = norm(x + _ein("btd,de->bte", a, p[f"dec{i}_self_o.w_0"], prec))
        q = _ein("btd,de->bte", x, p[f"dec{i}_cross_q.w_0"], prec)
        kv = _ein("bsd,de->bse", enc, p[f"dec{i}_cross_kv.w_0"], prec)
        a = _attend(q, kv[..., :d], kv[..., d:], src_bias, H, prec)
        x = norm(x + _ein("btd,de->bte", a, p[f"dec{i}_cross_o.w_0"], prec))
        x = norm(x + _ffn(p, f"dec{i}_ffn", x, prec))
    return _ein("btd,dv->btv", x, p["proj.w_0"], prec).astype(jnp.float32)


def loss_sum(p, cfg, batch, prec="float32"):
    """Sum over real target tokens of the label-smoothed cross entropy."""
    logits = forward(p, cfg, batch["src"], batch["src_len"], batch["trg"],
                     batch["trg_len"], prec)
    eps = cfg["label_smooth_eps"]
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, batch["label"][..., None], -1)[..., 0]
    tok = (1 - eps) * (lse - picked) + eps * (lse - jnp.mean(logits, -1))
    mask = jnp.arange(logits.shape[1])[None, :] < batch["trg_len"][:, None]
    return jnp.sum(jnp.where(mask, tok, 0.0))


def tree_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("t", "lr", "b1", "b2", "eps"))
def _adam(p, g, m, v, t, lr, b1, b2, eps):
    lr_t = lr * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in p}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in p}
    p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + eps) for k in p}
    return p, m, v


def _sizes(cfg):
    """The configuration's numbers as a hashable, for jit's cache."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)))


@functools.lru_cache(maxsize=None)
def _block_grad(sizes, prec):
    return jax.jit(lambda p, blk: jax.value_and_grad(loss_sum)(
        p, dict(sizes), blk, prec))


@jax.jit
def _acc(a, b):
    return jax.tree.map(jnp.add, a, b)


def train_steps(params, cfg, batches, opt, prec="float32", block_rows=32,
                rows=None):
    """Follow `len(batches)` Adam steps from `params` in float32.

    The batch is walked in blocks of `block_rows` rows (gradient of the
    summed loss, divided by the token count at the end), so that the
    float32 activations fit beside whatever else the chip holds. `rows` (a
    slice) keeps only those rows of every batch and takes the mean over
    them: the planted fault "half of the batch left out".

    Returns {"loss": [per step], "grad_norm": {leaf: norm at step 1},
    "delta_norm": {leaf: |p_after - p_before|}}, numbers on the host.
    """
    import numpy as np
    block_grad = _block_grad(_sizes(cfg), prec)
    p0 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    p = p0
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    out = {"loss": []}
    for t, batch in enumerate(batches, 1):
        if rows is not None:
            batch = {k: x[rows] for k, x in batch.items()}
        n = batch["src"].shape[0]
        acc = None
        for lo in range(0, n, block_rows):
            blk = {name: np.asarray(x[lo:lo + block_rows], np.int32)
                   for name, x in batch.items()}
            part = block_grad(p, blk)
            acc = part if acc is None else _acc(acc, part)
        total, grad = acc
        ntok = jnp.float32(max(1, int(batch["trg_len"].sum())))
        grad = jax.tree.map(lambda g: g / ntok, grad)
        out["loss"].append(float(total / ntok))
        if t == 1:
            out["grad_norm"] = jax.device_get(jax.jit(tree_norms)(grad))
        p, m, v = _adam(p, grad, m, v, t, opt["lr"], opt["beta1"],
                        opt["beta2"], opt["epsilon"])
    delta = jax.jit(lambda a, b: tree_norms(
        {k: a[k] - b[k] for k in a}))(p, p0)
    out["delta_norm"] = {k: float(x)
                         for k, x in jax.device_get(delta).items()}
    out["grad_norm"] = {k: float(x) for k, x in out["grad_norm"].items()}
    return out

