"""Plain Mellum 2 (JetBrains, `model_type: mellum`), float32 jax.numpy.

The benchmark's yardstick for `correct` in the `mellum2` cells. It imports
nothing of the program and takes nothing the program made: the parameters
and the batches come from `chipbench/models/mellum2.py`. Written from the
layer equations (ISSUE 36, from the public config):

    block:   h = h + attn_i(rms(h));  h = h + moe(rms(h))
             rms(x) = x * rsqrt(mean(x^2) + eps) * w, in float32
    attn_i:  q = h W_q [H heads x D];  k = h W_k, v = h W_v [KVH x D]
             q, k = rope_i(rms_head(q)), rope_i(rms_head(k)): an RMSNorm
             over one head's D, then the rotate-half form, the angle
             t * inv_freq_i[j], cos and sin times m_i, by layer type:
               sliding_attention (rope_type default):
                 inv_freq[j] = theta^(-2j/D), m = 1
               full_attention (rope_type yarn): e[j] = theta^(-2j/D),
                 n[j] = e[j] / factor;
                 dim(r) = D ln(original / (2 pi r)) / (2 ln theta);
                 low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)),
                 both kept in [0, D - 1];
                 ramp[j] = clip((j - low) / (high - low), 0, 1);
                 inv_freq[j] = n[j] ramp[j] + e[j] (1 - ramp[j]);
                 m = attention_factor
             query head h reads key-value head h // (H / KVH);
             a_t = softmax over the VISIBLE keys of (q_t k_s / sqrt(D)) v_s
               full_attention: s <= t
               sliding_attention: t - window < s <= t (`sliding_window`
               keys, the query's own among them)
             The visible keys are an explicit boolean mask on the scores.
             y = a W_o
    moe:     p = softmax(h W_r) over ALL experts, float32; chosen = top-k
             of p, the lower id first among equals;
             w = p[chosen] / (sum p[chosen] + 1e-6)
             moe(h) = sum over the chosen experts THAT ARE HELD HERE
             (`first_expert .. first_expert + experts_held - 1`) of
             w_e (silu(h W1_e) * (h W3_e)) W2_e
    model:   embedding -> blocks -> rms -> logits = h W_head (untied)
    loss:    mean next-token cross-entropy over every position

The share of a deployment: the parameters ARE the share (`experts_held`
experts from `first_expert` on, `vocab_size` rows); what the absent
experts would add is left out, here as in the program.

Departures from the published description, because the numbers compared
depend on them:
  - the per-head RMSNorm of q and k before the rotation has no key of its
    own in the config; it is the Qwen-MoE lineage's, whose keys the
    config's MoE block carries (the configuration's `assumed`);
  - the router's function is not stated: softmax over all the experts,
    no selection bias, the chosen renormalised over (their sum + 1e-6),
    the epsilon the program's router has (`assumed`);
  - no auxiliary load-balancing loss and no multi-token-prediction head
    (`described_as` names one; the config has no key for it);
  - Adam in Kingma & Ba's efficient form (as reference/nmt.py).

`prec` is reference/lfm2_moe.py's: "float32" (the reference), "bfloat16"
(what the configuration states), "int8" (the control). RMSNorm, the
router, the angles, the softmax and the loss are float32 in all three.

A planted fault for the builder: a configuration whose `sliding_window`
is None follows the same steps WITHOUT the window (every layer sees
every key s <= t); against the reference it has to fail a limit, else
the comparison cannot see the mechanism (PERF.md section 2).

Memory at the benchmark's size (595.1M parameters, 8192 positions): the
parameters and the gradient in float32 are 2.4 GB each beside the
caller's bfloat16 copy (1.2 GB), so Adam's two moments (4.8 GB) live on
the HOST between the steps and pass through the device a leaf at a time,
as reference/solar_open2.py keeps them; a layer is recomputed in the
backward pass (`jax.checkpoint`), the attention walks its queries in
blocks of `Q_BLOCK` rows ([32, 1024, 8192] float32 scores, 1.07 GB, at
once, masked in full), and the experts are walked `EXPERT_BLOCK` at a
time (one product over all 16 would hold [8192, 16, 2304] float32 twice).
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lfm2_moe import (  # noqa: F401  (tree_norms: API)
    HI, Q_BLOCK, _act, _ein, expert_share, rms_norm, tree_norms)
from chipbench.reference.solar_open2 import _adam_leaf, _add, _half

EXPERT_BLOCK = 4


def inv_freq(rope, D):
    """([D/2] float32 frequencies, the scale of cos and sin) of one
    `rope_parameters` block."""
    j = jnp.arange(D // 2, dtype=jnp.float32)
    e = jnp.float32(rope["rope_theta"]) ** (-2.0 * j / D)
    if rope["rope_type"] == "default":
        return e, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def dim(r):
        return D * math.log(rope["original_max_position_embeddings"]
                            / (2 * math.pi * r)) \
            / (2 * math.log(rope["rope_theta"]))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), D - 1)
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return e / rope["factor"] * ramp + e * (1.0 - ramp), \
        float(rope["attention_factor"])


def rope(x, parameters):
    """x [B, T, H, D]; the pair (x[j], x[j + D/2]) turned by t * inv_freq[j],
    cos and sin scaled."""
    T, D = x.shape[1], x.shape[-1]
    inv, m = inv_freq(parameters, D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * m)[None, :, None, :]
    sin = (jnp.sin(ang) * m)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend_block(q, k, v, lo, window, prec):
    """Queries lo .. lo + len(q) - 1 against ALL the keys, the visible
    ones picked by a boolean mask. q [B, Tq, KV, G, D]; k, v [B, S, KV, D]."""
    Tq, S, D = q.shape[1], k.shape[1], q.shape[-1]
    s = _ein("bqkgd,bskd->bkgqs", q, k, prec) * jnp.float32(D ** -0.5)
    t, key = (lo + jnp.arange(Tq))[:, None], jnp.arange(S)[None, :]
    visible = key <= t
    if window is not None:
        visible = visible & (key > t - window)
    w = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return _ein("bkgqs,bskd->bqkgd", _act(w, prec), v, prec)


def attention(p, name, x, kind, cfg, prec):
    B, T, _ = x.shape
    H, KV, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, parameters = cfg["rms_norm_eps"], cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None

    def heads(w, n):
        return _act(_ein("bth,hc->btc", x, p[w], prec),
                    prec).reshape(B, T, n, D)

    q = _act(rope(rms_norm(heads(f"{name}_q.w_0", H),
                           p[f"{name}_q_norm.w_0"], eps), parameters), prec)
    k = _act(rope(rms_norm(heads(f"{name}_k.w_0", KV),
                           p[f"{name}_k_norm.w_0"], eps), parameters), prec)
    v = heads(f"{name}_v.w_0", KV)
    q = q.reshape(B, T, KV, H // KV, D)
    block = jax.checkpoint(_attend_block, static_argnums=(3, 4, 5))
    outs = [block(q[:, lo:lo + Q_BLOCK], k, v, lo, window, prec)
            for lo in range(0, T, Q_BLOCK)]
    out = _act(jnp.concatenate(outs, axis=1), prec).reshape(B, T, H * D)
    return _ein("bth,hc->btc", out, p[f"{name}_o.w_0"], prec)


def route(x, w_r, k, norm_topk_prob=True):
    """(chosen [..., k] int32, weights [..., k]) over all the experts;
    float32 whatever `prec` is."""
    s = jax.nn.softmax(jnp.einsum(
        "...h,he->...e", x.astype(jnp.float32), w_r.astype(jnp.float32),
        precision=HI), axis=-1)
    _, chosen = jax.lax.top_k(jax.lax.stop_gradient(s), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return chosen.astype(jnp.int32), w


def moe(p, name, x, cfg, prec):
    chosen, w = route(x, p[f"{name}_router.w_0"],
                      cfg["num_experts_per_tok"], cfg["norm_topk_prob"])
    w1, w3, w2 = (p[f"{name}_experts.w_{i}"] for i in range(3))
    first = cfg.get("first_expert", 0)
    share = jax.checkpoint(expert_share, static_argnums=(6, 7))
    return sum(share(x, chosen, w, w1[lo:lo + EXPERT_BLOCK],
                     w3[lo:lo + EXPERT_BLOCK], w2[lo:lo + EXPERT_BLOCK],
                     first + lo, prec)
               for lo in range(0, w1.shape[0], EXPERT_BLOCK))


def _layer(p, h, i, kind, cfg, prec):
    name, eps = f"l{i}", cfg["rms_norm_eps"]
    x = _act(rms_norm(h, p[f"{name}_attn_norm.w_0"], eps), prec)
    h = _act(h + _act(attention(p, name, x, kind, cfg, prec), prec), prec)
    x = _act(rms_norm(h, p[f"{name}_ffn_norm.w_0"], eps), prec)
    return _act(h + _act(moe(p, name, x, cfg, prec), prec), prec)


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


def loss_sum(p, cfg, batch, prec="float32"):
    """Sum over every position of the next-token cross-entropy."""
    logits = forward(p, cfg, batch["ids"], prec)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked[..., 0])


_SIZES = ("layer_types", "num_attention_heads", "num_key_value_heads",
          "head_dim", "rms_norm_eps", "rope_parameters", "sliding_window",
          "num_experts_per_tok", "norm_topk_prob", "first_expert")


def _freeze(cfg):
    """What the forward pass reads of the configuration, as a hashable
    for jit's cache (the widths are in the parameters' shapes)."""
    return json.dumps({k: cfg[k] for k in _SIZES if k in cfg},
                      sort_keys=True)


@functools.lru_cache(maxsize=None)
def _block_grad(sizes, prec):
    cfg = json.loads(sizes)
    return jax.jit(jax.value_and_grad(
        lambda p, blk: loss_sum(p, cfg, blk, prec)))


def train_steps(params, cfg, batches, opt, prec="float32", block_rows=1,
                rows=None):
    """Follow `len(batches)` Adam steps from `params` in float32.

    `params` holds the trained leaves in whatever dtype the program runs
    them; it is read, never written. The batch is walked in blocks of
    `block_rows` rows (gradient of the summed loss, divided by the token
    count in the update). `rows` (a slice) is the planted fault of
    reference/solar_open2.py's `_half`. Adam's moments live on the host
    between the steps.

    Returns {"loss": [per step], "grad_norm": {leaf: norm at step 1},
    "delta_norm": {leaf: |p_after - p_before|}}, numbers on the host.
    """
    block_grad = _block_grad(_freeze(cfg), prec)
    f32 = jax.jit(lambda x: x.astype(jnp.float32))
    p = {k: f32(v) for k, v in params.items()}
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
            got = block_grad(p, blk)
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
