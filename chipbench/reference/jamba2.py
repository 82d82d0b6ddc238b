"""Plain Jamba 2 (AI21, `model_type: jamba`), float32 jax.numpy.

The benchmark's yardstick for `correct` in the `jamba2` cells. It imports
nothing of the program and takes nothing the program made: the parameters
and the batches come from `chipbench/models/jamba2.py`. Written from the
layer equations (ISSUE 42: the public config, and HF's `JambaMambaMixer`,
`JambaAttention`, `JambaMLP`):

    block:  h = h + mixer_i(rms(h));  h = h + mlp(rms(h))
            rms(x) = x * rsqrt(mean(x^2) + eps) * w, in float32
            mixer_i = attention where layer_types[i] == "attention"
    mamba:  [x | z] = h W_in (no bias);  x = silu(conv(x) + b_conv), conv a
            causal depthwise filter of d_conv taps (reference/lfm2_moe.py's)
            [dt_r | B | C] = x W_x;  dt_r, B, C = rms(dt_r), rms(B), rms(C)
            dt = softplus(dt_r W_dt + b_dt), float32;  A = -exp(A_log)
            h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n]
                        + dt_t[c] B_t[n] x_t[c]          h_{-1} = 0, float32
            y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]
            out = (y * silu(z)) W_out
            The recurrence is walked TOKEN BY TOKEN (`lax.scan` over t,
            rematerialised every SCAN_BLOCK steps so that its backward keeps
            one state a block and not one a token): the definition.
    attention: q = h W_q [H heads x D];  k = h W_k, v = h W_v [KVH heads];
            causal softmax(q k^T / sqrt(D)) v, no positions; then W_o
    mlp:    (silu(h W_gate) * (h W_up)) W_down
    model:  embedding -> blocks -> rms -> logits = h E^T (tied)
    loss:   mean next-token cross-entropy over every position

The share of a deployment: the parameters ARE the share (`channels_held`
inner channels, `heads_held` query heads, `intermediate_held` of the MLP's
width, `vocab_size` rows). A mamba mixer's W_x product is a sum over the
inner channels, which the deployment all-reduces; `mamba_in` gives a
share's own part of it and `mamba_out` takes whatever sum it is handed, so
that the shares of a layer can be added up (tests/test_jamba2.py). In a
cell the share is handed its own part: the exchange is left out, here as
in the program.

`prec` is reference/lfm2_moe.py's: "float32" (the reference), "bfloat16"
(what the configuration states), "int8" (the control: every product's
operands rounded to int8 per tensor, the scan's x, B and C among them,
since the scan multiplies them: dt B x, C h). RMSNorm, dt and b_dt,
A_log, D, the scan's state, the softmax and the loss are float32 in all
three, as the configuration states them.

A planted fault for the limits: `state_reset` in the configuration drops
the scan's state to zero every that many tokens (a mechanism left out
that a short-range test would not see).

Memory at the benchmark's size (400.2M parameters, 8192 positions):
parameters, gradient and Adam's moments in float32 are 6.4 GB; each layer
is recomputed in the backward pass (`jax.checkpoint`) and the attention
walks its queries in blocks of 1024, as reference/lfm2_moe.py does.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.lfm2_moe import (  # noqa: F401  (tree_norms: API)
    HI, Q_BLOCK, _act, _adam, _attend_block, _ein, _fake_int8, _swiglu,
    _zeros, dwconv_causal, rms_norm, tree_norms)
from chipbench.reference.solar_open2 import _half

SCAN_BLOCK = 256


def selective_scan(x, dt, A_log, B, C, D, reset=None):
    """x, dt [B, T, Ch], A_log [Ch, N], B, C [B, T, N], D [Ch] -> y [B, T,
    Ch], one token after another, float32. `reset`: the state is dropped
    to zero before every token t > 0 with t % reset == 0 (the fault)."""
    f32 = jnp.float32
    Bsz, T, Ch = x.shape
    a = -jnp.exp(A_log.astype(f32))
    t = jnp.arange(T)
    keep = jnp.ones((T,), f32) if reset is None \
        else jnp.where((t % reset == 0) & (t > 0), 0.0, 1.0).astype(f32)

    def step(h, inp):
        xt, dtt, bt, ct, kt = inp
        h = kt * h * jnp.exp(dtt[..., None] * a) \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, ct, precision=HI)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(step, h, xs)

    pad = (-T) % SCAN_BLOCK          # after the sequence: nothing read

    def blocks(v):
        v = jnp.moveaxis(v.astype(f32), 1, 0) if v.ndim > 1 else v
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((-1, SCAN_BLOCK) + v.shape[1:])

    h0 = jnp.zeros((Bsz, Ch, A_log.shape[-1]), f32)
    _, y = jax.lax.scan(block, h0, tuple(blocks(v)
                                         for v in (x, dt, B, C, keep)))
    y = jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:T], 0, 1)
    return y + D.astype(f32) * x.astype(f32)


def _scan_operand(v, prec):
    """x, B or C as the scan reads them: the int8 control rounds them as
    it rounds every product's operands; dt stays float32."""
    return _fake_int8(v) if prec == "int8" else v


def mamba_in(p, name, x, cfg, prec):
    """(x after the convolution, z, this share's part of x W_x)."""
    xz = _act(_ein("bth,hc->btc", x, p[f"{name}_in.w_0"], prec), prec)
    xi, z = jnp.split(xz, 2, axis=-1)
    u = dwconv_causal(xi, p[f"{name}_conv.w_0"].astype(jnp.float32)) \
        + p[f"{name}_conv.b_0"].astype(jnp.float32)
    xc = _act(jax.nn.silu(_act(u, prec)), prec)
    return xc, z, _ein("btc,cr->btr", xc, p[f"{name}_x.w_0"], prec)


def mamba_out(p, name, xc, z, x_proj, cfg, prec):
    """The mixer's output from x, z and the W_x product it is handed."""
    R, N, eps = cfg["mamba_dt_rank"], cfg["mamba_d_state"], cfg["rms_norm_eps"]
    x_proj = _act(x_proj, prec)
    dt_r, B, C = (_act(rms_norm(v, p[f"{name}_{w}_norm.w_0"], eps), prec)
                  for v, w in ((x_proj[..., :R], "dt"),
                               (x_proj[..., R:R + N], "b"),
                               (x_proj[..., R + N:], "c")))
    dt = jax.nn.softplus(_ein("btr,rc->btc", dt_r, p[f"{name}_dt.w_0"], prec)
                         + p[f"{name}_dt.b_0"].astype(jnp.float32))
    xs, B, C = (_scan_operand(v, prec) for v in (xc, B, C))
    y = _act(selective_scan(xs, dt, p[f"{name}_scan.w_0"], B, C,
                            p[f"{name}_scan.w_1"], cfg.get("state_reset")),
             prec)
    return _ein("btc,ch->bth", _act(y * jax.nn.silu(z), prec),
                p[f"{name}_out.w_0"], prec)


def mamba_mixer(p, name, x, cfg, prec):
    xc, z, x_proj = mamba_in(p, name, x, cfg, prec)
    return mamba_out(p, name, xc, z, x_proj, cfg, prec)


def attention(p, name, x, cfg, prec):
    B, T, _ = x.shape
    KV = cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // cfg["num_attention_heads"]
    H = p[f"{name}_q.w_0"].shape[1] // D              # the heads held
    q = _act(_ein("bth,hc->btc", x, p[f"{name}_q.w_0"], prec), prec)
    k = _act(_ein("bth,hc->btc", x, p[f"{name}_k.w_0"], prec), prec)
    v = _act(_ein("bth,hc->btc", x, p[f"{name}_v.w_0"], prec), prec)
    q = q.reshape(B, T, KV, H // KV, D)
    k, v = k.reshape(B, T, KV, D), v.reshape(B, T, KV, D)
    block = jax.checkpoint(_attend_block, static_argnums=(3, 4))
    outs = [block(q[:, lo:lo + Q_BLOCK], k[:, :lo + Q_BLOCK],
                  v[:, :lo + Q_BLOCK], lo, prec)
            for lo in range(0, T, Q_BLOCK)]
    out = _act(jnp.concatenate(outs, axis=1), prec).reshape(B, T, H * D)
    return _ein("bth,hc->btc", out, p[f"{name}_o.w_0"], prec)


def mlp(p, name, x, prec):
    return _swiglu(x, p[f"{name}_mlp_gate.w_0"], p[f"{name}_mlp_up.w_0"],
                   p[f"{name}_mlp_down.w_0"], prec)


def _layer(p, h, i, kind, cfg, prec):
    name, eps = f"l{i}", cfg["rms_norm_eps"]
    x = _act(rms_norm(h, p[f"{name}_mixer_norm.w_0"], eps), prec)
    mixer = mamba_mixer if kind == "mamba" else attention
    h = _act(h + _act(mixer(p, name, x, cfg, prec), prec), prec)
    x = _act(rms_norm(h, p[f"{name}_mlp_norm.w_0"], eps), prec)
    return _act(h + _act(mlp(p, name, x, prec), prec), prec)


def forward(p, cfg, ids, prec="float32"):
    """Logits [B, T, V] (float32) of the next id at every position."""
    h = _act(jnp.take(p["embed.w_0"], ids, axis=0).astype(jnp.float32), prec)
    for i, kind in enumerate(cfg["layer_types"]):
        layer = jax.checkpoint(
            functools.partial(_layer, i=i, kind=kind, cfg=cfg, prec=prec))
        h = layer({k: v for k, v in p.items()
                   if k.startswith(f"l{i}_")}, h)
    h = _act(rms_norm(h, p["final_norm.w_0"], cfg["rms_norm_eps"]), prec)
    return _ein("bth,vh->btv", h, p["embed.w_0"], prec).astype(jnp.float32)


def loss_sum(p, cfg, batch, prec="float32"):
    """Sum over every position of the next-token cross-entropy."""
    logits = forward(p, cfg, batch["ids"], prec)
    picked = jnp.take_along_axis(logits, batch["labels"][..., None], -1)
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked[..., 0])


_SIZES = ("hidden_size", "layer_types", "num_attention_heads",
          "num_key_value_heads", "mamba_dt_rank", "mamba_d_state",
          "rms_norm_eps", "state_reset")


def _freeze(cfg):
    """What the forward pass reads of the configuration, as a hashable
    for jit's cache (the widths are in the parameters' shapes)."""
    return json.dumps({k: cfg[k] for k in _SIZES if k in cfg},
                      sort_keys=True)


@functools.lru_cache(maxsize=None)
def _block_grad(sizes, prec):
    cfg = json.loads(sizes)

    def add(acc, p, blk):
        total, grad = jax.value_and_grad(loss_sum)(p, cfg, blk, prec)
        return acc[0] + total, jax.tree.map(jnp.add, acc[1], grad)
    return jax.jit(add, donate_argnums=0)


def train_steps(params, cfg, batches, opt, prec="float32", block_rows=1,
                rows=None):
    """Follow `len(batches)` Adam steps from `params` in float32.

    `params` is read, never written, in whatever dtype the program runs
    it. The batch is walked in blocks of `block_rows` rows (gradient of
    the summed loss, divided by the token count in the update). `rows` (a
    slice) is the planted fault "half of the batch left out"
    (reference/solar_open2.py's `_half`: a batch of one row keeps the
    first half of its positions).

    Returns {"loss": [per step], "grad_norm": {leaf: norm at step 1},
    "delta_norm": {leaf: |p_after - p_before|}}, numbers on the host.
    """
    block_grad = _block_grad(_freeze(cfg), prec)
    f32 = jax.jit(lambda t: {k: v.astype(jnp.float32) for k, v in t.items()})
    p = f32(params)
    m, v = _zeros(p), _zeros(p)
    norms = jax.jit(lambda g, n: tree_norms({k: x / n for k, x in g.items()}))
    out = {"loss": []}
    for t, batch in enumerate(batches, 1):
        if rows is not None:
            batch = _half(batch, rows)
        n = batch["ids"].shape[0]
        acc = (jnp.float32(0.0), _zeros(p))
        for lo in range(0, n, block_rows):
            blk = {name: np.asarray(x[lo:lo + block_rows], np.int32)
                   for name, x in batch.items()}
            acc = block_grad(acc, p, blk)
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
