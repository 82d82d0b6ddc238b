"""Linear-attention scans: the gated delta rule with a per-channel decay
(Kimi Delta Attention, arXiv:2510.26692) and the small ops around it.

Per head, with a state S in R^{Dk x Dv} (float32, zero before the
sequence), a decay g_t <= 0 per key channel and a step beta_t:

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

`kda_recurrent` walks the tokens one at a time (the definition, and the
registry's reference). The op runs the chunked form: chunks of `CHUNK`
tokens, inside a chunk the WY / UT transform, between chunks one state a
head. Where the trace lowers for a TPU and Dk, Dv are multiples of 128 it
is the Mosaic kernels of ops/pallas/kda.py (`try_kda`: a chunk in VMEM,
forward and a hand-derived backward); everywhere else (off the TPU, any
other width) it is `kda_chunked` below, a jnp composition of the same
rule. With G_i the decay summed from the chunk's start to row i,

    A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)      j <  i
    P_ij =        sum_d q_id k_jd exp(G_id - G_jd)      j <= i
    (I + A) [W | Uv] = beta * [k exp(G) | v]
    S_next = (diag(exp(G_C)) - Kh^T W) S + Kh^T Uv,   Kh_j = k_j exp(G_C - G_j)
    O = scale * ((q exp(G)) S + P (Uv - W S))

The decay is per channel, so exp(G_i - G_j) does not factor into
exp(G_i) * exp(-G_j) over a chunk: exp(-G) overflows float32 where g is
strong (64 steps of -1.6 already). The chunk is cut into sub-blocks of
`SUB` rows. A sub-block's rows against EARLIER rows are a product of two
factors taken relative to the sub-block's first row r: k_i exp(G_i - G_r)
and k_j exp(G_r - G_j), both exponents <= 0. Inside a sub-block the
exponent is the explicit difference G_i - G_j, masked to j <= i before
the exponential. Nothing is ever raised to a positive power.

In `kda_chunked` only the chain of chunk states is sequential (`lax.scan`
over T / CHUNK products of [Dk, Dk] with [Dk, Dv]); everything else is
batched over the chunks. `jax.value_and_grad` goes through all of it:
the composition has no hand-written backward. The whole function is
rematerialised in the backward pass (`jax.checkpoint`), so a layer keeps
q, k, v, g and beta and none of the chunk tensors.

The diagonal selective scan of a Mamba-1 mixer (`selective_scan`) keeps a
state per channel and state index whose decay exp(dt A) depends on both
and on the input, so it has no matrix form: `selective_scan_recurrent` is
its definition and what the op runs off the TPU; the Mosaic kernels of
ops/pallas/selective_scan.py run it where the channels are whole vregs on
a TPU.
"""
import functools

import jax
import jax.numpy as jnp

from .registry import kernel

__all__ = ["kda_chunked", "kda_recurrent", "selective_scan_recurrent",
           "CHUNK", "SUB", "SCAN_CHUNK"]

CHUNK = 64
SUB = 16
SCAN_CHUNK = 256
HI = jax.lax.Precision.HIGHEST


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def kda_recurrent(q, k, v, g, beta, scale=None):
    """The recurrence token by token. q, k, g [B, T, H, Dk], v [B, T, H,
    Dv], beta [B, T, H] -> o [B, T, H, Dv] in q's dtype; float32 inside.
    Walked in blocks of CHUNK tokens that the backward pass recomputes, so
    that a gradient keeps one state a block and not one a token."""
    f32 = jnp.float32
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    B, T, H, Dk = q.shape

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        r = vt - _ein("bhkv,bhk->bhv", S, kt)
        S = S + (bt[..., None] * kt)[..., None] * r[..., None, :]
        return S, _ein("bhkv,bhk->bhv", S, qt) * scale

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(step, S, xs)

    pad = (-T) % CHUNK           # g = 0, beta = 0: the state stands still

    def blocks(x):
        x = jnp.moveaxis(x.astype(f32), 1, 0)
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, CHUNK) + x.shape[1:])

    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(block, S0, tuple(blocks(x)
                                         for x in (q, k, v, g, beta)))
    o = o.reshape((-1,) + o.shape[2:])[:T]
    return jnp.moveaxis(o, 0, 1).astype(q.dtype)


def _intra_chunk(q, k, G, beta):
    """A (strictly lower, beta folded in) and P (lower) of every chunk.
    q, k, G [..., C, D], beta [..., C] -> [..., C, C] each."""
    C, D = q.shape[-2:]
    ns = C // SUB
    lead = q.shape[:-2]
    qs, ks, Gs = (x.reshape(lead + (ns, SUB, D)) for x in (q, k, G))
    first = Gs[..., 0, :]                                   # [..., ns, D]
    # a sub-block's rows against the chunk's earlier rows
    left = jnp.exp(Gs - first[..., None, :])
    earlier = jnp.arange(C)[None, :] < (jnp.arange(ns) * SUB)[:, None]
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        earlier[..., None], first[..., None, :] - G[..., None, :, :],
        -jnp.inf))                                          # [..., ns, C, D]
    A = _ein("...id,...jd->...ij", ks * left, right).reshape(lead + (C, C))
    P = _ein("...id,...jd->...ij", qs * left, right).reshape(lead + (C, C))
    # inside a sub-block: the explicit difference, masked before the exp
    i, j = jnp.arange(SUB)[:, None], jnp.arange(SUB)[None, :]
    E = jnp.exp(jnp.where((i >= j)[..., None],
                          Gs[..., :, None, :] - Gs[..., None, :, :],
                          -jnp.inf))                        # [.., S, S, D]
    kE = ks[..., None, :, :] * E
    A_in = jnp.sum(ks[..., :, None, :] * kE, -1) * (i > j)
    P_in = jnp.sum(qs[..., :, None, :] * kE, -1)
    eye = jnp.eye(ns, dtype=A.dtype)[:, None, :, None]

    def on_diagonal(x):          # [..., ns, S, S] -> [..., C, C]
        return (x[..., :, :, None, :] * eye).reshape(lead + (C, C))

    A = (A + on_diagonal(A_in)) * beta[..., None]
    return A, P + on_diagonal(P_in)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _kda_chunked(q, k, v, g, beta, scale):
    f32 = jnp.float32
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = CHUNK
    pad = (-T) % C
    nc = (T + pad) // C

    def chunks(x):               # [B, T, H, ...] -> [B, H, nc, C, ...]
        x = x.astype(f32)
        if pad:                  # g = 0, beta = 0: the state stands still
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((B, nc, C) + x.shape[2:]), 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)
    A, P = _intra_chunk(q, k, G, beta)
    rhs = jnp.concatenate([k * jnp.exp(G), v], -1) * beta[..., None]
    X = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    W, Uv = X[..., :Dk], X[..., Dk:]
    total = G[..., -1, :]                                   # [B, H, nc, Dk]
    Kh = k * jnp.exp(total[..., None, :] - G)
    M = jnp.exp(total)[..., None] * jnp.eye(Dk, dtype=f32) \
        - _ein("...cd,...ce->...de", Kh, W)
    N = _ein("...cd,...cv->...dv", Kh, Uv)

    def step(S, mn):
        return _ein("bhde,bhev->bhdv", mn[0], S) + mn[1], S

    _, S = jax.lax.scan(step, jnp.zeros((B, H, Dk, Dv), f32),
                        (jnp.moveaxis(M, 2, 0), jnp.moveaxis(N, 2, 0)))
    S = jnp.moveaxis(S, 0, 2)                # the state each chunk starts on
    U = Uv - _ein("...cd,...dv->...cv", W, S)
    o = _ein("...cd,...dv->...cv", q * jnp.exp(G), S) \
        + _ein("...ij,...jv->...iv", P, U)
    o = jnp.moveaxis(o * scale, 1, 3).reshape(B, nc * C, H, Dv)
    return o[:, :T]


def kda_chunked(q, k, v, g, beta, scale=None):
    """The same function as `kda_recurrent`, in the chunked form."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _kda_chunked(q, k, v, g, beta, float(scale)).astype(q.dtype)


@kernel("kda_attention")
def _kda_attention(ctx, ins, attrs):
    """Q, K, G [B, T, H, Dk], V [B, T, H, Dv], Beta [B, T, H] -> Out [B,
    T, H, Dv]: the gated delta rule with a per-channel decay, causal, zero
    state before the sequence, in chunks; float32 inside, Out in Q's dtype.
    Dispatched through the kern registry (whose STATS count the calls and
    whose reference is the token-by-token recurrence) to the Mosaic
    kernels, `pallas.kda.try_kda`; where that says None, `kda_chunked`."""
    args = (ins["Q"][0], ins["K"][0], ins["V"][0], ins["G"][0],
            ins["Beta"][0])
    scale = attrs.get("scale")
    out = ctx.accel("kda_attention")(*args, scale=scale)
    if out is None:
        out = kda_chunked(*args, scale=scale)
    return {"Out": [out]}


def selective_scan_recurrent(x, dt, A_log, B, C, D):
    """The diagonal selective scan of a Mamba-1 mixer, token by token: per
    channel c and state n, with A = -exp(A_log) and h_{-1} = 0,

        h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
        y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

    x, dt [B, T, C], A_log [C, N], B, C [B, T, N], D [C] -> y [B, T, C] in
    x's dtype; float32 inside. The definition, the registry's reference
    and the op's path off the TPU; walked in blocks of SCAN_CHUNK tokens
    that the backward pass recomputes, so that a gradient keeps one state
    a block and never a [T, C, N] tensor."""
    f32 = jnp.float32
    Bsz, T, Ch = x.shape
    a = -jnp.exp(A_log.astype(f32))

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt[..., None] * a) * h \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, ct, precision=HI)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(step, h, xs)

    pad = (-T) % SCAN_CHUNK          # dt = 0: the state stands still

    def blocks(v):
        v = jnp.moveaxis(v.astype(f32), 1, 0)
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape((-1, SCAN_CHUNK) + v.shape[1:])

    h0 = jnp.zeros((Bsz, Ch, A_log.shape[-1]), f32)
    _, y = jax.lax.scan(block, h0, tuple(blocks(v) for v in (x, dt, B, C)))
    y = jnp.moveaxis(y.reshape((-1,) + y.shape[2:])[:T], 0, 1)
    return (y + D.astype(f32) * x.astype(f32)).astype(x.dtype)


@kernel("selective_scan")
def _selective_scan(ctx, ins, attrs):
    """X, Dt [B, T, C], ALog [C, N], B, C [B, T, N], D [C] -> Out [B, T, C]
    in X's dtype: the diagonal selective scan, zero state before the
    sequence; float32 inside. Dispatched through the kern registry (whose
    STATS count the calls and whose reference is the token-by-token
    recurrence) to the Mosaic kernels, `pallas.selective_scan.
    try_selective_scan`; where that says None, the recurrence itself."""
    args = tuple(ins[s][0] for s in ("X", "Dt", "ALog", "B", "C", "D"))
    out = ctx.accel("selective_scan")(*args)
    if out is None:
        out = selective_scan_recurrent(*args)
    return {"Out": [out]}


@kernel("kda_gate")
def _kda_gate(ctx, ins, attrs):
    """X [B, T, H, D], ALog [H], DtBias [H, D] -> Out = -exp(ALog) *
    softplus(X + DtBias), float32: the log of a decay in (0, 1) per key
    channel. float32 whatever X is run in: the scan sums it over a chunk."""
    x = ins["X"][0].astype(jnp.float32)
    a = jnp.exp(ins["ALog"][0].astype(jnp.float32))
    out = -a[:, None] * jax.nn.softplus(x + ins["DtBias"][0].astype(
        jnp.float32))
    return {"Out": [out]}


@kernel("l2_norm")
def _l2_norm(ctx, ins, attrs):
    """Out = X / sqrt(sum(X^2, last axis) + epsilon), in float32."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    ss = jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(ss + attrs.get("epsilon", 1e-6))
    return {"Out": [out.astype(x.dtype)]}
