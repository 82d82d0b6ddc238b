"""Pallas kernels for the gated delta rule with a per-channel decay
(`kda_attention`; the rule and its symbols: ops/kernels_scan.py).

The chunked form of `kernels_scan.kda_chunked`, a chunk of `CHUNK` rows in
VMEM at a time. One grid step is one chunk of `HEADS_PER_STEP` heads of
one batch row; a head's chunks follow one another on the last, sequential
grid axis and the states S [Dk, Dv] (float32) stay in a scratch buffer
between them. Only the op's operands, its output and one state a (head,
chunk) cross HBM:

    kda_fwd   o, and the state every chunk starts on (what the backward
              keeps: [B, H, T / CHUNK, Dk, Dv] float32)
    kda_bwd   walks the chunks in reverse with dS resident; per chunk it
              rebuilds A, P, (I + A)^-1 and U from the chunk's operands
              and the saved state, and gives dq, dk, dv, dg, dbeta. The
              chunk's gradient is derived by hand (below), not traced.

Inside a chunk, by the composition's own rule: G is the running sum of g
(log2(CHUNK) shifted adds, no product); a sub-block of `SUB` rows against
EARLIER rows is a product of two factors taken relative to the
sub-block's first row; inside a sub-block the exponent is the explicit
difference G_i - G_j, masked before the exponential, one column j of all
sub-blocks a loop step (`lax.fori_loop` of SUB steps, unrolled: a step is
bound by its lane reductions, which Mosaic then schedules under the
MXU's products: 7.1 -> 4.3 ms a scan of the cell, forward + backward,
for 3 s of compilation). No exponent is ever positive. The
unit-lower-triangular solve is forward substitution on the SUB x SUB
diagonal blocks (column by column, in the same loop, on the identity: the
blocks' inverses) and products between blocks (pairs of inverted blocks
merge, [[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c a^-1, b^-1]], up to the
chunk). W is never formed: with T = (I + A)^-1,

    U  = T beta (v - (k e^G) S)          (= Uv - W S)
    o  = scale ((q e^G) S + P U)
    S' = diag(e^{G_C}) S + Kh^T U        (no [Dk, Dk] matrix)

Every product is float32 x float32 at Precision.HIGHEST (the
configuration's precision for this scan); q, k, v are widened inside.

The backward of one chunk, with Z = v - (k e^G) S and do' = scale do:

    dU  = P^T do' + Kh dS'          dP = tril(do' U^T)
    dZ  = T^T dU                    dA = -stril(dZ U^T)  (A with its beta)
    dS  = diag(e^{G_C}) dS' + (q e^G)^T do' - (k e^G)^T beta dZ
    dq  = (do' S^T) e^G + dq_P      dv = beta dZ
    dKg = -(beta dZ) S^T            dKh = U dS'^T
    dk  = dKh e^{G_C - G} + dKg e^G + dk_row + dk_col
    dG  = q e^G (do' S^T) + k e^G dKg - Kh dKh
          + k (dk_row - dk_col) + q dq_P            (+ dG_C on row C)
    dg_i = sum of dG from row i to the chunk's end
    dbeta = rowsum(dZ Z) + rowsum(dA A / beta)

where dq_P, dk_row (row i) and dk_col (column j) are the sums of
dP_ij k_j, dA_ij k_j and dA_ij k_i + dP_ij q_i against exp(G_i - G_j),
taken with the forward's own two-factor / explicit-difference rule.
"""
import functools

import jax
import jax.numpy as jnp

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

from ..kernels_scan import CHUNK, SUB
from ..registry import active

__all__ = ["try_kda", "kda", "supports", "STATS"]

# trace-time evidence that the kernels were taken (one count a call site
# a trace, as flash_attention.STATS)
STATS = {"pallas_calls": 0}

_LANES = 128
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_SHIFT = SUB.bit_length() - 1
assert 1 << _SHIFT == SUB and CHUNK % SUB == 0


def _mm(a, b, dims=_NN):
    """float32 products at full precision, one a head of the step: a b
    (_NN), a b^T (_NT) or a^T b (_TN) over the last two axes of [n, ., .]
    operands (a 2-D operand is every head's)."""
    n = max(a.shape[0] if a.ndim == 3 else 1, b.shape[0] if b.ndim == 3
            else 1)
    return jnp.stack([jax.lax.dot_general(
        a[h] if a.ndim == 3 else a, b[h] if b.ndim == 3 else b,
        (dims, ((), ())), precision=_HI, preferred_element_type=_F32)
        for h in range(n)])


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _running_sum(x, reverse=False):
    """The sum of rows 0..i of x [n, C, D] at row i (rows i..C-1 with
    `reverse`): log2(C) shifted adds, no product."""
    C = x.shape[1]
    row = _iota((C, 1), 0)
    shift = 1
    while shift < C:
        if reverse:
            x = x + jnp.where(row < C - shift,
                              pltpu.roll(x, C - shift, axis=1), 0.0)
        else:
            x = x + jnp.where(row >= shift, pltpu.roll(x, shift, axis=1),
                              0.0)
        shift *= 2
    return x


def _eye(m):
    return _iota((m, m), 0) == _iota((m, m), 1)


def _to_col(row):
    """[n, 1, m] -> [n, m, 1], exactly (the diagonal of the row's
    broadcast)."""
    return jnp.sum(jnp.where(_eye(row.shape[-1]), row, 0.0), axis=2,
                   keepdims=True)


def _to_row(col):
    return jnp.sum(jnp.where(_eye(col.shape[-2]), col, 0.0), axis=1,
                   keepdims=True)


def _sub_rows(ref, jj):
    """Row jj of every sub-block of ref [n, C, D], broadcast over the
    sub-block's rows: [n, C, D]."""
    n, C, D = ref.shape
    return jnp.concatenate(
        [jnp.broadcast_to(ref[:, pl.ds(s * SUB + jj, 1), :], (n, SUB, D))
         for s in range(C // SUB)], axis=1)


def _sub_sums(x):
    """The sum over every sub-block's rows, broadcast back: [n, C, D]."""
    n, C, D = x.shape
    return jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(x[:, r:r + SUB], axis=1, keepdims=True),
                          (n, SUB, D)) for r in range(0, C, SUB)], axis=1)


def _earlier(k, G, r):
    """The two factors of sub-block [r, r + SUB) against the rows before
    r, relative to row r: left [n, SUB, D] and rf [n, C, D] (0 from row r
    on), both exponents <= 0."""
    first = G[:, r:r + 1]
    left = jnp.exp(G[:, r:r + SUB] - first)
    rf = jnp.exp(jnp.where(_iota((k.shape[1], 1), 0) < r, first - G,
                           -jnp.inf))
    return left, rf


def _chunk_forward(q, k, g, beta, kf_ref, G_ref, inv_ref):
    """What forward and backward both build of one chunk of n heads, S
    aside. q, k, g [n, C, Dk] float32, beta [n, C, 1];
    kf_ref, G_ref [n, C, Dk] and inv_ref [n, C, C] are scratch. Returns a
    dict of the chunk's tensors."""
    n, C, _ = k.shape
    ns = C // SUB
    G = _running_sum(g)
    kf_ref[...] = k
    G_ref[...] = G
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    local = _iota((C, 1), 0) & (SUB - 1)
    # the column of sub-block s's own block that a loop step fills
    own = col - ((row >> _SHIFT) << _SHIFT)

    # a sub-block's rows against the chunk's earlier rows
    zero = jnp.zeros((n, SUB, C), _F32)
    A_rows, P_rows = [zero], [zero]
    for s in range(1, ns):
        r = s * SUB
        left, rf = _earlier(k, G, r)
        both = _mm(jnp.concatenate([k[:, r:r + SUB] * left,
                                    q[:, r:r + SUB] * left], axis=1),
                   k * rf, _NT)
        A_rows.append(both[:, :SUB])
        P_rows.append(both[:, SUB:])
    A = jnp.concatenate(A_rows, axis=1)
    P = jnp.concatenate(P_rows, axis=1)

    # inside a sub-block: column jj of every sub-block a step, and with
    # it column jj of the forward substitution (I + A_ss) X = I
    inv_ref[...] = jnp.broadcast_to(jnp.where(_eye(C), 1.0, 0.0),
                                    (n, C, C)).astype(_F32)

    def column(jj, AP):
        A, P = AP
        E = jnp.exp(jnp.where(local >= jj, G - _sub_rows(G_ref, jj),
                              -jnp.inf))
        kE = _sub_rows(kf_ref, jj) * E
        a = jnp.sum(k * kE, axis=2, keepdims=True)
        p = jnp.sum(q * kE, axis=2, keepdims=True)
        at = own == jj
        a = jnp.where(local > jj, a, 0.0)
        inv_ref[...] = inv_ref[...] - (beta * a) * _sub_rows(inv_ref, jj)
        return jnp.where(at, a, A), jnp.where(at, p, P)

    A, P = jax.lax.fori_loop(0, SUB, column, (A, P), unroll=True)
    A = jnp.where(row > col, A, 0.0)          # before beta: dbeta needs it
    bA = beta * A

    # the blocks' inverses -> (I + A)^-1: pairs of inverted blocks merge,
    # [[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c a^-1, b^-1]], up to C
    inv = inv_ref[...]
    for lo in range(_SHIFT, C.bit_length() - 1):
        c_block = ((row >> (lo + 1)) == (col >> (lo + 1))) \
            & ((row >> lo) != (col >> lo))
        inv = inv - _mm(_mm(inv, jnp.where(c_block, bA, 0.0)), inv)

    eG = jnp.exp(G)
    total = G[:, C - 1:C]
    dec = jnp.exp(total - G)
    return dict(G=G, eG=eG, A=A, P=P, inv=inv, Kg=k * eG, Qg=q * eG,
                total=total, dec=dec, Kh=k * dec, local=local, own=own)


def _heads(ref, n):
    """[C, n * D] (n heads side by side in the lanes) -> [n, C, D]
    float32."""
    D = ref.shape[1] // n
    return jnp.stack([ref[:, h * D:(h + 1) * D].astype(_F32)
                      for h in range(n)])


def _put_heads(ref, x):
    D = x.shape[2]
    for h in range(x.shape[0]):
        ref[:, h * D:(h + 1) * D] = x[h].astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref,
                S, kf_ref, G_ref, inv_ref, *, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        S[...] = jnp.zeros_like(S)

    n = S.shape[0]
    q, k, v, g = (_heads(x, n) for x in (q_ref, k_ref, v_ref, g_ref))
    beta = _to_col(beta_ref[...])
    f = _chunk_forward(q, k, g, beta, kf_ref, G_ref, inv_ref)
    C = k.shape[1]
    S0 = S[...]
    s_ref[...] = S0
    # Kg and Qg against the state in one product: the state is loaded once
    on_S = _mm(jnp.concatenate([f["Kg"], f["Qg"]], axis=1), S0)
    U = _mm(f["inv"], beta * (v - on_S[:, :C]))
    _put_heads(o_ref, scale * (on_S[:, C:] + _mm(f["P"], U)))
    S[...] = _to_col(jnp.exp(f["total"])) * S0 + _mm(f["Kh"], U, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, s_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                dS, kf_ref, G_ref, inv_ref, *, scale):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dS[...] = jnp.zeros_like(dS)

    n = dS.shape[0]
    q, k, v, g = (_heads(x, n) for x in (q_ref, k_ref, v_ref, g_ref))
    beta = _to_col(beta_ref[...])
    f = _chunk_forward(q, k, g, beta, kf_ref, G_ref, inv_ref)
    C = k.shape[1]
    G, eG, A, P, inv = (f[x] for x in ("G", "eG", "A", "P", "inv"))
    Kg, Qg, Kh, dec, local, own = (f[x] for x in ("Kg", "Qg", "Kh", "dec",
                                                  "local", "own"))
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    S0 = s_ref[...]
    dSn = dS[...]
    dos = scale * _heads(do_ref, n)
    Z = v - _mm(Kg, S0)
    U = _mm(inv, beta * Z)

    dP = jnp.where(row >= col, _mm(dos, U, _NT), 0.0)
    dU = _mm(P, dos, _TN) + _mm(Kh, dSn)
    dKh = _mm(U, dSn, _NT)
    dZ = _mm(inv, dU, _TN)
    bdZ = beta * dZ
    # what meets the state, in one product a side of it
    d_out = jnp.concatenate([dos, bdZ], axis=1)
    on_S = _mm(d_out, S0, _NT)
    dQg, dKg = on_S[:, :C], -on_S[:, C:]
    e_total = jnp.exp(f["total"])
    dS[...] = _to_col(e_total) * dSn \
        + _mm(jnp.concatenate([Qg, -Kg], axis=1), d_out, _TN)
    # d(total): through diag(e^total) S (a row of per-channel sums, by a
    # product with ones) and through Kh
    t = dKh * Kh
    d_total = e_total * _mm(jnp.ones((8, S0.shape[2]), _F32), S0 * dSn,
                            _NT)[:, :1] + jnp.sum(t, axis=1, keepdims=True)
    dA = jnp.where(row > col, -_mm(dZ, U, _NT), 0.0)
    dbeta = jnp.sum(dZ * Z, axis=2, keepdims=True) \
        + jnp.sum(dA * A, axis=2, keepdims=True)
    dA = beta * dA

    # through A and P: the forward's two rules, transposed
    ns = C // SUB
    zero = jnp.zeros((n, SUB, k.shape[2]), _F32)
    dkr_rows, dqp_rows = [zero], [zero]
    dkc = jnp.zeros_like(k)
    for s in range(1, ns):
        r = s * SUB
        left, rf = _earlier(k, G, r)
        d_both = jnp.concatenate([dA[:, r:r + SUB], dP[:, r:r + SUB]],
                                 axis=1)
        rows = _mm(d_both, k * rf)
        dkr_rows.append(left * rows[:, :SUB])
        dqp_rows.append(left * rows[:, SUB:])
        dkc = dkc + rf * _mm(d_both, jnp.concatenate(
            [k[:, r:r + SUB] * left, q[:, r:r + SUB] * left], axis=1), _TN)
    dkr = jnp.concatenate(dkr_rows, axis=1)
    dqp = jnp.concatenate(dqp_rows, axis=1)

    def column(jj, acc):
        dkr, dqp, dkc = acc
        E = jnp.exp(jnp.where(local >= jj, G - _sub_rows(G_ref, jj),
                              -jnp.inf))
        kE = _sub_rows(kf_ref, jj) * E
        at = own == jj
        a = jnp.sum(jnp.where(at, dA, 0.0), axis=2, keepdims=True)
        p = jnp.sum(jnp.where(at, dP, 0.0), axis=2, keepdims=True)
        into_j = _sub_sums((a * k + p * q) * E)
        return (dkr + a * kE, dqp + p * kE,
                jnp.where(local == jj, dkc + into_j, dkc))

    dkr, dqp, dkc = jax.lax.fori_loop(0, SUB, column, (dkr, dqp, dkc),
                                      unroll=True)

    _put_heads(dq_ref, dQg * eG + dqp)
    _put_heads(dk_ref, dKh * dec + dKg * eG + dkr + dkc)
    _put_heads(dv_ref, bdZ)
    dG = dQg * Qg + dKg * Kg - t + k * (dkr - dkc) + q * dqp
    dG = jnp.where(_iota((C, 1), 0) == C - 1, dG + d_total, dG)
    _put_heads(dg_ref, _running_sum(dG, reverse=True))
    dbeta_ref[...] = _to_row(dbeta)


def _layout(q, k, v, g, beta):
    """[B, T, H, D] operands -> [B, Tp, H * D] (free reshapes; Tp the
    next multiple of CHUNK, padded with g = 0, beta = 0: the state stands
    still) and beta -> [B, H, Tp / CHUNK, 1, CHUNK] float32."""
    B, T, H, _ = q.shape
    pad = (-T) % CHUNK

    def rows(x):
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(B, T + pad, -1)

    beta = rows(beta.astype(_F32)).reshape(B, (T + pad) // CHUNK, CHUNK, H)
    return (rows(q), rows(k), rows(v), rows(g.astype(_F32)),
            jnp.moveaxis(beta, 3, 1)[:, :, :, None, :])


# heads a grid step: their chains of small dependent products interleave
# in one instruction stream (a single head's step waits on the MXU's
# latency much of the time: 5.4 -> 4.3 ms a scan of the cell at two, 4.1
# at four; my chip runs, PR 35)
HEADS_PER_STEP = 2


def _heads_per_step(H):
    return max(n for n in range(1, HEADS_PER_STEP + 1) if H % n == 0)


def _plan(q, k, v, g, beta, reverse):
    """What both calls hand `pl.pallas_call` beside their kernel: the
    operands as rows ([B, Tp, H * D], beta [B, H, nc, 1, CHUNK]), the
    BlockSpecs of a (batch, n heads, chunk) grid step (a [CHUNK, n * D]
    block of keys, of values, the chunk's betas, its states; the chunks
    walked backwards with `reverse`), and grid, scratch and semantics."""
    B, _, H, Dk = q.shape
    Dv = v.shape[-1]
    rows = _layout(q, k, v, g, beta)
    nc = rows[0].shape[1] // CHUNK
    n = _heads_per_step(H)

    def chunk(c):
        return nc - 1 - c if reverse else c

    def lanes(D):
        return pl.BlockSpec((None, CHUNK, n * D),
                            lambda b, h, c: (b, chunk(c), h))

    def per_chunk(*block):
        return pl.BlockSpec((None, n, None) + block,
                            lambda b, h, c: (b, h, chunk(c), 0, 0))

    specs = lanes(Dk), lanes(Dv), per_chunk(1, CHUNK), per_chunk(Dk, Dv)
    common = dict(
        grid=(B, H // n, nc),
        scratch_shapes=[pltpu.VMEM((n, Dk, Dv), _F32),        # S or dS
                        pltpu.VMEM((n, CHUNK, Dk), _F32),     # k
                        pltpu.VMEM((n, CHUNK, Dk), _F32),     # G
                        pltpu.VMEM((n, CHUNK, CHUNK), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    return rows, specs, common


@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd_call(q, k, v, g, beta, scale, interpret):
    """(o [B, T, H, Dv] in q's dtype, the state each chunk starts on
    [B, H, nc, Dk, Dv] float32). Jitted so that a model's layers of one
    shape trace and lower the kernel once."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    rows, (rk, rv, bs, st), common = _plan(q, k, v, g, beta, reverse=False)
    Tp = rows[0].shape[1]
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        in_specs=[rk, rk, rv, rk, bs],
        out_specs=[rv, st],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Tp // CHUNK, Dk, Dv), _F32)],
        name="kda_fwd", interpret=interpret, **common)(*rows)
    return o.reshape(B, Tp, H, Dv)[:, :T], states


@functools.partial(jax.jit, static_argnums=(2, 3))
def _bwd_call(res, do, scale, interpret):
    q, k, v, g, beta, states = res
    B, T, H, _ = q.shape
    rows, (rk, rv, bs, st), common = _plan(q, k, v, g, beta, reverse=True)
    Tp = rows[0].shape[1]
    dor = jnp.pad(do, ((0, 0), (0, Tp - T), (0, 0), (0, 0))).reshape(
        B, Tp, -1)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        in_specs=[rk, rk, rv, rk, bs, rv, st],
        out_specs=[rk, rk, rv, rk, bs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in rows],
        name="kda_bwd", interpret=interpret, **common)(*rows, dor, states)
    dbeta = jnp.moveaxis(dbeta[:, :, :, 0, :], 1, 3).reshape(B, Tp, H)

    def back(x, like):
        return x.reshape((B, Tp) + like.shape[2:])[:, :T].astype(like.dtype)

    return (back(dq, q), back(dk, k), back(dv, v), back(dg, g),
            back(dbeta, beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, scale, interpret):
    return _fwd_call(q, k, v, g, beta, scale, interpret)[0]


def _kda_fwd(q, k, v, g, beta, scale, interpret):
    o, states = _fwd_call(q, k, v, g, beta, scale, interpret)
    return o, (q, k, v, g, beta, states)


def _kda_bwd(scale, interpret, res, do):
    return _bwd_call(res, do, scale, interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta, scale=None, interpret=False):
    """q, k, g [B, T, H, Dk], v [B, T, H, Dv], beta [B, T, H] -> o [B, T,
    H, Dv] in q's dtype. Differentiable in all five (custom_vjp)."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    STATS["pallas_calls"] += 1
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _kda(q, k, v, g, beta, float(scale), bool(interpret))


def supports(q, k, v, g, beta, scale=None, interpret=False):
    """Static shape test (the registry's probe): five arrays of one
    batch, length and head count, and whole vregs of key and value
    channels: Dk and Dv multiples of 128. Any B, H, T."""
    if not _HAS_PALLAS or q.ndim != 4 or not (q.shape == k.shape
                                              == g.shape):
        return False
    if v.ndim != 4 or v.shape[:3] != q.shape[:3] \
            or beta.shape != q.shape[:3]:
        return False
    return q.shape[-1] % _LANES == 0 and v.shape[-1] % _LANES == 0


def try_kda(q, k, v, g, beta, scale=None):
    """The dispatch entry (try_* convention): o through the kernels, or
    None and the op lowers `kernels_scan.kda_chunked`."""
    use_pallas, interpret = active()
    if not use_pallas or not supports(q, k, v, g, beta):
        return None
    return kda(q, k, v, g, beta, scale, interpret)
