"""Pallas kernels for the diagonal selective scan of a Mamba-1 mixer
(`selective_scan`; the recurrence and its symbols: ops/kernels_scan.py).

The decay exp(dt_t[c] A[c, n]) differs per channel and state and depends
on the input, so the scan does not factor into matrix products: it is
elementwise work on the VPU and the EUP, sequential in time. One grid step
is one chunk of `CHUNK` tokens of one block of channels of one batch row;
a block's chunks follow one another on the last, sequential grid axis and
its state h [N, c_blk] (float32, states in the sublanes, channels in the
lanes) stays in a VMEM scratch buffer between them. Only the op's operands,
its output and one state a chunk cross HBM:

    selective_scan_fwd   y, and the state every chunk starts on (what the
                         backward keeps: [B, T / CHUNK, N, C] float32)
    selective_scan_bwd   walks the chunks in reverse with dh resident; per
                         chunk it first rebuilds the chunk's states from
                         the saved start (into VMEM), then walks its tokens
                         backwards and gives dx, ddt, dB, dC (float32
                         partial sums over the block's channels, summed
                         outside the kernel), dA_log and dD

A token is a few [N, c_blk] slabs: with a = -exp(A_log) (a [N, c_blk]
block of A's transpose), u_t = dt_t x_t, e_t = exp(dt_t a),

    h_t = e_t h_{t-1} + B_t u_t          y_t = sum_n C_t h_t + D x_t

and backwards, with lam_t the gradient reaching h_t (C_t dy_t, plus
e_{t+1} lam_{t+1} from the token after it),

    dC_t = sum_c dy_t h_t     dB_t = sum_c lam_t u_t    du_t = sum_n lam_t B_t
    g_t  = lam_t h_{t-1} e_t  (the gradient of dt_t a)
    ddt_t = sum_n g_t a + du_t x_t       dx_t = D dy_t + du_t dt_t
    dA_log = sum_t g_t dt_t a             dD = sum_t dy_t x_t

B_t and C_t arrive as rows of N lanes and are turned into columns exactly
(the diagonal of the row's broadcast, summed over the lanes by the XLU).
Tokens are walked several at a time in straight-line code, so that one
token's exponentials, loads and lane sums overlap the previous token's
chain of updates: the forward `FWD_UNROLL` a loop step, the backward
`BWD_UNROLL`, where a chunk holds a whole number of them (else `UNROLL`).
The backward makes a loop step's columns one loop step before it (two
slots in VMEM), so that their lane sums overlap a whole step of token work
instead of holding up its start. Every float32 operation and its order is
what the narrower walk does: the outputs do not depend on the unroll
(bit for bit on the chip: PERF.md 6).
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

from ..kernels_scan import SCAN_CHUNK as CHUNK
from ..registry import active

__all__ = ["try_selective_scan", "selective_scan", "supports", "STATS",
           "CHUNK"]

# trace-time evidence that the kernels were taken (one count a call site a
# trace, as kda.STATS)
STATS = {"pallas_calls": 0}

# tokens of one loop step, in straight-line code: the forward's and the
# backward's where a chunk holds a whole number of them, else UNROLL
UNROLL = 8
FWD_UNROLL = 32
BWD_UNROLL = 32
_LANES = 128
_BLOCK_LANES = 256       # the widest block of channels a grid step holds
_F32 = jnp.float32


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _col(row):
    """[1, n] -> [n, 1], exactly (the diagonal of the row's broadcast)."""
    n = row.shape[-1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col):
    """[n, 1] -> [1, n], exactly."""
    n = col.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _token(ref, t):
    return ref[pl.ds(t, 1), :]


def _unroll(L, wide):
    """`wide` tokens a loop step where a chunk of L holds a whole number of
    them, else UNROLL."""
    return wide if L % wide == 0 else UNROLL


class _Columns:
    """The backward's columns of B_t and C_t (one for each of `refs`), made
    a loop step before the step that uses them: slot `i % 2` of `cs`
    [2, >= len(refs) * u * N, 128] holds loop step i's, each `_col` broadcast
    over 128 lanes, and step i fills the other slot for the next step, so
    that the XLU's lane sums overlap a step of token work instead of
    starting each step's chain of updates."""

    def __init__(self, cs, refs, u, shape):
        self.cs, self.refs, self.u = cs, refs, u
        self.n, self.lanes = shape

    def fill(self, slot, base):
        for q, r in enumerate(self.refs):
            for j in range(self.u):
                self.cs[slot, pl.ds((q * self.u + j) * self.n, self.n), :] = \
                    jnp.broadcast_to(_col(_token(r, base + j)),
                                     (self.n, _LANES))

    def at(self, slot, j):
        """Token j of the step's columns, [N, lanes] each."""
        return [jnp.concatenate(
            [self.cs[slot, pl.ds((q * self.u + j) * self.n, self.n), :]]
            * (self.lanes // _LANES), axis=1) for q in range(len(self.refs))]


def _fwd_kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, y_ref, s_ref,
                h_scr, xs, ys):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    h0 = h_scr[...]
    s_ref[...] = h0
    a = -jnp.exp(alog_ref[...])
    d = d_ref[...]
    xs[...] = x_ref[...].astype(_F32)
    L = xs.shape[0]
    u = _unroll(L, FWD_UNROLL)

    def step(i, h):
        base = pl.multiple_of(i * u, u)
        for j in range(u):
            t = base + j
            dt, x = _token(dt_ref, t), _token(xs, t)
            h = jnp.exp(dt * a) * h + _col(_token(b_ref, t)) * (dt * x)
            ys[pl.ds(t, 1), :] = jnp.sum(_col(_token(c_ref, t)) * h, axis=0,
                                         keepdims=True) + d * x
        return h

    h_scr[...] = jax.lax.fori_loop(0, L // u, step, h0)
    y_ref[...] = ys[...].astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, dy_ref, s_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, dalog_ref, dd_ref,
                lam_scr, da_scr, dd_scr, hs, xs, dys, dxs, cs):
    @pl.when(pl.program_id(2) == 0)
    def _():
        lam_scr[...] = jnp.zeros_like(lam_scr)
        da_scr[...] = jnp.zeros_like(da_scr)
        dd_scr[...] = jnp.zeros_like(dd_scr)

    a = -jnp.exp(alog_ref[...])
    d = d_ref[...]
    xs[...] = x_ref[...].astype(_F32)
    dys[...] = dy_ref[...].astype(_F32)
    L = xs.shape[0]
    u = _unroll(L, BWD_UNROLL)
    steps = L // u
    bcols = _Columns(cs, (b_ref,), u, a.shape)
    bcols.fill(0, 0)

    def rebuild(i, h):                  # hs[t]: the state before token t
        base = pl.multiple_of(i * u, u)
        slot = i % 2
        for j in range(u):
            t = base + j
            hs[t] = h
            dt, x = _token(dt_ref, t), _token(xs, t)
            b, = bcols.at(slot, j)
            h = jnp.exp(dt * a) * h + b * (dt * x)
        bcols.fill(1 - slot, jnp.minimum(base + u, L - u))
        return h

    jax.lax.fori_loop(0, steps, rebuild, s_ref[...])
    cols = _Columns(cs, (b_ref, c_ref), u, a.shape)
    cols.fill(0, L - u)

    def step(i, carry):
        lam_next, da, dd = carry        # lam_next: e_{t+1} lam_{t+1}
        base = pl.multiple_of((steps - 1 - i) * u, u)
        slot = i % 2
        for j in reversed(range(u)):
            t = base + j
            dt, x, dy = _token(dt_ref, t), _token(xs, t), _token(dys, t)
            b, c = cols.at(slot, j)
            e = jnp.exp(dt * a)
            h_prev = hs[t]
            u_t = dt * x
            h = e * h_prev + b * u_t
            lam = c * dy + lam_next
            dc_ref[pl.ds(t, 1), :] = _row(jnp.sum(dy * h, axis=1,
                                                  keepdims=True))
            db_ref[pl.ds(t, 1), :] = _row(jnp.sum(lam * u_t, axis=1,
                                                  keepdims=True))
            du = jnp.sum(lam * b, axis=0, keepdims=True)
            g = lam * h_prev * e
            ddt_ref[pl.ds(t, 1), :] = jnp.sum(g * a, axis=0,
                                              keepdims=True) + du * x
            dxs[pl.ds(t, 1), :] = d * dy + du * dt
            da = da + g * dt
            dd = dd + dy * x
            lam_next = e * lam
        cols.fill(1 - slot, jnp.maximum(base - u, 0))
        return lam_next, da, dd

    lam, da, dd = jax.lax.fori_loop(
        0, steps, step, (lam_scr[...], da_scr[...], dd_scr[...]))
    lam_scr[...] = lam
    da_scr[...] = da
    dd_scr[...] = dd
    dx_ref[...] = dxs[...].astype(dx_ref.dtype)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        dalog_ref[...] = da * a
        dd_ref[...] = dd


def _blocks(T, C):
    """(tokens a chunk, channels a block): the shapes decide. A chunk is
    CHUNK tokens, or the whole (8-aligned) sequence where that is shorter;
    a block the widest multiple of 128 lanes up to _BLOCK_LANES that
    divides C."""
    L = min(CHUNK, -(-T // 8) * 8)
    cb = max(w for w in range(_LANES, _BLOCK_LANES + 1, _LANES)
             if C % w == 0)
    return L, cb


def _plan(x, dt, A_log, B, C, D, reverse):
    """What both calls hand `pl.pallas_call` beside their kernel: the
    operands padded to whole chunks (dt = 0 there: the state stands still)
    with B, C float32, A_log as its float32 transpose [N, C] and D as
    [1, C]; the BlockSpecs of a (batch row, channel block, chunk) grid
    step, the chunks walked backwards with `reverse`; grid and semantics."""
    Bsz, T, Ch = x.shape
    N = B.shape[-1]
    L, cb = _blocks(T, Ch)
    pad = (-T) % L
    nc = (T + pad) // L

    def rows(v, dtype=None):
        v = v if dtype is None else v.astype(dtype)
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    ops = (rows(x), rows(dt, _F32), rows(B, _F32), rows(C, _F32),
           A_log.astype(_F32).T, D.astype(_F32).reshape(1, Ch))

    def chunk(c):
        return nc - 1 - c if reverse else c

    specs = dict(
        lanes=pl.BlockSpec((None, L, cb), lambda b, k, c: (b, chunk(c), k)),
        tok=pl.BlockSpec((None, L, N), lambda b, k, c: (b, chunk(c), 0)),
        par=pl.BlockSpec((N, cb), lambda b, k, c: (0, k)),
        d=pl.BlockSpec((1, cb), lambda b, k, c: (0, k)),
        saved=pl.BlockSpec((None, None, N, cb),
                           lambda b, k, c: (b, chunk(c), 0, k)),
        part=pl.BlockSpec((None, None, L, N),
                          lambda b, k, c: (b, k, chunk(c), 0)),
        per_row=pl.BlockSpec((None, N, cb), lambda b, k, c: (b, 0, k)),
        per_row_d=pl.BlockSpec((None, 1, cb), lambda b, k, c: (b, 0, k)))
    common = dict(
        grid=(Bsz, Ch // cb, nc),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    return ops, specs, common, (L, cb, nc, pad)


@functools.partial(jax.jit, static_argnums=(6,))
def _fwd_call(x, dt, A_log, B, C, D, interpret):
    """(y [B, T, C] in x's dtype, the state each chunk starts on [B, nc,
    N, C] float32). Jitted so that a model's layers of one shape trace and
    lower the kernel once."""
    Bsz, T, Ch = x.shape
    N = B.shape[-1]
    ops, s, common, (L, cb, nc, pad) = _plan(x, dt, A_log, B, C, D, False)
    y, states = pl.pallas_call(
        _fwd_kernel,
        in_specs=[s["lanes"], s["lanes"], s["tok"], s["tok"], s["par"],
                  s["d"]],
        out_specs=[s["lanes"], s["saved"]],
        out_shape=[jax.ShapeDtypeStruct((Bsz, T + pad, Ch), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, nc, N, Ch), _F32)],
        scratch_shapes=[pltpu.VMEM((N, cb), _F32),           # h
                        pltpu.VMEM((L, cb), _F32),           # x
                        pltpu.VMEM((L, cb), _F32)],          # y
        name="selective_scan_fwd", interpret=interpret, **common)(*ops)
    return y[:, :T], states


@functools.partial(jax.jit, static_argnums=(2,))
def _bwd_call(res, dy, interpret):
    x, dt, A_log, B, C, D, states = res
    Bsz, T, Ch = x.shape
    N = B.shape[-1]
    ops, s, common, (L, cb, nc, pad) = _plan(x, dt, A_log, B, C, D, True)
    dyp = jnp.pad(dy, ((0, 0), (0, pad), (0, 0))) if pad else dy
    nb = Ch // cb
    dx, ddt, dB, dC, dA_log, dD = pl.pallas_call(
        _bwd_kernel,
        in_specs=[s["lanes"], s["lanes"], s["tok"], s["tok"], s["par"],
                  s["d"], s["lanes"], s["saved"]],
        out_specs=[s["lanes"], s["lanes"], s["part"], s["part"],
                   s["per_row"], s["per_row_d"]],
        out_shape=[jax.ShapeDtypeStruct((Bsz, T + pad, Ch), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, T + pad, Ch), _F32),
                   jax.ShapeDtypeStruct((Bsz, nb, T + pad, N), _F32),
                   jax.ShapeDtypeStruct((Bsz, nb, T + pad, N), _F32),
                   jax.ShapeDtypeStruct((Bsz, N, Ch), _F32),
                   jax.ShapeDtypeStruct((Bsz, 1, Ch), _F32)],
        scratch_shapes=[pltpu.VMEM((N, cb), _F32),           # lam
                        pltpu.VMEM((N, cb), _F32),           # dA
                        pltpu.VMEM((1, cb), _F32),           # dD
                        pltpu.VMEM((L, N, cb), _F32),        # the states
                        pltpu.VMEM((L, cb), _F32),           # x
                        pltpu.VMEM((L, cb), _F32),           # dy
                        pltpu.VMEM((L, cb), _F32),           # dx
                        pltpu.VMEM((2, 2 * _unroll(L, BWD_UNROLL) * N,
                                    _LANES), _F32)],         # columns
        name="selective_scan_bwd", interpret=interpret,
        **common)(*ops, dyp, states)
    return (dx[:, :T], ddt[:, :T].astype(dt.dtype),
            jnp.sum(dA_log, axis=0).T.astype(A_log.dtype),
            jnp.sum(dB, axis=1)[:, :T].astype(B.dtype),
            jnp.sum(dC, axis=1)[:, :T].astype(C.dtype),
            jnp.sum(dD, axis=(0, 1)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, A_log, B, C, D, interpret):
    return _fwd_call(x, dt, A_log, B, C, D, interpret)[0]


def _scan_fwd(x, dt, A_log, B, C, D, interpret):
    y, states = _fwd_call(x, dt, A_log, B, C, D, interpret)
    return y, (x, dt, A_log, B, C, D, states)


def _scan_bwd(interpret, res, dy):
    return _bwd_call(res, dy, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, A_log, B, C, D, interpret=False):
    """x, dt [B, T, C], A_log [C, N], B, C [B, T, N], D [C] -> y [B, T, C]
    in x's dtype. Differentiable in all six (custom_vjp)."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    STATS["pallas_calls"] += 1
    return _scan(x, dt, A_log, B, C, D, bool(interpret))


def supports(x, dt, A_log, B, C, D, interpret=False):
    """Static shape test (the registry's probe): x and dt of one shape
    [B, T, C], B and C [B, T, N], A_log [C, N], D [C], and whole vregs of
    channels: C a multiple of 128. Any B, T, N."""
    if not _HAS_PALLAS or x.ndim != 3 or dt.shape != x.shape:
        return False
    Bsz, T, Ch = x.shape
    N = A_log.shape[-1] if A_log.ndim == 2 else -1
    if B.shape != (Bsz, T, N) or C.shape != (Bsz, T, N) \
            or A_log.shape != (Ch, N) or D.shape != (Ch,):
        return False
    return Ch % _LANES == 0


def try_selective_scan(x, dt, A_log, B, C, D):
    """The dispatch entry (try_* convention): y through the kernels, or
    None and the op lowers `kernels_scan.selective_scan_recurrent`."""
    use_pallas, interpret = active()
    if not use_pallas or not supports(x, dt, A_log, B, C, D):
        return None
    return selective_scan(x, dt, A_log, B, C, D, interpret)
