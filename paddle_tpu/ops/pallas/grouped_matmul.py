"""Pallas grouped matrix products for a no-drop mixture-of-experts FFN.

The expert layer of a sparse language model, for the experts THIS chip
holds (`moe_expert_ffn` op, ops/kernels_moe.py): every token has chosen
`k` of the model's experts; the pairs (token, expert) whose expert lives
here are sorted by expert into a row buffer, each expert's group padded
to whole tiles of `tile_rows` rows, so that one tile belongs to one
expert. The buffer has the worst case's rows (every token sending
min(k, experts held) pairs here: no pair is ever dropped, whatever the
imbalance, and every shape is static); the tiles past the last used one
are never computed: a kernel's grid walks all tiles, an unused tile's
index maps point at the blocks of the last used one (no DMA) and its
body is skipped. Device time follows the pairs routed here.

    expert e:  y = (silu(x W1[e]) * (x W3[e])) W2[e]
    out[n]   = sum over the pairs (n, e) held here of w[n, e] * y

Four kernels, one grid step a tile, the expert's whole weight matrix
one block (it changes only where the expert changes, so the weights
are read once an expert):

    moe_gmm_swiglu   g = silu(xs W1[e]) * (xs W3[e])
    moe_gmm          out = sum_i lhs_i rhs_i[e]  (rhs transposed or not):
                     y = g W2[e]; backward dg = dy W2[e]^T,
                     dxs = dh1 W1[e]^T + dh3 W3[e]^T
    moe_swiglu_bwd   recomputes h1, h3 from xs (they are not kept: a
                     worst-case buffer a layer would not fit beside the
                     activations) and gives dh1, dh3, w * g and the
                     routing weight's gradient, all elementwise work
                     inside the used tiles
    moe_tgmm         dW[e] = sum over e's tiles of lhs^T rhs

The gathers between the token order and the sorted buffer are XLA's:
into the buffer a tile at a time over the tiles in use, back to the
tokens one gather over every (token, choice), a pair that is not held
reading a filled-in zero (PERF.md section 7).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

from .flash_attention import active

__all__ = ["expert_ffn", "expert_ffn_reference", "try_expert_ffn",
           "supports", "make_plan", "DEFAULT_TILE_ROWS"]

# Rows of one tile. An expert's group is padded to whole tiles, so a
# larger tile wastes more rows (half a tile an expert on average) and a
# smaller one re-reads nothing (weights are read once an expert) but
# makes more grid steps. Not yet swept on the chip (PERF.md section 7).
DEFAULT_TILE_ROWS = 512
_VMEM_LIMIT = 100 * 1024 * 1024
_LANES = 128


def _precision(a):
    """As ops/pallas/flash_attention.py: Mosaic takes sub-fp32 operands
    at DEFAULT only; fp32 operands keep the ambient precision."""
    return None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _dot(a, b, transpose_b=False):
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=_precision(a),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the plan: where each pair's row lies in the sorted buffer
# ---------------------------------------------------------------------------
def buffer_tiles(n_tokens, k, n_held, tile_rows):
    """Tiles of the worst case: every token sends min(k, n_held) pairs
    here, and each expert's last tile is partly empty."""
    pairs = n_tokens * min(k, n_held)
    return -(-pairs // tile_rows) + n_held


def make_plan(topk_idx, first_expert, n_held, tile_rows):
    """topk_idx [N, k] (ids over all the model's experts) -> the plan:

    dest [N, k]       row of the pair in the buffer; M (one past it)
                      where the pair's expert is not held here
    src [M]           the token of each buffer row; N for padding rows
    tile_expert [T]   the held expert (0-based) of each tile
    n_active [1]      tiles in use, at least one an expert
    counts [n_held]   pairs of each held expert
    """
    N, k = topk_idx.shape
    T = buffer_tiles(N, k, n_held, tile_rows)
    M = T * tile_rows
    local = topk_idx.astype(jnp.int32).reshape(-1) - first_expert
    held = (local >= 0) & (local < n_held)
    onehot = (local[:, None] == jnp.arange(n_held)[None, :]) & held[:, None]
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    tiles = jnp.maximum(1, -(-counts // tile_rows))
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile_rows
    col = jnp.clip(local, 0, n_held - 1)[:, None]
    rank = jnp.take_along_axis(ranks, col, axis=1)[:, 0]
    dest = jnp.where(held, row_start[col[:, 0]] + rank, M)
    token = jnp.arange(N * k, dtype=jnp.int32) // k
    src = jnp.full((M,), N, jnp.int32).at[dest].set(token, mode="drop")
    tile_expert = jnp.minimum(
        jnp.sum(jnp.arange(T)[:, None] >= tile_end[None, :], axis=1),
        n_held - 1).astype(jnp.int32)
    return {"dest": dest.reshape(N, k), "src": src,
            "tile_expert": tile_expert,
            "n_active": tile_end[-1:].astype(jnp.int32), "counts": counts}


# ---------------------------------------------------------------------------
# kernels. Scalar prefetch: te (tile -> expert), na (tiles in use).
# ---------------------------------------------------------------------------
def _row(i, na):
    return jnp.minimum(i, na[0] - 1)


def _rows_spec(tm, width):
    return pl.BlockSpec((tm, width), lambda i, te, na: (_row(i, na), 0))


def _expert_spec(d0, d1):
    return pl.BlockSpec((None, d0, d1),
                        lambda i, te, na: (te[_row(i, na)], 0, 0))


def _call(kernel, name, n_tiles, in_specs, out_specs, out_shape, interpret,
          scratch_shapes=()):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch_shapes)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name=name, interpret=interpret)


def _silu(h):
    return h * jax.nn.sigmoid(h)


def _gmm_swiglu_kernel(te, na, xs_ref, w1_ref, w3_ref, g_ref):
    @pl.when(pl.program_id(0) < na[0])
    def _():
        xs = xs_ref[...]
        h1 = _dot(xs, w1_ref[...])
        h3 = _dot(xs, w3_ref[...])
        g_ref[...] = (_silu(h1) * h3).astype(g_ref.dtype)


def _gmm_swiglu(plan, xs, w1, w3, tm, interpret):
    M, H = xs.shape
    F = w1.shape[2]
    return _call(
        _gmm_swiglu_kernel, "moe_gmm_swiglu", M // tm,
        [_rows_spec(tm, H), _expert_spec(H, F), _expert_spec(H, F)],
        _rows_spec(tm, F), jax.ShapeDtypeStruct((M, F), xs.dtype),
        interpret)(plan["tile_expert"], plan["n_active"], xs, w1, w3)


def _gmm_kernel(te, na, *refs, n_pairs, transpose_rhs):
    out_ref = refs[-1]

    @pl.when(pl.program_id(0) < na[0])
    def _():
        acc = None
        for p in range(n_pairs):
            part = _dot(refs[2 * p][...], refs[2 * p + 1][...],
                        transpose_rhs)
            acc = part if acc is None else acc + part
        out_ref[...] = acc.astype(out_ref.dtype)


def _gmm(plan, pairs, transpose_rhs, tm, interpret):
    """sum_i lhs_i [M, K] x rhs_i[e] ([E, K, N], or [E, N, K] with
    `transpose_rhs`) -> [M, N], each tile with its expert's matrix."""
    lhs0, rhs0 = pairs[0]
    M = lhs0.shape[0]
    N_out = rhs0.shape[1] if transpose_rhs else rhs0.shape[2]
    in_specs, args = [], []
    for lhs, rhs in pairs:
        in_specs += [_rows_spec(tm, lhs.shape[1]),
                     _expert_spec(rhs.shape[1], rhs.shape[2])]
        args += [lhs, rhs]
    return _call(
        functools.partial(_gmm_kernel, n_pairs=len(pairs),
                          transpose_rhs=transpose_rhs),
        "moe_gmm", M // tm, in_specs, _rows_spec(tm, N_out),
        jax.ShapeDtypeStruct((M, N_out), lhs0.dtype),
        interpret)(plan["tile_expert"], plan["n_active"], *args)


def _swiglu_bwd_kernel(te, na, xs_ref, w1_ref, w3_ref, dg_ref, w_ref,
                       dh1_ref, dh3_ref, gw_ref, dw_ref):
    @pl.when(pl.program_id(0) < na[0])
    def _():
        xs = xs_ref[...]
        h1 = _dot(xs, w1_ref[...])
        h3 = _dot(xs, w3_ref[...])
        sig = jax.nn.sigmoid(h1)
        silu = h1 * sig
        g = silu * h3
        dg_u = dg_ref[...].astype(jnp.float32)    # of the unweighted y
        w = w_ref[...][:, :1]                     # [tm, 1] routing weight
        dw_ref[...] = jnp.broadcast_to(
            jnp.sum(dg_u * g, axis=1, keepdims=True), dw_ref.shape)
        dg = dg_u * w
        dh1_ref[...] = (dg * h3 * (sig + silu * (1.0 - sig))).astype(
            dh1_ref.dtype)
        dh3_ref[...] = (dg * silu).astype(dh3_ref.dtype)
        gw_ref[...] = (g * w).astype(gw_ref.dtype)


def _swiglu_bwd(plan, xs, w1, w3, dg_u, w_rows, tm, interpret):
    M, H = xs.shape
    F = w1.shape[2]
    act = jax.ShapeDtypeStruct((M, F), xs.dtype)
    return _call(
        _swiglu_bwd_kernel, "moe_swiglu_bwd", M // tm,
        [_rows_spec(tm, H), _expert_spec(H, F), _expert_spec(H, F),
         _rows_spec(tm, F), _rows_spec(tm, _LANES)],
        [_rows_spec(tm, F), _rows_spec(tm, F), _rows_spec(tm, F),
         _rows_spec(tm, _LANES)],
        [act, act, act, jax.ShapeDtypeStruct((M, _LANES), jnp.float32)],
        interpret)(plan["tile_expert"], plan["n_active"], xs, w1, w3, dg_u,
                   w_rows)


def _tgmm_kernel(te, na, lhs_ref, rhs_ref, out_ref, acc_ref, *, n_tiles):
    i = pl.program_id(0)
    last = na[0] - 1
    here = te[jnp.minimum(i, last)]
    first_of_group = (i == 0) | (te[jnp.maximum(i - 1, 0)] != here)
    last_of_group = (i == last) | (
        te[jnp.minimum(i + 1, n_tiles - 1)] != here)

    @pl.when(i <= last)
    def _():
        @pl.when(first_of_group)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += _dot(lhs_ref[...].T, rhs_ref[...])

        @pl.when(last_of_group)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(plan, lhs, rhs, n_held, tm, interpret):
    """dW[e] = sum over e's tiles of lhs^T rhs: [M, K], [M, N] ->
    [E, K, N]. Every expert has at least one tile (padding rows are
    zero rows), so every block of the output is written."""
    M, K = lhs.shape
    N_out = rhs.shape[1]
    return _call(
        functools.partial(_tgmm_kernel, n_tiles=M // tm), "moe_tgmm",
        M // tm, [_rows_spec(tm, K), _rows_spec(tm, N_out)],
        _expert_spec(K, N_out),
        jax.ShapeDtypeStruct((n_held, K, N_out), lhs.dtype), interpret,
        scratch_shapes=[pltpu.VMEM((K, N_out), jnp.float32)])(
            plan["tile_expert"], plan["n_active"], lhs, rhs)


# ---------------------------------------------------------------------------
# the expert FFN over the sorted buffer, with its backward
# ---------------------------------------------------------------------------
def _gather_rows(x, plan, tm):
    """x [N, H] -> the sorted buffer [M, H], a tile an iteration over the
    tiles in use only (a loop whose trip count is `n_active`: the time
    follows the pairs routed here; one gather over the worst case's M
    rows took 1.38 ms where this takes 0.82: PERF.md section 6, PR 30).
    A padding row (src = N) reads zeros; the tiles past the last used one
    are zeros that no kernel reads."""
    src = plan["src"]

    def tile(t, out):
        rows = jax.lax.dynamic_slice(src, (t * tm,), (tm,))
        blk = jnp.take(x, rows, axis=0, mode="fill", fill_value=0)
        return jax.lax.dynamic_update_slice(out, blk, (t * tm, 0))

    return jax.lax.fori_loop(0, plan["n_active"][0], tile,
                             jnp.zeros((src.shape[0], x.shape[1]), x.dtype))


def _combine(rows, dest, weights=None):
    """Buffer rows [M, H] back to tokens: out[n] = sum_k of the rows of
    token n's held pairs (times `weights` [N, k]). A pair whose expert
    is not held points one past the buffer (dest = M) and reads a zero
    the gather fills in, without a copy: nine pairs in ten at eight
    experts held of 64, and a row copied for each of them cost four
    times the whole gather (4.8 ms against 1.2)."""
    picked = jnp.take(rows, dest, axis=0, mode="fill", fill_value=0)
    picked = picked.astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[..., None]
    return jnp.sum(picked, axis=1)


def _int_zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ffn(x, topk_w, w1, w3, w2, plan, n_held, tm, interpret):
    return _ffn_fwd(x, topk_w, w1, w3, w2, plan, n_held, tm, interpret)[0]


def _ffn_fwd(x, topk_w, w1, w3, w2, plan, n_held, tm, interpret):
    xs = _gather_rows(x, plan, tm)
    g = _gmm_swiglu(plan, xs, w1, w3, tm, interpret)
    y = _gmm(plan, [(g, w2)], False, tm, interpret)
    out = _combine(y, plan["dest"], topk_w).astype(x.dtype)
    return out, (x, topk_w, w1, w3, w2, plan)


def _ffn_bwd(n_held, tm, interpret, res, dout):
    x, topk_w, w1, w3, w2, plan = res
    M = plan["src"].shape[0]
    dest = plan["dest"]
    xs = _gather_rows(x, plan, tm)
    dys = _gather_rows(dout.astype(x.dtype), plan, tm)     # unweighted
    w_rows = jnp.zeros((M,), jnp.float32).at[dest.reshape(-1)].set(
        topk_w.astype(jnp.float32).reshape(-1), mode="drop")
    w_rows = jnp.broadcast_to(w_rows[:, None], (M, _LANES))
    dg_u = _gmm(plan, [(dys, w2)], True, tm, interpret)
    dh1, dh3, gw, dw_rows = _swiglu_bwd(plan, xs, w1, w3, dg_u, w_rows, tm,
                                        interpret)
    dxs = _gmm(plan, [(dh1, w1), (dh3, w3)], True, tm, interpret)
    dw1 = _tgmm(plan, xs, dh1, n_held, tm, interpret)
    dw3 = _tgmm(plan, xs, dh3, n_held, tm, interpret)
    dw2 = _tgmm(plan, gw, dys, n_held, tm, interpret)
    dx = _combine(dxs, dest).astype(x.dtype)
    dw = jnp.take(dw_rows[:, 0], dest, mode="fill",
                  fill_value=0).astype(topk_w.dtype)
    return (dx, dw, dw1.astype(w1.dtype), dw3.astype(w3.dtype),
            dw2.astype(w2.dtype), jax.tree.map(_int_zero, plan))


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def expert_ffn(x, topk_idx, topk_w, w1, w3, w2, first_expert=0,
               tile_rows=None, interpret=False):
    """x [N, H], topk_idx / topk_w [N, k], w1 / w3 [E, H, F], w2 [E, F, H]
    (the E experts held here: the model's experts `first_expert ..
    first_expert + E - 1`) -> (out [N, H], pairs of each held expert [E]).
    Differentiable in x, topk_w and the weights."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    n_held = w1.shape[0]
    tm = int(tile_rows or DEFAULT_TILE_ROWS)
    plan = make_plan(topk_idx, int(first_expert), n_held, tm)
    counts = plan.pop("counts")
    out = _ffn(x, topk_w.astype(jnp.float32), w1, w3, w2, plan, n_held, tm,
               bool(interpret))
    return out, counts


def expert_ffn_reference(x, topk_idx, topk_w, w1, w3, w2, first_expert=0,
                         **_kw):
    """The same sum, expert by expert over every token (the composition
    the op lowers where no kernel can)."""
    n_held = w1.shape[0]
    local = topk_idx.astype(jnp.int32) - first_expert
    out = jnp.zeros(x.shape, jnp.float32)
    counts = []
    for e in range(n_held):
        hit = local == e
        gate = jnp.sum(jnp.where(hit, topk_w.astype(jnp.float32), 0.0), -1)
        h1 = jnp.dot(x, w1[e], preferred_element_type=jnp.float32)
        h3 = jnp.dot(x, w3[e], preferred_element_type=jnp.float32)
        g = (_silu(h1) * h3).astype(x.dtype)
        y = jnp.dot(g, w2[e], preferred_element_type=jnp.float32)
        out = out + gate[:, None] * y
        counts.append(jnp.sum(hit, dtype=jnp.int32))
    return out.astype(x.dtype), jnp.stack(counts)


def supports(x, topk_idx, topk_w, w1, w3, w2, first_expert=0,
             tile_rows=None, interpret=False, **_kw):
    """Static shape test: 2-D tokens, one float dtype for x and the
    weights, lane-aligned widths and a sublane-aligned tile on the chip
    (the interpreter takes any)."""
    if not _HAS_PALLAS or getattr(x, "ndim", 0) != 2 or w1.ndim != 3:
        return False
    E, H, F = w1.shape
    if w3.shape != (E, H, F) or w2.shape != (E, F, H) \
            or x.shape[1] != H or topk_idx.shape != topk_w.shape \
            or topk_idx.shape[0] != x.shape[0]:
        return False
    if not (x.dtype == w1.dtype == w3.dtype == w2.dtype):
        return False
    tm = int(tile_rows or DEFAULT_TILE_ROWS)
    return interpret or (H % _LANES == 0 and F % _LANES == 0
                         and tm % 16 == 0)


def try_expert_ffn(x, topk_idx, topk_w, w1, w3, w2, first_expert=0,
                   tile_rows=None):
    """The dispatch entry (try_* convention): (out, counts) through the
    kernels, or None and the op lowers its own composition."""
    use_pallas, interpret = active()
    if not use_pallas or not supports(x, topk_idx, topk_w, w1, w3, w2,
                                      first_expert, tile_rows, interpret):
        return None
    if interpret and tile_rows is None:
        tile_rows = 8      # the tests' sizes: several tiles an expert
    return expert_ffn(x, topk_idx, topk_w, w1, w3, w2, first_expert,
                      tile_rows, interpret)
