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

Four kernels over the buffer, one grid step a tile, the expert's whole
weight matrix one block (it changes only where the expert changes, so
the weights are read once an expert):

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

and a fifth on the way back to the tokens:

    moe_combine      out[n] = sum of the buffer rows of token n's held
                     pairs (times their weights): one grid step a block
                     of tokens. The pairs are ranked in token order, so
                     the rows a block needs of one expert are one range
                     of the buffer; the kernel brings each range in by
                     DMA and places its rows by a 0/1 (or weight) matrix
                     on the MXU. Its cost follows the rows in use and
                     the output, not the N * k (token, choice) pairs, of
                     which the other chips' experts hold most.

The gather into the buffer is XLA's, a tile at a time over the tiles in
use, and the buffer it fills starts unwritten (`_gather_rows`: the output
of a Mosaic call with an empty body, `moe_gather_buffer`): nothing of the
worst case's rows is written but the tiles in use. Until PR 37 the loop
started from zeros, which XLA wrote out whole before every gather (570 MB
at 69632 x 4096 bf16, two a layer). A Mosaic kernel that copies a token's
row by DMA cannot be built: a 2-D array lies in HBM in tiles of 8 (bf16:
16) rows x 128 columns, a row is not contiguous there, and Mosaic refuses
a slice of one row ("must be aligned to tiling (8)"). PERF.md section 6,
PR 37, and 7.7.
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

from ..registry import active

__all__ = ["expert_ffn", "expert_ffn_reference", "try_expert_ffn",
           "supports", "make_plan", "DEFAULT_TILE_ROWS", "STATS"]

# traces of a gather into a buffer that starts unwritten (`_gather_rows`:
# three a layer, forward and backward), as flash_attention.STATS
STATS = {"gather_kernel": 0}

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
    block_off [(B + 1) * n_held]
                      the pairs are ranked in token order, so the rows of
                      expert e that belong to the tokens of block b (B
                      blocks of `_combine_tiling`'s tokens) are the one
                      range [off[b, e], off[b + 1, e]) of the buffer:
                      what `moe_combine` walks
    """
    N, k = topk_idx.shape
    tb = _combine_tiling(N, tile_rows)[0]
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
    block_end = jnp.minimum((jnp.arange(-(-N // tb)) + 1) * tb, N) * k - 1
    block_off = row_start[None, :] + jnp.concatenate(
        [jnp.zeros((1, n_held), jnp.int32), ranks[block_end] + 1])
    return {"dest": dest.reshape(N, k), "src": src,
            "tile_expert": tile_expert,
            "n_active": tile_end[-1:].astype(jnp.int32), "counts": counts,
            "block_off": block_off.reshape(-1).astype(jnp.int32)}


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
def _fill_tiles(out, x, plan, tm):
    """out [M, H] with the tiles in use written from x [N, H], a tile an
    iteration: row r of a tile gets x[src[r]], a padding row (src = N)
    zeros."""
    src = plan["src"]

    def tile(t, out):
        rows = jax.lax.dynamic_slice(src, (t * tm,), (tm,))
        blk = jnp.take(x, rows, axis=0, mode="fill", fill_value=0)
        return jax.lax.dynamic_update_slice(out, blk, (t * tm, 0))

    return jax.lax.fori_loop(0, plan["n_active"][0], tile, out)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gather(x, plan, tm, interpret):
    # A buffer that nothing has written: the output of a Mosaic call whose
    # body is empty, left in HBM. x goes in unread, so that two buffers
    # asked for over one array are one call to XLA (the backward's gather
    # of x merges with the forward's, buffer and loop, as the loops did
    # when they started from zeros) and two over two arrays are two.
    unwritten = pl.pallas_call(
        lambda x_ref, out_ref: None,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((plan["src"].shape[0], x.shape[1]),
                                       x.dtype),
        name="moe_gather_buffer", interpret=interpret)(x)
    return _fill_tiles(unwritten, x, plan, tm)


def _gather_rows(x, plan, tm, interpret=False):
    """x [N, H] -> the sorted buffer [M, H], filled where it is used and
    nowhere else: a tile an iteration over the tiles in use (a loop whose
    trip count is `n_active`: the time follows the pairs routed here),
    into a buffer that starts unwritten. A padding row inside a used tile
    (src = N) reads zeros, since `moe_tgmm` sums over it and `moe_combine`
    multiplies it by 0; the rows past the last used tile hold whatever
    the memory held, and no kernel reads them (their grids skip those
    tiles, `moe_combine` clamps its last chunk inside the used ones).
    Until PR 37 the loop started from `jnp.zeros((M, H))`, which XLA
    wrote out whole before every gather. One gather alone on the chip,
    device time from its trace, zeros / unwritten, ms (my chip runs, PR
    37; `tools/bench_expert_ffn.py`'s `take_gather` / `gather` read the
    same with 0.04 more of dispatch): [16384, 2048] -> [69632, 2048],
    19 tiles in use: 0.737 (fill 0.436 + loop 0.301) / 0.301; [8192,
    4096] -> [69632, 4096], 8 tiles: 1.070 (0.973 + 0.097) / 0.217;
    [8192, 2304] -> [73728, 2304], 40 tiles: 0.862 (0.536 + 0.327) /
    0.716. The loop's own time is XLA's choice of where x lies while it
    runs: with x brought into VMEM its gather takes 0.035 ms for 8 tiles
    and 0.13 for 40, from HBM 0.155 and 0.52. Alone, XLA prefetches x
    under the fill and not without it; inside the cells' steps the loops
    ran at the slow rate behind the fills and run faster without them (a
    loop of mellum2_train_1chip 0.91 -> 0.44 ms, of lfm2_train_1chip
    0.40 -> 0.29, of solar_train_1chip 0.15 -> 0.13, `copy-done` up 0.9
    ms a step: PERF.md section 6, PR 37), so a piece timed alone says
    little about the loop. Two and more tiles an iteration are slower
    alone (0.359, 0.275, 0.813 at two).
    Jitted so that a program's expert layers share one trace."""
    STATS["gather_kernel"] += 1
    return _gather(x, plan, tm, interpret)


def _combine_tiling(n_tokens, tm):
    """(token block, rows a chunk, chunks a product, row alignment) of
    `moe_combine`, from the shapes. A chunk is what one DMA brings in: it
    starts on a whole sublane tile of the buffer, lies inside the tiles in
    use (so it is no longer than a tile) and is short, because the MXU's
    passes follow the rows staged and a block's range of one expert is
    short (16 rows of 256 tokens at 8 experts held of 64, top-4). A
    product selects from 256 staged rows; the token block is a whole
    number of lane groups (the per-choice arrays have the tokens along
    the lanes). On the chip at 16384 tokens x 2048 bf16, 8245 pairs held
    (PERF.md section 6, PR 31), unweighted / weighted ms: 256 tokens,
    chunks of 16: 0.27 / 0.49; chunks of 32: 0.28 / 0.55; of 64: 0.33 /
    0.72; 128 tokens: 0.30 / 0.50; 512 tokens, chunks of 32: 0.29 / 0.67;
    of 128: 0.47 / 1.22; 1024 tokens: 0.48 / 1.22; the gather this
    replaced: 3.92 / 3.92."""
    align = 16 if tm % 16 == 0 else 8 if tm % 8 == 0 else 1
    chunk = max(align, min(16, tm) // align * align)
    return (256 if n_tokens > _LANES else _LANES), chunk, \
        max(1, 256 // chunk), align


def _panel(width):
    """Columns of one pass over the tokens: the whole row up to 2048, so
    that the staged rows and the float32 accumulator stay a few MB."""
    if width <= 2048:
        return width
    return next((w for w in range(2048, 0, -_LANES) if width % w == 0),
                width)


def _select_dot(sel, rows, weighted):
    """sel [tb, K] float32, one non-zero a column at most (1, or the
    pair's weight) x rows [K, H] -> [tb, H] float32, each product exact
    and the weight a float32: a bf16 row times a 0/1 is one MXU pass; a
    float32 weight is the sum of three bf16 pieces, each piece times a
    bf16 row exact in float32; float32 rows take the MXU's full
    precision."""
    if rows.dtype == jnp.float32:
        return jax.lax.dot_general(
            sel, rows, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    out = None
    for _ in range(3 if weighted else 1):
        piece = sel.astype(rows.dtype)
        part = _dot(piece, rows)
        out = part if out is None else out + part
        sel = sel - piece.astype(jnp.float32)
    return out


def _combine_kernel(off, na, *refs, weighted, n_held, k, tm, chunk, group,
                    align, slots):
    """One token block a grid step. Step b starts the DMAs of block b + 1
    (each held expert's range of the buffer, a chunk a DMA, into the
    other half of the staging rows) and then selects block b's rows out
    of its own half: sel[t, r] is the weight of the pair of token t whose
    row is staged at r, found by comparing the block's `dest` with the
    buffer positions of the staged rows."""
    dest_ref, *w_ref, rows_ref, out_ref, stage_ref, acc_ref, sem, meta, \
        count = refs
    b = pl.program_id(1)
    active = na[0] * tm
    K = group * chunk
    width = stage_ref.shape[1]
    col = pl.multiple_of(pl.program_id(0) * width, width)

    def copy(slot, start):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(pl.multiple_of(start, align), chunk),
                        pl.ds(col, width)],
            stage_ref.at[pl.ds(pl.multiple_of(slot * chunk, chunk), chunk),
                         :],
            sem.at[slot])

    def stage(blk):
        base = (blk % 2) * slots

        def expert(e, n):
            lo = off[blk * n_held + e]
            hi = off[(blk + 1) * n_held + e]
            lo_al = lo // align * align
            n_chunks = jnp.where(hi > lo, (hi - lo_al + chunk - 1) // chunk,
                                 0)

            def one(c, n):
                first = lo_al + c * chunk
                # the last chunk in use is read from inside the tiles in
                # use: past them the buffer was never written
                start = jnp.minimum(first, active - chunk)
                slot = base + n
                copy(slot, start).start()
                meta[3 * slot] = start
                meta[3 * slot + 1] = jnp.maximum(lo, first)
                meta[3 * slot + 2] = jnp.minimum(hi, first + chunk)
                return n + 1

            return jax.lax.fori_loop(0, n_chunks, one, n)

        count[blk % 2] = jax.lax.fori_loop(0, n_held, expert, 0)

    @pl.when(b == 0)
    def _():
        # a staged row that no pair owns is multiplied by 0: it has to be
        # finite, so the rows no DMA has filled yet are zeros
        stage_ref[...] = jnp.zeros_like(stage_ref)
        stage(b)

    @pl.when(b + 1 < pl.num_programs(1))
    def _():
        stage(b + 1)

    def columns(ref):
        """[k, tb], the tokens along the lanes (how [N, k] is dense in
        HBM) -> [tb, 128], choice j of a token in lane j."""
        per_choice = ref[...]
        tb = per_choice.shape[1]
        sub = jax.lax.broadcasted_iota(jnp.int32, (_LANES, tb), 0)
        wide = jnp.zeros((_LANES, tb), per_choice.dtype)
        for j in range(k):
            wide = jnp.where(sub == j, per_choice[j:j + 1, :], wide)
        return wide.T

    base = (b % 2) * slots
    n = count[b % 2]
    dest = columns(dest_ref)
    w = columns(w_ref[0]) if weighted else None
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def product(g, carry):
        first = base + g * group

        def staged(i, pos):
            """The buffer positions of slot i's rows that this block owns
            (the others stay -1, which no `dest` is), lanes i * chunk on;
            a slot past the last one staged owns nothing."""
            slot = first + i
            live = g * group + i < n

            @pl.when(live)
            def _():
                copy(slot, 0).wait()

            p = meta[3 * slot] + lane - i * chunk
            own = (lane >= i * chunk) & (lane < (i + 1) * chunk) \
                & (p >= meta[3 * slot + 1]) \
                & (p < jnp.where(live, meta[3 * slot + 2], 0))
            return jnp.where(own, p, pos)

        pos = jax.lax.fori_loop(0, group, staged,
                                jnp.full((1, K), -1, jnp.int32))
        sel = jnp.zeros((dest.shape[0], K), jnp.float32)
        for j in range(k):
            sel = jnp.where(dest[:, j:j + 1] == pos,
                            w[:, j:j + 1] if weighted else 1.0, sel)
        rows = stage_ref[pl.ds(pl.multiple_of(first * chunk, K), K), :]
        acc_ref[...] += _select_dot(sel, rows, weighted)
        return carry

    jax.lax.fori_loop(0, -(-n // group), product, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _combine(rows, plan, weights, n_held, tm, interpret):
    """Buffer rows [M, H] back to tokens: out[n] = sum of the rows of
    token n's held pairs (times `weights` [N, k], float32), in float32,
    rounded once to the rows' dtype. The cost follows the rows in use and
    the [N, H] output: XLA's gather over every (token, choice) cost a row
    for each of the N * k pairs, nine in ten of them held elsewhere.
    Jitted so that a program's expert layers share one trace of the
    kernel a variant (8 calls a step traced and lowered apart cost the
    cell's set-up 4.1 s of Python, 0.6 s so: PERF.md section 6, PR 31)."""
    dest = plan["dest"]
    N, k = dest.shape
    H = rows.shape[1]
    tb, chunk, group, align = _combine_tiling(N, tm)
    Hp = _panel(H)
    # a block's ranges hold at most tb * k rows, and each range may start
    # and end inside a chunk
    slots = (tb * k + n_held * (align + chunk - 2)) // chunk
    slots = -(-slots // group) * group
    weighted = weights is not None

    # [N, k] arrays go in as [k, N]: a Mosaic operand is row-major, and
    # k = 4 in the minor dimension would be padded to 128 lanes in HBM
    per_choice = pl.BlockSpec((k, tb), lambda c, b, off, na: (0, b))
    args = [dest.T] + ([weights.T] if weighted else []) + [rows]
    return pl.pallas_call(
        functools.partial(_combine_kernel, weighted=weighted, n_held=n_held,
                          k=k, tm=tm, chunk=chunk, group=group, align=align,
                          slots=slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(H // Hp, -(-N // tb)),
            in_specs=[per_choice] * (len(args) - 1)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb, Hp), lambda c, b, off, na: (b, c)),
            scratch_shapes=[
                pltpu.VMEM((2 * slots * chunk, Hp), rows.dtype),
                pltpu.VMEM((tb, Hp), jnp.float32),
                pltpu.SemaphoreType.DMA((2 * slots,)),
                pltpu.SMEM((2 * slots * 3,), jnp.int32),
                pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((N, H), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_combine", interpret=interpret)(
            plan["block_off"], plan["n_active"], *args)


def _int_zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ffn(x, topk_w, w1, w3, w2, plan, n_held, tm, interpret):
    return _ffn_fwd(x, topk_w, w1, w3, w2, plan, n_held, tm, interpret)[0]


def _ffn_fwd(x, topk_w, w1, w3, w2, plan, n_held, tm, interpret):
    xs = _gather_rows(x, plan, tm, interpret)
    g = _gmm_swiglu(plan, xs, w1, w3, tm, interpret)
    y = _gmm(plan, [(g, w2)], False, tm, interpret)
    out = _combine(y, plan, topk_w, n_held, tm, interpret)
    return out, (x, topk_w, w1, w3, w2, plan)


def _ffn_bwd(n_held, tm, interpret, res, dout):
    x, topk_w, w1, w3, w2, plan = res
    M = plan["src"].shape[0]
    dest = plan["dest"]
    xs = _gather_rows(x, plan, tm, interpret)
    dys = _gather_rows(dout.astype(x.dtype), plan, tm,
                       interpret)                          # unweighted
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
    dx = _combine(dxs, plan, None, n_held, tm, interpret)
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
             interpret=False, **_kw):
    """Static shape test: 2-D tokens, one float dtype for x and the
    weights, lane-aligned widths on the chip (the interpreter takes
    any)."""
    if not _HAS_PALLAS or getattr(x, "ndim", 0) != 2 or w1.ndim != 3:
        return False
    E, H, F = w1.shape
    if w3.shape != (E, H, F) or w2.shape != (E, F, H) \
            or x.shape[1] != H or topk_idx.shape != topk_w.shape \
            or topk_idx.shape[0] != x.shape[0]:
        return False
    if not (x.dtype == w1.dtype == w3.dtype == w2.dtype):
        return False
    return interpret or (H % _LANES == 0 and F % _LANES == 0)


def try_expert_ffn(x, topk_idx, topk_w, w1, w3, w2, first_expert=0):
    """The dispatch entry (try_* convention): (out, counts) through the
    kernels, or None and the op lowers its own composition."""
    use_pallas, interpret = active()
    if not use_pallas or not supports(x, topk_idx, topk_w, w1, w3, w2,
                                      first_expert, interpret=interpret):
        return None
    # the tests' sizes: several tiles an expert
    tile_rows = 8 if interpret else None
    return expert_ffn(x, topk_idx, topk_w, w1, w3, w2, first_expert,
                      tile_rows, interpret)
