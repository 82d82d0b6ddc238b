"""Pallas fused embedding lookup+pool for TPU.

The sparse hot path (Tensor Processing Primitives, PAPERS.md): given a
row buffer `table` [C, D] and per-example slot indices `inv` [R, F],
produce the pooled embedding `out[r] = reduce_f w[r, f] * table[inv[r, f]]`
(sum or mean over the field axis) in ONE kernel. XLA lowers the jnp
composition as gather → [R, F, D] materialization in HBM → reduce; the
kernel never writes the [R, F, D] intermediate.

The gather is expressed as a weighted one-hot contraction on the MXU:
for a row block, `counts[r, c] = sum_f w[r, f] * (inv[r, f] == c)` is
built with F vectorized compares in VMEM, and `out = counts @ table` is
a single [BR, C] x [C, D] matmul — the TPU-idiomatic gather for tables
that fit VMEM (the same trick XLA uses for small one-hot gathers, here
fused with the field-axis pool and the per-position weights). Negative
`inv` entries match no column and contribute zero — that is the
padding/invalid convention, no clipping needed.

Registered via jax.custom_vjp so jax.value_and_grad stays fused on the
forward; the backward is the O(unique-rows) scatter: d(table) is a
segment-sum of the pooled cotangent over `inv` (jnp — it IS the
deduped-update composition the sparse engine wants), d(w) a row-gather
dot.

Dispatch: try_lookup_pool() returns None (→ caller's jnp fallback,
lookup_pool_reference) off-TPU, when the table or the one-hot block
would not fit the VMEM budget, or when no legal row block exists —
the flash_attention/layer_norm capability-probe pattern.

Callers: the `fused_embedding_seq_pool` op (ops/kernels_extra.py, ref
paddle/fluid/operators/fused/fused_embedding_seq_pool_op.h) and the
sharded-embedding engine's local lookup (parallel/sparse.py, gather
mode: F=1, pool="sum").
"""
import functools

import jax
import jax.numpy as jnp

try:
    from jax.experimental import pallas as pl
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

from ..registry import active

__all__ = ["lookup_pool", "lookup_pool_reference", "try_lookup_pool",
           "STATS"]

# Trace-time evidence the Pallas path was selected (tests assert on it).
STATS = {"pallas_calls": 0}

# VMEM budget in f32 elements for table + one-hot block + out block
# (~6 MB of the ~16 MB VMEM, leaving room for double-buffering).
_VMEM_BUDGET = 1536 * 1024


def _pick_rows(R, C, D, F):
    """Largest row block (multiple of 8, or R itself) that divides R
    and fits the budget next to the resident [C, D] table. 0 if none."""
    table = C * D
    if table >= _VMEM_BUDGET:
        return 0
    per_row = C + D + F          # one-hot row + out row + inv row
    cap = (_VMEM_BUDGET - table) // max(per_row, 1)
    pref = max(8, min(R, cap))
    if pref >= R:
        return R
    for b in range(pref // 8 * 8, 0, -8):
        if R % b == 0:
            return b
    return R if R * per_row + table <= _VMEM_BUDGET else 0


def _pool_kernel(inv_ref, w_ref, tab_ref, out_ref, *, mean):
    inv = inv_ref[...].astype(jnp.int32)           # [BR, F]
    C = tab_ref.shape[0]
    BR, F = inv.shape
    # weighted one-hot counts [BR, C]: F compares against the lane iota
    iota = jax.lax.broadcasted_iota(jnp.int32, (BR, C), 1)
    counts = jnp.zeros((BR, C), jnp.float32)
    has_w = w_ref is not None
    w = w_ref[...].astype(jnp.float32) if has_w else None
    for f in range(F):
        hit = (iota == inv[:, f:f + 1]).astype(jnp.float32)
        counts += hit * w[:, f:f + 1] if has_w else hit
    # HIGHEST: this matmul IS the gather, and at the MXU's default an
    # fp32 operand is cut to one bf16 pass — rows would come back with
    # 8 mantissa bits (seen on v5e: 3e-3 off the jnp gather)
    acc = jnp.dot(counts, tab_ref[...].astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    if mean:
        denom = jnp.maximum(
            jnp.sum((inv >= 0).astype(jnp.float32), axis=1,
                    keepdims=True), 1.0)
        acc = acc / denom
    out_ref[...] = acc.astype(out_ref.dtype)


def _fwd(table, inv, weights, pool, block_rows, interpret):
    STATS["pallas_calls"] += 1
    C, D = table.shape
    R, F = inv.shape
    br = block_rows or _pick_rows(R, C, D, F)
    grid = (R // br,)
    in_specs = [pl.BlockSpec((br, F), lambda i: (i, 0))]
    args = [inv]
    if weights is not None:
        in_specs.append(pl.BlockSpec((br, F), lambda i: (i, 0)))
        args.append(weights)
    in_specs.append(pl.BlockSpec((C, D), lambda i: (0, 0)))
    args.append(table)

    def kern(*refs):
        if weights is None:
            inv_ref, tab_ref, out_ref = refs
            w_ref = None
        else:
            inv_ref, w_ref, tab_ref, out_ref = refs
        _pool_kernel(inv_ref, w_ref, tab_ref, out_ref,
                     mean=(pool == "mean"))

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), table.dtype),
        name="embedding_lookup",
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def lookup_pool(table, inv, weights, pool="sum", block_rows=None,
                interpret=False):
    """Fused `out[r] = pool_f weights[r, f] * table[inv[r, f]]`.

    table: [C, D]; inv: [R, F] int (negative = padding, contributes 0
    and is excluded from the mean denominator); weights: [R, F] or
    None; pool: "sum" | "mean". Returns [R, D] in table's dtype."""
    return _fwd(table, inv, weights, pool, block_rows, interpret)


def _fwd_vjp(table, inv, weights, pool, block_rows, interpret):
    y = _fwd(table, inv, weights, pool, block_rows, interpret)
    return y, (table, inv, weights)


def _bwd_vjp(pool, block_rows, interpret, res, dy):
    table, inv, weights = res
    C, D = table.shape
    R, F = inv.shape
    dyf = dy.astype(jnp.float32)
    valid = (inv >= 0)
    if pool == "mean":
        denom = jnp.maximum(valid.sum(axis=1, keepdims=True), 1
                            ).astype(jnp.float32)
        dyf = dyf / denom
    w = weights.astype(jnp.float32) if weights is not None \
        else jnp.ones((R, F), jnp.float32)
    w = jnp.where(valid, w, 0.0)
    # d(table): the deduped scatter — one segment-sum over the flat
    # (row, field) stream, never a [R, F, D] HBM intermediate either
    contrib = (w[:, :, None] * dyf[:, None, :]).reshape(R * F, D)
    seg = jnp.where(valid, inv, C).reshape(R * F)
    dtab = jax.ops.segment_sum(contrib, seg, num_segments=C + 1)[:C]
    dw = None
    if weights is not None:
        rows = jnp.take(table, jnp.clip(inv, 0, C - 1), axis=0
                        ).astype(jnp.float32)        # [R, F, D]
        dw = jnp.where(valid,
                       jnp.einsum("rfd,rd->rf", rows, dyf),
                       0.0).astype(weights.dtype)
    return dtab.astype(table.dtype), None, dw


lookup_pool.defvjp(_fwd_vjp, _bwd_vjp)


# The jnp reference/fallback composition lives with the op kernel
# (ops/kernels_extra.py) so fallback paths never import this package;
# re-exported here for back-compat (tests and the sparse engine used to
# find it in this module).
from ..kernels_extra import lookup_pool_reference  # noqa: E402


def try_lookup_pool(table, inv, weights=None, pool="sum"):
    """THE dispatch policy: the fused kernel's result, or None → caller
    falls back to lookup_pool_reference. Requirements: Pallas active,
    2D table/inv, a known pool mode, and table + row block within the
    VMEM budget."""
    use_pallas, interpret = active()
    if not use_pallas or pool not in ("sum", "mean"):
        return None
    if table.ndim != 2 or inv.ndim != 2:
        return None
    C, D = table.shape
    R, F = inv.shape
    if R < 8:
        return None
    br = _pick_rows(R, C, D, F)
    if not br or (R // br) * br != R:
        return None
    return lookup_pool(table, inv.astype(jnp.int32), weights, pool,
                       br, interpret)
