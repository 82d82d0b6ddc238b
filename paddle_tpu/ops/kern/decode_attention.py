"""Single-token decode attention over the slot-pool cache layout.

The serving decoder (models/transformer.IncrementalDecoder) holds its
KV cache as [slots, T_max, heads, Dh] — every slot is a live request
at its own position, so the effective attention is RAGGED: slot s
attends to t <= pos[s] of a fixed T_max buffer. The jnp composition
materializes [S, H, T] scores and, on the int8 cache, a fully
dequantized fp32 [S, T, H, Dh] copy of BOTH caches every step. These
kernels stream the cache through VMEM in (block_t, H, Dh) tiles with
flash-style online softmax instead:

- decode_attend       fp32/bf16 cache: one pass over K and V, no
                      [S,H,T] score tensor in HBM, whole k-blocks
                      above pos[s] skipped (the ragged win: a slot at
                      position 37 of a 2048-deep pool reads one block,
                      not 2048 rows).
- dequant_attend      the PR-13 block-quantized cache: int8 codes +
                      per-block scales are dequantized IN the kernel's
                      VMEM tile right before the dot — the fp32 cache
                      copy never exists, so HBM read bytes drop ~4x on
                      the decode hot path (the EQuARX fusion argument).

Grid is (slots, n_t) with t innermost and "arbitrary" (online softmax
carries m/l/acc scratch across t-steps, exactly the flash kernel's
structure). Every block keeps the cache's own last two dims (H, Dh)
whole — q and the output as [H, Dh] rows, K/V as [bt, H, Dh] tiles —
which is the one block shape Mosaic takes at any head count and width;
pos rides scalar prefetch (SMEM) and steers the cache index_map.

Numerics convention matches the decoder composition exactly: f32
logits, mask to -1e30 (vs the composition's -inf — both vanish in
softmax; parity gate tolerance covers it), f32 softmax, weighted sum
in f32. pos[s] < 0 (never produced by the decoder) yields an all-zero
row, not NaN.

Perf gates (auto mode only; interpret bypasses): MIN_T_DECODE /
MIN_T_DEQUANT. Defaults are conservative and UNMEASURED on real chips
— the expected crossover by the flash MIN_SEQ_LEN analogy, pending an
on-chip sweep (no benchmark cell runs them yet: PERF.md 7.1).
"""
import functools

import jax
import jax.numpy as jnp

from ..pallas import flash_attention as fa
from ..registry import active

if fa._HAS_PALLAS:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attend", "decode_attend_reference", "try_decode_attend",
           "dequant_attend", "dequant_attend_reference",
           "try_dequant_attend", "probe_decode", "probe_dequant",
           "STATS", "DEFAULT_BLOCK_T", "MIN_T_DECODE", "MIN_T_DEQUANT"]

STATS = {"pallas_calls": 0}

DEFAULT_BLOCK_T = 512

# Hardware perf gates on the pool depth T_max (interpret bypasses):
# fp32 decode attend is a bandwidth tie with XLA's fused einsum until
# the score tensor + cache reread stop fitting; the dequant variant
# wins as soon as skipping the fp32 cache materialization pays for the
# grid overhead. Unmeasured defaults — see module docstring.
MIN_T_DECODE = 1024
MIN_T_DEQUANT = 256


def _pick_bt(T, pref=None):
    return fa._pick_block(T, pref or DEFAULT_BLOCK_T)


# ------------------------------------------------------------ kernels
# One grid step handles ALL heads of one slot for one [bt] stretch of
# the pool: the cache block is (bt, H, Dh) — the array's own last two
# dims, so Mosaic's (8, 128)-or-full block rule holds at any head count
# and head width, and heads sit on sublanes exactly as the cache stores
# them. A single query row per head makes the "matmul" a matrix-vector
# product, done on the VPU as multiply + lane reduce with every
# intermediate kept rank-3 ([bt, H, 1]) so no lane<->sublane shuffle
# or in-kernel reshape is needed.
def _attend_block(q, k_f, v_f, pos, j, bt, scale, m_ref, l_ref, acc_ref):
    """Online-softmax update for one block. q [H, Dh]; k_f/v_f
    [bt, H, Dh] (or a lane-broadcastable factorization of them, see
    _dequant_kernel); scratch m/l [H, LANES] lane-replicated, acc
    [H, Dh]."""
    s = jnp.sum(k_f * q[None], axis=-1, keepdims=True) * scale  # [bt,H,1]
    k_pos = j * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    s = jnp.where(k_pos <= pos, s, fa._NEG_INF)
    m_prev = m_ref[...][:, :1]                                   # [H, 1]
    l_prev = l_ref[...][:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    p = jnp.exp(s - m_new[None])                                 # [bt,H,1]
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=0)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v_f, axis=0)


def _init(j, m_ref, l_ref, acc_ref):
    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, fa._NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _flush(j, n_t, l_ref, acc_ref, o_ref):
    # MUST be emitted after the compute block: on the last t step both
    # predicates are true and pl.when bodies run in emission order
    @pl.when(j == n_t - 1)
    def _():
        l = jnp.maximum(l_ref[...][:, :1], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, n_t, bt):
    """pos_ref [S] int32 in SMEM (scalar prefetch); q_ref/o_ref [H, Dh];
    k/v_ref [bt, H, Dh]."""
    j = pl.program_id(1)
    _init(j, m_ref, l_ref, acc_ref)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j * bt <= pos)   # whole blocks above pos: no compute
    def _compute():
        _attend_block(q_ref[...].astype(jnp.float32),
                      k_ref[...].astype(jnp.float32),
                      v_ref[...].astype(jnp.float32),
                      pos, j, bt, scale, m_ref, l_ref, acc_ref)

    _flush(j, n_t, l_ref, acc_ref, o_ref)


def _lane_scales(s, dh):
    """Per-block scales [bt, H, nb] -> a factor that multiplies a
    [bt, H, dh] tile: [bt, H, 1] (lane broadcast) for one block per
    head, else [bt, H, dh] built by nb lane-iota selects (no reshape of
    the tile)."""
    nb = s.shape[-1]
    if nb == 1:
        return s
    blk = jax.lax.broadcasted_iota(jnp.int32, (1, 1, dh), 2) // (dh // nb)
    out = jnp.zeros(s.shape[:2] + (dh,), jnp.float32)
    for b in range(nb):
        out = jnp.where(blk == b, s[:, :, b:b + 1], out)
    return out


def _dequant_kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                    o_ref, m_ref, l_ref, acc_ref, *, scale, n_t, bt):
    """int8 codes [bt, H, Dh] + scales [bt, H, Dh/qblock] per tile;
    dequantize in VMEM right before use — no fp32 cache copy in HBM."""
    j = pl.program_id(1)
    _init(j, m_ref, l_ref, acc_ref)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j * bt <= pos)
    def _compute():
        dh = kq_ref.shape[-1]
        k_f = kq_ref[...].astype(jnp.float32) * _lane_scales(
            ks_ref[...], dh)
        v_f = vq_ref[...].astype(jnp.float32) * _lane_scales(
            vs_ref[...], dh)
        _attend_block(q_ref[...].astype(jnp.float32), k_f, v_f,
                      pos, j, bt, scale, m_ref, l_ref, acc_ref)

    _flush(j, n_t, l_ref, acc_ref, o_ref)


# -------------------------------------------------------------- calls
def _call(kernel, q, caches, pos, bt, interpret):
    """Shared wiring: grid (slots, T/bt) with t innermost; pos rides
    scalar prefetch so the cache index_map can stop at each slot's last
    live block — steps past it re-name the same block and Mosaic's
    pipeline skips the DMA, which is what makes the read ragged."""
    S, H, Dh = q.shape
    T = caches[0].shape[1]
    n_t = T // bt

    def row(s, j, pos_ref):
        return (s, 0, 0)

    def blk(s, j, pos_ref):
        last = jnp.clip(pos_ref[s] // bt, 0, n_t - 1)
        return (s, jnp.minimum(j, last), 0, 0)

    in_specs = [pl.BlockSpec((None, H, Dh), row)] + [
        pl.BlockSpec((None, bt, H, c.shape[-1]), blk) for c in caches]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, n_t),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, H, Dh), row),
            scratch_shapes=[
                pltpu.VMEM((H, fa._LANES), jnp.float32),
                pltpu.VMEM((H, fa._LANES), jnp.float32),
                pltpu.VMEM((H, Dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(pos.astype(jnp.int32), q, *caches)


def decode_attend(q, k, v, pos, scale=None, block_t=None,
                  interpret=False):
    """q [S,H,Dh], k/v [S,T,H,Dh], pos [S] int32 (attend to t <=
    pos[s]) -> [S,H,Dh]."""
    Dh = q.shape[-1]
    T = k.shape[1]
    scale = float(scale) if scale is not None else Dh ** -0.5
    bt = _pick_bt(T, block_t)
    if not bt:
        raise NotImplementedError("pool depth must tile")
    STATS["pallas_calls"] += 1
    kern = functools.partial(_decode_kernel, scale=scale, n_t=T // bt,
                             bt=bt)
    return _call(kern, q, (k, v), pos, bt, interpret)


def dequant_attend(q, kq, ks, vq, vs, pos, scale=None, block_t=None,
                   interpret=False):
    """q [S,H,Dh] f32; kq/vq [S,T,H,Dh] int8; ks/vs [S,T,H,Dh/qblock]
    f32 per-block scales; pos [S] int32 -> [S,H,Dh] f32. qblock is
    implied by the scale layout (Dh // ks.shape[-1])."""
    Dh = q.shape[-1]
    T = kq.shape[1]
    scale = float(scale) if scale is not None else Dh ** -0.5
    bt = _pick_bt(T, block_t)
    if not bt:
        raise NotImplementedError("pool depth must tile")
    STATS["pallas_calls"] += 1
    kern = functools.partial(_dequant_kernel, scale=scale, n_t=T // bt,
                             bt=bt)
    return _call(kern, q, (kq, ks, vq, vs), pos, bt, interpret)


# ---------------------------------------------------------- reference
def decode_attend_reference(q, k, v, pos, scale=None):
    """EXACTLY the IncrementalDecoder composition on [S,T,H,Dh]: f32
    logits, -inf mask on t > pos, the custom-vjp _attn_softmax, cast,
    weighted sum — so kernel-vs-reference parity IS kernel-vs-decoder
    parity."""
    from ..kernels_nn import _attn_softmax
    Dh = q.shape[-1]
    T = k.shape[1]
    scale = float(scale) if scale is not None else Dh ** -0.5
    logits = jnp.einsum("shd,sthd->sht", q, k).astype(jnp.float32) \
        * jnp.asarray(scale, jnp.float32)
    keep = (jnp.arange(T)[None, None, :] <= pos[:, None, None])
    logits = jnp.where(keep, logits, -jnp.inf)
    w = _attn_softmax(logits).astype(q.dtype)
    return jnp.einsum("sht,sthd->shd", w, v).astype(q.dtype)


def dequant_attend_reference(q, kq, ks, vq, vs, pos, scale=None):
    """The decoder's int8 composition: dequantize BOTH caches to fp32
    in-graph (codes * broadcast scales), then the fp32 reference."""
    S, T, H, Dh = kq.shape
    nb = ks.shape[-1]
    qblock = Dh // nb
    k = (kq.astype(jnp.float32).reshape(S, T, H, nb, qblock)
         * ks[..., None]).reshape(S, T, H, Dh)
    v = (vq.astype(jnp.float32).reshape(S, T, H, nb, qblock)
         * vs[..., None]).reshape(S, T, H, Dh)
    return decode_attend_reference(q, k, v, pos, scale)


# ------------------------------------------------------------- probes
def probe_decode(q, k, v, pos, scale=None, *, interpret=False):
    """STATIC acceptance (shape-only; works on ShapeDtypeStruct)."""
    if getattr(q, "ndim", None) != 3 or getattr(k, "ndim", None) != 4:
        return False
    if getattr(v, "ndim", None) != 4 or tuple(k.shape) != tuple(v.shape):
        return False
    S, H, Dh = q.shape
    if k.shape[0] != S or k.shape[2] != H or k.shape[3] != Dh:
        return False
    if tuple(pos.shape) != (S,):
        return False
    T = k.shape[1]
    if not interpret and T < MIN_T_DECODE:
        return False
    return bool(_pick_bt(T))


def probe_dequant(q, kq, ks, vq, vs, pos, scale=None, *,
                  interpret=False):
    if getattr(q, "ndim", None) != 3 or getattr(kq, "ndim", None) != 4:
        return False
    if getattr(ks, "ndim", None) != 4 or getattr(vq, "ndim", None) != 4 \
            or getattr(vs, "ndim", None) != 4:
        return False
    if tuple(kq.shape) != tuple(vq.shape) \
            or tuple(ks.shape) != tuple(vs.shape):
        return False
    S, H, Dh = q.shape
    if kq.shape[0] != S or kq.shape[2] != H or kq.shape[3] != Dh:
        return False
    if jnp.dtype(kq.dtype) != jnp.dtype(jnp.int8):
        return False
    nb = ks.shape[-1]
    if nb < 1 or Dh % nb or ks.shape[:3] != kq.shape[:3]:
        return False
    if tuple(pos.shape) != (S,):
        return False
    T = kq.shape[1]
    if not interpret and T < MIN_T_DEQUANT:
        return False
    return bool(_pick_bt(T))


# ----------------------------------------------------------- dispatch
def try_decode_attend(q, k, v, pos, scale=None):
    """try_* dispatch entry (the house policy shape): result or None."""
    use, interpret = active()
    if not use:
        return None
    if not probe_decode(q, k, v, pos, scale, interpret=interpret):
        return None
    return decode_attend(q, k, v, pos, scale, interpret=interpret)


def try_dequant_attend(q, kq, ks, vq, vs, pos, scale=None):
    use, interpret = active()
    if not use:
        return None
    if not probe_dequant(q, kq, ks, vq, vs, pos, scale,
                         interpret=interpret):
        return None
    return dequant_attend(q, kq, ks, vq, vs, pos, scale,
                          interpret=interpret)
