"""Shared int8 blockwise quantize/dequantize primitive (EQuARX wire).

ONE implementation of the scheme that previously lived in three
places: gradsync's bucketed collectives, the block-quantized decode KV
cache (models/transformer.py), and the collective all-reduce wire
(parallel/collective.py) all route here now. Wire format is unchanged
byte-for-byte: per-block fp32 scales = absmax/127 (zero blocks get a
unit scale so 0/0 never happens), codes = clip(round(x/scale), ±127)
as int8 — `quantize_int8_blockwise_reference` IS the gradsync
composition, moved.

The Pallas kernel computes absmax + scale + round/clip in one VMEM
pass per row block (the guide's quantization-kernel pattern, minus
stochastic rounding — the error-feedback loop in gradsync already owns
rounding bias). Its arithmetic is the same jnp expression evaluated
per block, so codes and scales are bit-identical to the reference in
interpret mode, and the registry parity gate pins that. Scales come
back lane-replicated from the kernel ([nb, 128]) because a 1-lane
VMEM tile is not legal on hardware; the wrapper slices [:, :1] so
callers keep the historical [nb, 1] shape.

Dequantize stays a jnp one-liner on purpose: everywhere it matters it
should FUSE into the consumer instead of materializing fp32 (that is
exactly what decode_attention.dequant_attend does for the KV cache).

This module imports NO Pallas code at module level (every int8
producer imports it — the pallas pieces load lazily inside the kernel
entry points only).
"""
import jax
import jax.numpy as jnp

__all__ = ["quantize_int8_blockwise", "dequantize_int8_blockwise",
           "quantize_int8_blockwise_reference", "quantize_int8_pallas",
           "try_quantize", "probe_quant", "STATS", "DEFAULT_BLOCK_ROWS"]

STATS = {"pallas_calls": 0}

DEFAULT_BLOCK_ROWS = 512

# VMEM budget for one [rows, block_size] fp32 tile (plus the int8 and
# scale outputs) — conservative vs the flash kernel's 2M-element scores
# budget since three buffers are live.
_VMEM_BUDGET = 1024 * 1024


def quantize_int8_blockwise_reference(flat, block_size=256):
    """The jnp reference composition (gradsync's original code, moved
    verbatim): flat [n] -> (codes int8 [n/bs, bs], scales f32
    [n/bs, 1])."""
    blocks = flat.reshape(-1, block_size)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = absmax / 127.0
    safe = jnp.where(scales == 0, 1.0, scales)
    q = jnp.clip(jnp.round(blocks / safe), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_int8_blockwise(q, scales):
    """codes [nb, bs] + scales [nb, 1] -> flat f32 [nb*bs]."""
    return (q.astype(jnp.float32) * scales).reshape(-1)


def _pick_rows(nb, block_size, pref=None):
    """Legal row block for the [nb, block_size] layout: 128-multiple or
    the full axis (fa._pick_block), shrunk to the VMEM budget."""
    from ..pallas import flash_attention as fa
    br = fa._pick_block(nb, pref or DEFAULT_BLOCK_ROWS)
    while br and br * block_size > _VMEM_BUDGET and br > 128:
        nxt = fa._pick_block(nb, br // 2)
        if not nxt or nxt == br:
            break
        br = nxt
    if br and br * block_size > _VMEM_BUDGET and br != nb:
        return 0
    return br


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                        # [br, bs] f32
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)   # [br, 1]
    scale = absmax / 127.0
    safe = jnp.where(scale == 0, 1.0, scale)
    q_ref[...] = jnp.clip(jnp.round(x / safe), -127, 127
                          ).astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def quantize_int8_pallas(flat, block_size=256, block_rows=None,
                         interpret=False):
    """One-pass fused quantize: grid over row blocks, absmax and codes
    computed from a single VMEM residency of each block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..pallas import flash_attention as fa
    nb = flat.size // block_size
    br = _pick_rows(nb, block_size, block_rows)
    if not br:
        raise NotImplementedError("no legal row block")
    STATS["pallas_calls"] += 1
    x2 = flat.reshape(nb, block_size).astype(jnp.float32)
    q, s_rep = pl.pallas_call(
        _quant_kernel,
        grid=(nb // br,),
        in_specs=[pl.BlockSpec((br, block_size), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, block_size), lambda i: (i, 0)),
            pl.BlockSpec((br, fa._LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block_size), jnp.int8),
            jax.ShapeDtypeStruct((nb, fa._LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="quant_int8",
        interpret=interpret,
    )(x2)
    return q, s_rep[:, :1]


def probe_quant(flat, block_size=256, *, interpret=False):
    """STATIC acceptance: 1-D float input, whole blocks, a legal row
    tile. (Shape-only — works on ShapeDtypeStruct.)"""
    if getattr(flat, "ndim", None) != 1 or block_size < 1:
        return False
    # f32 only: the wire format's scales are fp32 and the reference
    # derives them in the input dtype — keep the two paths bit-equal
    if jnp.dtype(flat.dtype) != jnp.dtype(jnp.float32):
        return False
    n = flat.shape[0]
    if n == 0 or n % block_size:
        return False
    return bool(_pick_rows(n // block_size, block_size))


def try_quantize(flat, block_size=256):
    """try_* dispatch entry: the fused kernel's (codes, scales), or
    None -> caller runs the jnp reference."""
    from ..registry import active
    use, interpret = active()
    if not use:
        return None
    if not probe_quant(flat, block_size, interpret=interpret):
        return None
    return quantize_int8_pallas(flat, block_size, interpret=interpret)


def quantize_int8_blockwise(flat, block_size=256):
    """THE shared entry every int8 producer calls: the fused kernel
    where its gate and probe pass (through the ops.registry.accel
    seam), else the jnp reference — same bits either way."""
    from ..registry import accel
    fused = accel("int8_quant")
    if fused is not None:
        got = fused(flat, block_size=block_size)
        if got is not None:
            return got
    return quantize_int8_blockwise_reference(flat, block_size)
