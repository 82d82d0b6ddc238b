"""Every Pallas kernel, declared to the registry.

One KernelSpec per kernel: the try_* dispatch entry, the jnp reference
composition it must match, a STATIC capability probe (runs on
jax.ShapeDtypeStruct — meshlint and the CLI probe without data), the
parity tolerance, and a small interpret-runnable example for the
selftest gate.

The probes mirror each try_* function's own acceptance conditions
minus the active() backend gate — fn stays self-gating (dispatch
correctness never depends on a probe), the probe exists so OTHER
subsystems can ask "would this kernel take these shapes?" statically.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ..pallas import flash_attention as fa
from ..pallas import layer_norm as ln
from ..pallas import embedding as emb
from ..pallas import grouped_matmul as gm
from ..pallas import kda
from ..pallas import selective_scan as ssm
from . import decode_attention as da
from . import quant
from .. import kernels_scan as scan
from .registry import KernelSpec, register


# ------------------------------------------------------------ layer_norm
def _ln_reference(x, scale, bias, eps, begin_norm_axis):
    """The (y, mean, var) triple the op kernel's jnp fallback produces
    for minor-axis norm — what try_layer_norm returns."""
    C = x.shape[-1]
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1)
    var = jnp.var(xf, axis=-1)
    y = ((xf - mean[..., None]) / jnp.sqrt(var[..., None] + eps)
         * scale.reshape(C).astype(jnp.float32)
         + bias.reshape(C).astype(jnp.float32)).astype(x.dtype)
    return y, mean.squeeze(), var.squeeze()


def _ln_probe(x, scale, bias, eps, begin_norm_axis, *, interpret=False,
              **kw):
    if scale is None or bias is None:
        return False
    ndim = getattr(x, "ndim", 0)
    if begin_norm_axis != ndim - 1 or ndim < 2:
        return False
    C = x.shape[-1]
    if C % 128 != 0 and C > 256:
        return False
    rows = x.shape[-2]
    if rows < 8:
        return False
    br = ln._pick_rows(rows, C)
    if not br or (rows // br) * br != rows:
        return False
    if ndim >= 3 and rows * C > ln._BLOCK_BUDGET:
        return False
    return True


def _ln_example(rng):
    x = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(128), jnp.float32)
    b = jnp.asarray(rng.standard_normal(128), jnp.float32)
    return (x, g, b, 1e-5, 1), {}


register(KernelSpec(
    name="layer_norm",
    fn=ln.try_layer_norm,
    reference=_ln_reference,
    probe=_ln_probe,
    tol=(2e-5, 2e-5),
    op_types=("layer_norm",),
    example=_ln_example,
    note="fused minor-axis LayerNorm, fwd+bwd (custom_vjp)",
))


# -------------------------------------------------------- flash_attention
def _flash_bhtd(q, k, v, layout="bhtd"):
    """Shape structs of q, k, v as the tiled kernel sees them."""
    if layout != "bthd":
        return q, k, v

    def swap(x):
        B, T, H, D = x.shape
        return jax.ShapeDtypeStruct((B, H, T, D), x.dtype)
    return swap(q), swap(k), swap(v)


def _flash_probe(q, k, v, bias=None, causal=False, scale=None,
                 with_lse=False, causal_offset=0, *, interpret=False,
                 layout="bhtd", window=None, packed=None, n_heads=None,
                 **kw):
    """try_flash's own tests, less the backend gate: the short kernel's
    pick, then the tiled kernel's gate and shapes (a window is the tiled
    kernel's alone, on a causal, unshifted diagonal, without the lse); a
    packed call's segments where the short kernel does not take them
    whole."""
    if packed is not None:
        if fa.picks_packed(q, k, v, packed, n_heads, bias, interpret,
                           window):
            return True
        q, k, v = fa.packed_segments(q, k, v, packed, n_heads)
        layout = "bthd"
    if getattr(q, "ndim", 0) != 4:
        return False
    if window is not None:
        if with_lse or causal_offset or not causal:
            return False
        window = fa._window(window, causal, fa._tiled_dims(q, k, layout)[1])
    if fa.picks_short(q, k, v, bias, with_lse, causal_offset, layout,
                      interpret, window):
        return True
    if not interpret \
            and fa._tiled_dims(q, k, layout)[1] < fa.tiled_min_len(
                with_lse, layout):
        return False
    return fa.supports(*_flash_bhtd(q, k, v, layout), bias=bias)


def _flash_example(rng):
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    return (q, k, v), {"causal": True}


register(KernelSpec(
    name="flash_attention",
    fn=fa.try_flash,
    reference=fa.flash_attention_reference,
    probe=_flash_probe,
    tol=(2e-5, 2e-5),
    op_types=("flash_attention",),
    example=_flash_example,
    note="fused attention, fwd+bwd (custom_vjp): one-tile kernel on "
         "[B,T,H*D] for short sequences, tiled online-softmax for long",
))


# ------------------------------------------------------------ lookup_pool
def _emb_probe(table, inv, weights=None, pool="sum", *,
               interpret=False, **kw):
    if pool not in ("sum", "mean"):
        return False
    if getattr(table, "ndim", 0) != 2 or getattr(inv, "ndim", 0) != 2:
        return False
    C, D = table.shape
    R, F = inv.shape
    if R < 8:
        return False
    br = emb._pick_rows(R, C, D, F)
    return bool(br) and (R // br) * br == R


def _emb_example(rng):
    table = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    inv = jnp.asarray(rng.randint(-1, 64, size=(16, 4)), jnp.int32)
    return (table, inv), {"pool": "mean"}


register(KernelSpec(
    name="lookup_pool",
    fn=emb.try_lookup_pool,
    reference=emb.lookup_pool_reference,
    probe=_emb_probe,
    tol=(2e-5, 2e-5),
    op_types=("lookup_pool", "fused_embedding_seq_pool"),
    example=_emb_example,
    note="fused embedding lookup+pool (one-hot MXU gather)",
))


# ---------------------------------------------------------- decode_attend


def _da_example(rng):
    S, T, H, Dh = 4, 128, 2, 128
    q = jnp.asarray(rng.standard_normal((S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, T, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, T, H, Dh)), jnp.float32)
    pos = jnp.asarray(rng.randint(0, T, size=(S,)), jnp.int32)
    return (q, k, v, pos), {}


register(KernelSpec(
    name="decode_attend",
    fn=da.try_decode_attend,
    reference=da.decode_attend_reference,
    probe=da.probe_decode,
    tol=(2e-5, 2e-5),
    op_types=("decode_attend",),
    example=_da_example,
    note="single-token ragged decode attention over the slot pool",
))


# ----------------------------------------------------- dequant_attend_int8


def _dq_example(rng):
    S, T, H, Dh, qb = 4, 128, 2, 128, 64
    nb = Dh // qb
    q = jnp.asarray(rng.standard_normal((S, H, Dh)), jnp.float32)
    kq = jnp.asarray(rng.randint(-127, 128, size=(S, T, H, Dh)),
                     jnp.int8)
    vq = jnp.asarray(rng.randint(-127, 128, size=(S, T, H, Dh)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, size=(S, T, H, nb)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, size=(S, T, H, nb)),
                     jnp.float32)
    pos = jnp.asarray(rng.randint(0, T, size=(S,)), jnp.int32)
    return (q, kq, ks, vq, vs, pos), {}


register(KernelSpec(
    name="dequant_attend_int8",
    fn=da.try_dequant_attend,
    reference=da.dequant_attend_reference,
    probe=da.probe_dequant,
    tol=(2e-5, 2e-5),
    op_types=("dequant_attend_int8",),
    example=_dq_example,
    note="fused int8 dequantize-attend over the block-quantized KV cache",
))


# -------------------------------------------------------------- int8_quant


def _q_example(rng):
    # 1024 blocks: two row tiles of the default 512
    flat = jnp.asarray(rng.standard_normal(1024 * 256), jnp.float32)
    # a zero block exercises the safe-scale path
    flat = flat.at[:256].set(0.0)
    return (flat,), {"block_size": 256}


register(KernelSpec(
    name="int8_quant",
    fn=quant.try_quantize,
    reference=quant.quantize_int8_blockwise_reference,
    probe=quant.probe_quant,
    # codes are int8 (compared exactly); scales are the same jnp
    # expression per block — bit-equal, the tol is slack for the fp32
    # reduction order
    tol=(0.0, 1e-7),
    op_types=("int8_quant",),
    example=_q_example,
    note="shared int8 blockwise quantize (EQuARX wire format)",
))


# ---------------------------------------------------------- moe_expert_ffn


def _moe_example(rng):
    N, H, F, E, k = 32, 16, 24, 2, 2
    x = jnp.asarray(rng.standard_normal((N, H)), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 4, size=(N, 1)), jnp.int32)
    idx = jnp.concatenate([idx, (idx + 1) % 4], axis=1)
    tw = jnp.asarray(rng.uniform(0.2, 0.8, size=(N, k)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, H, F)) * 0.3, jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((E, H, F)) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, F, H)) * 0.3, jnp.float32)
    return (x, idx, tw, w1, w3, w2), {"first_expert": 1}


register(KernelSpec(
    name="moe_expert_ffn",
    fn=gm.try_expert_ffn,
    reference=gm.expert_ffn_reference,
    probe=gm.supports,
    tol=(2e-5, 2e-5),
    op_types=("moe_expert_ffn",),
    example=_moe_example,
    note="no-drop expert FFN of the experts held here: grouped products "
         "over pairs sorted by expert, fwd+bwd (custom_vjp)",
))


# ----------------------------------------------------------- kda_attention
def _kda_example(rng):
    B, T, H, D = 1, 80, 2, 128         # T off a multiple of the chunk
    def unit(*shape):
        x = rng.standard_normal(shape)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True),
                           jnp.float32)
    q, k = unit(B, T, H, D), unit(B, T, H, D)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0.0, 3.0, size=(B, T, H, D)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, size=(B, T, H)), jnp.float32)
    return (q, k, v, g, beta), {}


register(KernelSpec(
    name="kda_attention",
    fn=kda.try_kda,
    reference=scan.kda_recurrent,
    probe=kda.supports,
    tol=(1e-4, 1e-4),
    example=_kda_example,
    note="gated delta rule, per-channel decay: chunks of 64 in VMEM (WY / "
         "UT transform, sub-blocks of 16), the state resident across a "
         "head's chunks, fwd+bwd (custom_vjp, hand-derived); float32 at "
         "HIGHEST; Dk, Dv multiples of 128, else the op's jnp composition; "
         "reference: the token-by-token recurrence",
))


# ---------------------------------------------------------- selective_scan
def _scan_example(rng):
    Bsz, T, C, N = 1, 300, 256, 16     # T off a multiple of the chunk
    x = jnp.asarray(rng.standard_normal((Bsz, T, C)), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (Bsz, T, C))), jnp.float32)
    a_log = jnp.asarray(np.log(np.broadcast_to(np.arange(1, N + 1), (C, N))),
                        jnp.float32)
    B = jnp.asarray(rng.standard_normal((Bsz, T, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((Bsz, T, N)), jnp.float32)
    D = jnp.ones((C,), jnp.float32)
    return (x, dt, a_log, B, Cm, D), {}


register(KernelSpec(
    name="selective_scan",
    fn=ssm.try_selective_scan,
    reference=scan.selective_scan_recurrent,
    probe=ssm.supports,
    # both walk the same recurrence in float32 and differ only in the
    # order of the 16-term sum of y: a few float32 spacings of y's largest
    # terms, which sum 300 tokens of decayed inputs
    tol=(1e-5, 1e-5),
    example=_scan_example,
    note="diagonal selective scan (Mamba-1): the state [N, channels] "
         "resident in VMEM across a block's chunks of 256 tokens, one "
         "state saved a chunk, fwd+bwd (custom_vjp, hand-derived; the "
         "backward rebuilds a chunk's states); float32 on the VPU; "
         "channels multiples of 128, else the op's chunked composition; "
         "reference: the token-by-token recurrence",
))
