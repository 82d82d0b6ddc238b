"""Pallas flash-attention kernels for TPU, forward AND backward: one for
long sequences, one for short ones. try_flash picks between them and
XLA's composition (ops/kernels_nn.py::_sdpa) from the shapes, the layout
and the dtype of its arguments, by crossovers measured on the chip (the
table above SHORT_MIN_SEQ_LEN).

**The tiled kernel** (flash_attention, flash_attention_with_lse; arrays
[B, H, T, D]). Online-softmax attention (FlashAttention algorithm)
written as pipelined Pallas TPU kernels: the grid is (batch*heads,
q_blocks, k_blocks) with the k dimension innermost and marked
"arbitrary", so Mosaic double-buffers the K/V block DMAs against the
MXU matmuls. Online-softmax state (m, l, acc) lives in VMEM scratch that
persists across the k iterations of one q block; outputs are flushed on
the last k step. No [T,S] score matrix ever hits HBM. The backward pass
is the standard flash recomputation: forward saves only the per-row
logsumexp, and ONE kernel, flash_attention_bwd, rebuilds the
probabilities of a block once and gives all five products their
operands: its grid is (batch*kv_heads, k_blocks, group*q_blocks) with
the q blocks innermost, dk and dv accumulate in VMEM scratch over the q
blocks of the query heads that share the key-value head, and dq
accumulates in a float32 buffer that holds the dq of all those heads
([group*T, D]: 32 MiB of VMEM at the 8192-token cell, with the output's
buffers) and is written back once a key-value head. Where that buffer
would pass FUSED_BWD_VMEM (which the shapes decide, nothing else) the
two kernels this one replaced run instead, flash_attention_dq (k blocks
innermost) and flash_attention_dkv, each rebuilding the probabilities.
Under a causal diagonal a block above it is skipped (and not fetched:
the index maps hand a skipped step the block of a neighbouring active
one), a block the diagonal crosses is masked, and a block wholly under
it takes the same body without the mask. This replaces the reference's
unfused softmax(QK^T)V composition
(python/paddle/fluid/nets.py:scaled_dot_product_attention) as the
long-sequence attention path, and is registered through jax.custom_vjp
so it stays on the training path under jax.value_and_grad.

**The short-sequence kernel** (flash_attention_bthd; arrays [B, T, H, D]
= [B, T, H*D], the layout the model keeps, so no transpose on either
side and no 64-wide minor dimension anywhere). Where the whole key axis
fits one block there is nothing to tile and nothing online: a grid step
takes one batch row, walks the heads inside the body one 128-lane
group at a time (two heads at D = 64, picked by lane masks so that every
product is 128 lanes wide), and computes plain max / exp / sum in
float32 on [256, S] scores that never leave VMEM. ONE backward kernel
recomputes p once and gives dq, dk and dv (as the tiled kernel's does
since PR 33). Residuals: out and the per-row logsumexp [B, H, T]. p is rounded
to the operands' dtype only as the operand of the second matmul, as
_sdpa does; the scores themselves stay float32 (_sdpa rounds them to
bf16), so it is at least as exact as the composition it replaces. At the
transformer-base training shape ([128, 256, 8 x 64] bf16, v5e) it takes
1.06 ms forward + backward against the composition's 2.96 and the tiled
kernel's 4.90 (PERF.md section 6, PR 28). It also reads q, k and v out
of a fused projection as the projection's matmul wrote it
(flash_attention_packed: one [B, T, 3*H*D], or q and one [B, S, 2*H*D];
a block's index map picks the 128-lane-aligned segment) and writes the
gradient of such an array as one array of its shape, so the model's
`split` and its transpose (a zero-filled pad and update per slice) leave
the step (PR 40).

Supported extras (covers the flagship transformer end-to-end):
- `bias`: additive key-padding bias of shape [B, S] (the [B,1,1,S]
  pad-mask the NMT model builds, squeezed). Carried as [B, 1, S] so
  every block keeps Mosaic's (8,128)-or-full tiling rule; the per-head
  grid row maps onto the batch row inside the index_map (no per-head
  materialization). The bias is DIFFERENTIABLE: the backward kernel row-sums
  the recomputed ds block into a per-(batch,head) [BH,1,S] f32 output
  (accumulated in-place across the innermost q steps) and the vjp
  reduces it over heads — a learnable additive bias (e.g. ALiBi-style
  per-position offsets) trains identically to the jnp reference
  (tests/test_flash_bias_grad.py). bias=None statically compiles the
  bias add and the db output out of every kernel, so the no-bias path
  pays nothing for this feature. Full [B,H,T,S] biases take the
  caller's jnp fallback.
- `causal`: in-kernel triangular masking + whole-block skipping above
  the diagonal. `causal_offset` shifts the diagonal (offset -1 = strict
  triangle, the striped-ring case). CONVENTION for fully-masked rows
  (possible only with negative offsets): the normalized `out` row is
  implementation-defined (it averages v over whichever blocks ran — NOT
  the reference's uniform softmax over all keys), while its lse is
  ~-1e30, so (out, lse)-merging callers (ring attention) weight it to
  zero. Do not read fully-masked rows from the plain `flash_attention`
  output.
- `window` (with `causal`, on an unshifted diagonal): a sliding window,
  query t sees the keys `t - window < s <= t` only. The band of blocks
  has a lower edge as well as the diagonal: a q block has a FIRST k block
  (_first_k) as well as a last (_last_k), a k block a LAST q block
  (_last_q) as well as a first (_first_q), and the innermost grid axis
  walks the band's blocks only (_band_steps: two k blocks a q block of
  eight at 8192 with a window of 1024 and 1024 x 1024 blocks), so a
  block outside the band is neither computed, fetched nor stepped over;
  a block that either edge crosses is masked, one wholly inside takes
  the body without the mask. In the one backward kernel a q block's
  rows of the resident dq open at the first k block of its band and
  close at the last. The kernels of a windowed call carry names of
  their own (flash_attention_win_fwd, _win_bwd; _win_dq / _win_dkv over
  FUSED_BWD_VMEM) and STATS["tiled_window"] counts its backwards.
  window=None statically compiles all of it out: the kernels, their
  names, grids and index maps are then what they were. A window at or
  over the key length is no window (_window).

The tiled kernel's blocks default to 1024 queries x 2048 keys, 1024 x
1024 under a causal diagonal (clamped to a VMEM budget per head dim, see
_choose_blocks; the chip's sweep is the table above DEFAULT_BLOCK_Q);
what the chip measured of it is in PERF.md sections 5 and 7.6.

When to use which path is try_flash's to say, and only its: the short
kernel for `bthd` arrays with both lengths 256, 384 or 512; the tiled
kernel for `bthd` arrays from S = 1024 on (it beats the composition
there forward and forward + backward, 2x at 1024, and from 2048 on it
is the only path whose backward fits the chip's memory); the
composition elsewhere. `bhtd` callers (ulysses, ring attention) were
not measured and keep their gate of 4096: the "XLA's fused attention is
faster below ~4k" it came with was a reading from before the direct
runtime with no shape stated, and the table above SHORT_MIN_SEQ_LEN
replaces it for the op's path only. Interpret mode (CPU tests) bypasses
the performance gates.

Where the tiled kernel's time goes at D=64 is in PERF.md sections 5 and
7.6: every product of this attention contracts over 64 of the MXU's 128
rows or fills 64 of its 128 columns, so the chip's floor at this head
is about twice the counted one, and per score element the kernels do
2D=128 MXU flops against ~10 VPU ops (exp/max/mul in f32).

An escape from that VPU cost is implemented behind `softmax_dtype`: with
jnp.bfloat16, the probability exp (the dominant VPU cost — one
transcendental per score element, forward and backward) runs in bf16 while
everything that controls numerics stays f32: the scores matmul
accumulation, the running max m, the scale factor alpha, the row-sum l
(f32-accumulated reduction over bf16 p), and the output rescale. The
bf16 exp argument is (s - m) <= 0, so the absolute error is bounded by
bf16's ~3-digit mantissa on values in (0, 1] — ~0.4% per element,
averaged down by the row sums. Default stays f32 (exact flash
algorithm); set_softmax_dtype(jnp.bfloat16) or the per-call kwarg opts
in. NOTE: the dtype is baked in at TRACE time — callers holding an
already-jitted/cached executable (including Executor's program cache)
keep the dtype they were traced with; flip the knob before building
the step function. No on-chip measurement of the bf16 variant exists
yet (the sweep needs the real chip); until one is recorded here and in
SURVEY §5, treat it as an unvalidated escape hatch.
"""
import functools

import jax
import jax.numpy as jnp

# the gate lives beside mosaic_target in ops/registry.py; the two names
# are re-exported for the callers that spell them fa.set_mode / fa.active
from ..registry import active, set_mode  # noqa: F401

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_reference", "try_flash", "STATS", "set_mode",
           "set_softmax_dtype", "active", "MIN_SEQ_LEN",
           "MIN_SEQ_LEN_BTHD", "SHORT_MIN_SEQ_LEN", "SHORT_MAX_SEQ_LEN",
           "flash_attention_bthd", "supports_short", "picks_short",
           "tiled_min_len", "flash_attention_packed", "picks_packed",
           "packed_segments", "unpack"]

_NEG_INF = -1e30

# The crossovers the chip showed (v5e, B*H = 1024, D = 64, bf16, bias on,
# causal on and off, arrays in the op's `bthd` layout, S = T; the table
# and the command are in PERF.md section 6, PR 28). ms forward + backward
# (forward alone), short kernel / XLA's composition / tiled kernel:
#   S =  128    0.60 (0.21) /  0.58 (0.28) /  2.31 (0.90)
#   S =  256    1.06 (0.35) /  2.96 (0.83) /  4.90 (2.02)
#   S =  384    1.84 (0.68) /  7.18 (1.58) /  6.68 (2.99)
#   S =  512    3.64 (0.98) / 12.15 (3.07) /  8.73 (3.83)
#   S = 1024   15.16 (3.49) / 47.42 (11.9) / 25.10 (10.0)
#   S = 2048    not compiled / out of memory (30.3) / 77.19 (26.9)
# The short kernel (whole key axis in one block, `bthd`) is picked where
# it was measured to win: both lengths in [SHORT_MIN_SEQ_LEN,
# SHORT_MAX_SEQ_LEN] and multiples of 128 (Mosaic has compiled it at no
# other length; the ragged lengths of the tests run in interpret mode
# only). At 128 it only ties; at 1024 it still wins but its unrolled
# tiles take 33 s to compile a kernel, so the tiled kernel keeps that
# length.
SHORT_MIN_SEQ_LEN = 256
SHORT_MAX_SEQ_LEN = 512

# `bhtd` callers (parallel/ulysses.py, parallel/ring_attention.py and its
# `with_lse`) keep the gate they had: no cell measures them, nothing was
# timed at their [B, H/sp, T, D] shapes or at a small B*H, and PR 28 did
# not change what they run. The "XLA is faster below ~4k" it came with
# was a reading from before the direct runtime, with no shape stated.
MIN_SEQ_LEN = 4096

# The op's `bthd` path, the one the table measured (B*H = 1024, the
# transposes try_flash makes for the tiled kernel included): from this
# key length on the tiled kernel beats the composition forward and
# forward + backward. At 512 it wins only with the backward (8.7 against
# 12.1 ms) and loses the forward alone (3.8 against 3.1), which
# try_flash cannot tell apart.
MIN_SEQ_LEN_BTHD = 1024

# Trace-time evidence that the Pallas path (not the jnp fallback) was
# selected — tests assert on this (VERDICT r1: the kernel must demonstrably
# run under value_and_grad, not silently fall back).
STATS = {"pallas_calls": 0,
         # which backward the tiled kernel traced: one kernel with dq
         # resident in VMEM, or dq and dk / dv apart (FUSED_BWD_VMEM)
         "tiled_bwd_fused": 0, "tiled_bwd_split": 0,
         # backwards traced with a window (a band of blocks, kernels
         # named flash_attention_win_*)
         "tiled_window": 0,
         # short-kernel calls that read q, k, v from the fused
         # projection's [B, T, 3*H*D] (or q and a [B, S, 2*H*D] kv)
         # and write its gradient packed the same way
         "short_packed": 0}

# m/l scratch rows are stored lane-replicated at this width (1-lane
# vectors are not a legal VMEM tile).
_LANES = 128

# Shared by supports() and flash_attention() so the dispatch guard and
# the call can't drift (2048x2048 fails to compile: the fp32 scores tile
# exceeds VMEM). _choose_blocks clamps the pair to a VMEM budget for
# larger head dims. Swept on the chip once the backward was one kernel
# (v5e, D = 64, bf16, `bthd` arrays, tools/bench_attention.py; PERF.md
# section 6, PR 33): ms forward / backward (the vjp alone), block_q x
# block_k; [B, S, H] with T = S:
#              [2, 8192, 32 over 8]  [64, 2048, 16], bias      [128, 1024, 8], bias
#              causal                causal       full         causal       full
#   512x512    22.68 / 22.13         36.47/40.15  49.22/50.95  13.32/15.10  15.45/16.67
#   512x1024   12.92 / 20.91         26.84/40.86  31.17/48.17  10.83/15.82  10.13/15.76
#   512x2048   12.22 / 21.27         28.35/45.94  26.21/45.86
#   1024x512   21.18 / 20.74         37.70/41.46  45.60/48.32  14.44/16.14  14.40/16.14
#   1024x1024  11.05 / 19.84         24.57/40.24  28.41/47.26   8.79/15.79  10.02/15.98
#   1024x2048  11.85 / 21.10         27.84/47.46  26.99/46.62
# (the two-kernel backward it replaced, at 8192: 1024x2048 12.05 / 29.32,
# 1024x1024 11.44 / 28.35.) Under a causal diagonal 1024 x 1024 wins at
# every length: blocks of 2048 keys compute 1.25 x the causal half at
# 8192 where blocks of 1024 compute 1.125 x. Without one, 2048 keys a
# block are 2-5% ahead at 2048. Keys in blocks of 512 double the
# forward's time. So the key block follows `causal` (_choose_blocks).
# A window keeps the causal pair. Swept on the chip (v5e,
# [1, 8192, 32 over 4, 128] bf16, `bthd` arrays, causal, a window of 1024,
# no bias, tools/bench_attention.py ... causal,nobias,window1024; PERF.md
# section 6, PR 36): ms forward / backward (the vjp alone) / both in one
# program, and the blocks of 1024^2 score elements that the pair computes
# for a band of 7.5 a head:
#   1024x1024   2.98 / 4.60 /  7.48   15
#   512x1024    3.31 / 4.70 /  7.80   15
#   512x512     4.35 / 3.97 /  8.12   11.25
#   256x512     4.69 / 4.91 /  9.45   11.25
#   1024x512    5.00 / 4.82 /  9.62   15
#   512x256     7.53 / 5.41 / 12.77   11.25
#   256x256     6.97 / 5.99 / 12.93   9.375
# (the same arrays with no window, 1024 x 1024: 5.65 / 9.66 / 15.12, 36
# blocks a head.) The pair that computes the least loses: as without a
# window, keys in blocks of 512 or 256 cost the forward more in steps than
# they save in masked area (its online softmax rescales the accumulator
# every k step), and only the backward, which has no such state, is
# faster at 512 x 512 (3.97 against 4.60). One pair serves both, and it
# is the causal one: a window changes no block that `causal` picks.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 2048
DEFAULT_BLOCK_K_CAUSAL = 1024

# The tiled backward is ONE kernel (flash_attention_bwd) where the dq of
# the query heads that share a key-value head can stay in VMEM for the
# whole head (_bwd_resident_bytes): under this many bytes. Over it, dq
# and dk / dv are two kernels that each rebuild the probabilities. The
# v5e has 128 MiB of VMEM; with _TILED_VMEM beside it this budget asks
# for 96 MiB at most (the short kernel's _SHORT_VMEM_MAX), which a
# described v5e compiles at [1, 65536, 1 x 128]. The cell's [2, 8192, 32
# over 8 x 64] holds 32 MiB and ran on the chip: 18.10 ms a step where the
# two kernels took 27.48 (PERF.md section 6, PR 33).
FUSED_BWD_VMEM = 64 * 1024 * 1024
# the limit asked for the tiled backward's blocks and [bq, bk] float32
# temporaries beside the resident dq: at 1024 x 2048 blocks they take
# 21.2 MiB (a described v5e refuses the cell's kernel at 48 MiB in all
# and takes 53.19)
_TILED_VMEM = 32 * 1024 * 1024


# dtype of the probability exp inside the kernels; f32 = exact flash
# algorithm, bf16 = the VPU-pressure escape (see module docstring)
_SOFTMAX_DTYPE = jnp.float32


def set_softmax_dtype(dtype):
    """Set the in-kernel probability-exp dtype. Trace-time only: jitted
    executables (and Executor's program cache) keep the dtype they were
    traced with — call this BEFORE building the step function."""
    global _SOFTMAX_DTYPE
    dtype = jnp.dtype(dtype)
    assert dtype in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    _SOFTMAX_DTYPE = dtype


def _pick_block(n, pref):
    """Largest 128-MULTIPLE block <= pref that divides n, or n itself
    when one block covers the whole axis (block == array dim is always a
    legal Mosaic tile). Returns 0 when no legal block exists — lane dims
    that are neither 128-multiples nor the full axis violate the Mosaic
    tiling rule on hardware (interpret mode wouldn't catch it), so such
    shapes must take the fallback path. Scans multiples downward (a
    naive halving loop can land on divisors like 960 that are not
    128-multiples)."""
    if n <= 128:
        return n
    if pref >= n:
        return n
    for b in range(pref // 128 * 128, 0, -128):
        if n % b == 0:
            return b
    return 0


def _choose_blocks(T, S, D, DV, pref_q=None, pref_k=None, causal=False):
    """The ONE block-selection policy (supports() and _prep share it):
    pick legal tiles (the defaults where the caller names none; the key
    block by `causal`, as the sweeps above DEFAULT_BLOCK_Q found, with
    a window or without), then shrink — re-legalizing through
    _pick_block at every step — until the fp32 scores tile fits the
    VMEM budget (measured on v5e: 2M elements compiles at head dim
    <= 64, 4M does not; halved budget for wider heads). Returns (0, 0)
    if no legal in-budget pair exists; what is legal does not depend on
    `causal`."""
    bq = _pick_block(T, pref_q or DEFAULT_BLOCK_Q)
    bk = _pick_block(S, DEFAULT_BLOCK_K_CAUSAL) \
        if causal and not pref_k else 0
    bk = bk or _pick_block(S, pref_k or DEFAULT_BLOCK_K)
    if not bq or not bk:
        return 0, 0
    budget = 2 * 1024 * 1024 if max(D, DV) <= 64 else 1024 * 1024
    while bq * bk > budget:
        if bq >= bk and bq > 128:
            nb = _pick_block(T, bq // 2)
            if not nb:
                return 0, 0
            bq = nb
        elif bk > 128:
            nb = _pick_block(S, bk // 2)
            if not nb:
                return 0, 0
            bk = nb
        else:
            break
    return bq, bk


def _causal_active(q_idx, k_idx, block_q, block_k, offset):
    """Does k block k_idx intersect rows <= the (bottom-right-aligned)
    diagonal of q block q_idx? offset = S - T aligns the diagonal to the
    bottom-right corner, matching jnp.tril(..., k=S-T) in the fallback."""
    return k_idx * block_k <= (q_idx + 1) * block_q - 1 + offset


def _causal_whole(q_idx, k_idx, block_q, block_k, offset):
    """Does k block k_idx lie wholly under the diagonal of q block q_idx
    (its last key visible to the block's first query)? Such a block needs
    no mask."""
    return (k_idx + 1) * block_k - 1 <= q_idx * block_q + offset


def _window_active(q_idx, k_idx, block_q, block_k, offset, window):
    """Does k block k_idx reach into the window of q block q_idx (its
    last key inside the window of the block's first query)? Query t sees
    the keys s with t + offset - window < s <= t + offset."""
    return (k_idx + 1) * block_k - 1 > q_idx * block_q + offset - window


def _window_whole(q_idx, k_idx, block_q, block_k, offset, window):
    """Does k block k_idx lie wholly over the window's lower edge (its
    first key inside the window of the block's last query)?"""
    return k_idx * block_k > (q_idx + 1) * block_q - 1 + offset - window


def _causal_mask(s, q_idx, k_idx, block_q, block_k, offset, window=None):
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if window is None:
        return jnp.where(q_pos + offset >= k_pos, s, _NEG_INF)
    # both edges in one compare: 0 <= (t + offset) - s < window, the
    # difference read as unsigned
    ahead = jax.lax.bitcast_convert_type(q_pos + offset - k_pos, jnp.uint32)
    return jnp.where(ahead < jnp.uint32(window), s, _NEG_INF)


def _on_block(causal, q_idx, k_idx, block_q, block_k, offset, body,
              window=None, inside=None):
    """Run `body(mask)` on block (q_idx, k_idx) for what the causal
    diagonal (and, with `window`, the band's lower edge) leaves of it:
    not at all outside, with `mask` (s -> masked s) where an edge crosses
    it, and with mask = None where it lies wholly inside (28 of the 36
    active blocks of a head at 8192 with 1024 x 1024 blocks) or nothing
    is causal. `inside` (windowed kernels, whose grid walks the band's
    steps only): is this step one of the band's at all."""
    if not causal:
        body(None)
        return
    at = (q_idx, k_idx, block_q, block_k, offset)
    whole = _causal_whole(*at)
    if window is None:
        pl.when(whole)(lambda: body(None))
        pl.when(_causal_active(*at) & jnp.logical_not(whole))(
            lambda: body(lambda s: _causal_mask(s, *at)))
        return
    active = inside & _causal_active(*at) & _window_active(*at, window)
    whole = whole & _window_whole(*at, window)
    pl.when(active & whole)(lambda: body(None))
    pl.when(active & jnp.logical_not(whole))(
        lambda: body(lambda s: _causal_mask(s, *at, window)))


# The four edges of the band of blocks, each for Python ints (the grid's
# size, counted where the call is made) and for the traced indices of an
# index map or a kernel body alike.
def _imax(a, b):
    return max(a, b) if isinstance(a, int) else jnp.maximum(a, b)


def _imin(a, b):
    return min(a, b) if isinstance(a, int) else jnp.minimum(a, b)


def _idiv(a, b):
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _last_k(i, block_q, block_k, offset, n_k):
    """The last k block that q block i reads under the causal diagonal.
    An index map of k / v hands a skipped step (j past it) this block
    again, so that Mosaic does not fetch what the body will not read."""
    reach = _imax((i + 1) * block_q - 1 + offset, 0)
    return _imin(_idiv(reach, block_k), n_k - 1)


def _first_q(j, block_q, block_k, offset, n_q):
    """The first q block that reads k block j: the q-side twin of
    _last_k, for the kernel that walks the q blocks innermost (its
    skipped steps come first and are handed the block that follows)."""
    start = _imax(j * block_k - offset, 0)
    return _imin(_idiv(start, block_q), n_q - 1)


def _first_k(i, block_q, block_k, offset, window):
    """The first k block that q block i reads under a window: the one
    that holds the lowest key of its first query."""
    return _idiv(_imax(i * block_q + offset - window + 1, 0), block_k)


def _last_q(j, block_q, block_k, offset, window, n_q):
    """The last q block that reads k block j under a window: the one
    that holds the last query whose window reaches the block's last
    key."""
    reach = _imax((j + 1) * block_k - 1 - offset + window - 1, 0)
    return _imin(_idiv(reach, block_q), n_q - 1)


def _band_steps(n_outer, first, last):
    """The inner grid axis of a windowed kernel: the most blocks that one
    outer block's band holds (15 of a head's 64 blocks lie in the band at
    8192 with a window of 1024 and 1024 x 1024 blocks, two a row; a grid
    over all 64 would step over the other 49)."""
    return max(last(i) - first(i) + 1 for i in range(n_outer))


def _precision(a):
    """Sub-fp32 operands multiply exactly on the MXU at DEFAULT, and
    Mosaic refuses any other setting for them ("Bad lhs type") — so an
    ambient jax.default_matmul_precision must not reach these dots.
    fp32 operands keep the ambient precision."""
    return None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _dot_t(a, b):
    """a @ b.T with fp32 accumulation, inputs kept in their (bf16) dtype
    so the MXU runs at full rate."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_precision(a),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    """a @ b with fp32 accumulation (bf16 inputs stay bf16 on the MXU)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_precision(a),
                               preferred_element_type=jnp.float32)


def _kv_row(group):
    """Row of k / v for row b of q where `group` query heads share one
    key-value head: q is [B * H, T, D] and k, v are [B * H / group, S, D],
    so b = batch * H + head reads batch * H / group + head // group."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _band_k(q_idx, j, block_q, block_k, offset, n_k, window):
    """(k block, is it one of the band's) of step j of q block q_idx in a
    grid whose innermost axis walks the k blocks: all of them, or with a
    window the band's only, from the q block's first."""
    if window is None:
        return j, None
    k_idx = _first_k(q_idx, block_q, block_k, offset, window) + j
    return k_idx, k_idx <= _last_k(q_idx, block_q, block_k, offset, n_k)


def _fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, causal, scale, n_k, offset,
                steps, p_dtype=jnp.float32, has_bias=True, window=None):
    """Grid (B*H, n_q, steps), k innermost: steps = n_k, or with a window
    the band's k blocks only. q_ref [bq, D]; k/v_ref [bk, D]; b_ref
    [1, bk]; scratch m/l [bq, _LANES] (lane-replicated), acc [bq, DV].
    """
    q_idx, j = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    k_idx, inside = _band_k(q_idx, j, bq, bk, offset, n_k, window)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(mask):
        # bf16 operands + fp32 accumulation: full-rate MXU, scale folded in
        # after the matmul
        s = _dot_t(q_ref[...], k_ref[...]) * scale
        if has_bias:
            s = s + b_ref[0, :].astype(jnp.float32)[None, :]    # [bq, bk]
        if mask is not None:
            s = mask(s)
        m_prev = m_ref[...][:, :1]                              # [bq, 1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # the full-tile exp is the dominant VPU cost; p_dtype=bf16 runs
        # it at the packed rate while m/alpha/l stay f32 (argument is
        # <= 0, so bf16's mantissa bounds the element error at ~0.4%).
        # A row that the band's lower edge masks out of its first block
        # sums exp(0) there; the next block's alpha = 0 wipes that
        p = jnp.exp((s - m_new).astype(p_dtype))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True,
                                         dtype=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(v_ref.dtype), v_ref[...])

    _on_block(causal, q_idx, k_idx, bq, bk, offset, _compute, window, inside)

    @pl.when(j == steps - 1)
    def _flush():
        m = m_ref[...][:, :1]
        l = jnp.maximum(l_ref[...][:, :1], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, :] = (m + jnp.log(l))[:, 0]


def _k_innermost_specs(kv, H, block_q, block_k, D, DV, causal, offset, n_k,
                       window=None):
    """The q, k, v and bias BlockSpecs of a grid (B*H, n_q, steps): k / v
    / bias blocks follow j, held at the row's last active block under a
    causal diagonal; with a window they start at the row's first."""
    if window is not None:
        def kj(i, j):
            return jnp.minimum(
                _first_k(i, block_q, block_k, offset, window) + j,
                _last_k(i, block_q, block_k, offset, n_k))
    elif causal:
        def kj(i, j):
            return jnp.minimum(j, _last_k(i, block_q, block_k, offset, n_k))
    else:
        def kj(i, j):
            return j
    return [
        pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, block_k, D),
                     lambda b, i, j: (kv(b), kj(i, j), 0)),
        pl.BlockSpec((None, block_k, DV),
                     lambda b, i, j: (kv(b), kj(i, j), 0)),
        pl.BlockSpec((None, 1, block_k),
                     lambda b, i, j: (b // H, 0, kj(i, j))),
    ]


def _k_steps(n_q, n_k, block_q, block_k, offset, window):
    """The innermost axis of a grid that walks the k blocks of a q
    block: n_k, or the band's most."""
    if window is None:
        return n_k
    return _band_steps(
        n_q, lambda i: _first_k(i, block_q, block_k, offset, window),
        lambda i: _last_k(i, block_q, block_k, offset, n_k))


def _fwd_call(q, k, v, bias, n_heads, causal, scale, block_q, block_k,
              interpret, p_dtype=jnp.float32, causal_offset=0,
              has_bias=True, window=None):
    """q [BH, T, D]; k/v [BH, S, D]; bias [B, 1, S] (mapped to the batch
    row b // n_heads by the index_map — no per-head materialization).
    has_bias=False statically skips the bias add (the operand is still
    threaded, but never read). Returns (out [BH,T,D], lse [BH,1,T])."""
    BH, T, D = q.shape
    S = k.shape[1]
    DV = v.shape[-1]
    n_q, n_k = T // block_q, S // block_k
    offset = S - T + causal_offset
    steps = _k_steps(n_q, n_k, block_q, block_k, offset, window)
    grid = (BH, n_q, steps)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale, n_k=n_k,
                          offset=offset, p_dtype=p_dtype,
                          has_bias=has_bias, window=window, steps=steps),
        grid=grid,
        in_specs=_k_innermost_specs(_kv_row(BH // k.shape[0]), n_heads,
                                    block_q, block_k, D, DV, causal,
                                    offset, n_k, window),
        out_specs=[
            pl.BlockSpec((None, block_q, DV), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, DV), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, DV), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=_kernel_name("fwd", window),
        interpret=interpret,
    )(q, k, v, bias)
    return out, lse


def _kernel_name(which, window):
    """A windowed call's kernels carry names of their own, so that a
    trace tells the band's calls from a full layer's."""
    return f"flash_attention_{'win_' if window is not None else ''}{which}"


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _p_and_ds(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref, mask, *,
              scale, p_dtype, has_bias):
    """The one recomputation every backward product reads: p [bq, bk] (in
    p_dtype) and ds = p (dp - delta) in float32, from the block's
    scores, the saved logsumexp and dp = dO v^T."""
    lse = lse_ref[0, :][:, None]                         # [bq, 1]
    delta = dl_ref[0, :][:, None]
    s = _dot_t(q_ref[...], k_ref[...]) * scale
    if has_bias:
        s = s + b_ref[0, :].astype(jnp.float32)[None, :]
    if mask is not None:
        s = mask(s)
    p = jnp.exp((s - lse).astype(p_dtype))               # [bq, bk]
    dp = _dot_t(do_ref[...], v_ref[...])                 # [bq, bk]
    return p, p * (dp - delta)


def _dq_kernel(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
               dq_ref, acc_ref, *, causal, scale, n_k, offset, steps,
               p_dtype=jnp.float32, has_bias=True, window=None):
    """Grid (B*H, n_q, steps) as the forward's: recompute p block-wise,
    accumulate dq in VMEM scratch, flush on the last k step. Runs only
    where the fused kernel's resident dq does not fit (FUSED_BWD_VMEM)."""
    q_idx, j = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    k_idx, inside = _band_k(q_idx, j, bq, bk, offset, n_k, window)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(mask):
        _, ds = _p_and_ds(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                          dl_ref, mask, scale=scale, p_dtype=p_dtype,
                          has_bias=has_bias)
        acc_ref[...] = acc_ref[...] + _dot(
            ds.astype(k_ref.dtype), k_ref[...]) * scale

    _on_block(causal, q_idx, k_idx, bq, bk, offset, _compute, window, inside)

    @pl.when(j == steps - 1)
    def _flush():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref, dl_ref,
                *refs, causal, scale, n_q, n_k, offset, fused, steps,
                p_dtype=jnp.float32, has_bias=True, window=None):
    """Grid (B*KVH, n_kv, group*steps), q innermost: recompute p and ds
    of a block ONCE and give every product that reads them. dk/dv
    accumulate in VMEM scratch over the q blocks of the `group` query
    heads of this key-value head (group = 1: one head's). `fused`: dq
    too, `ds k`, into dq_acc [group*T, D] float32, which holds the dq of
    all the key-value head's query heads (they follow one another in
    [B*H, T, D]) for the whole head; dq_ref, the same rows in the
    operands' dtype, has a block index constant over both inner axes, so
    it is written back once a key-value head. Not `fused`: this is the
    dk / dv half beside _dq_kernel. With has_bias, db_ref [1, bk] is the
    per-head bias gradient row (d s / d bias = 1): its block index is
    constant in the innermost q dim, so it stays resident in VMEM and
    accumulates in-place across the q steps; without it, neither the
    bias add nor the db output exists (no-bias path pays nothing).
    steps = n_q, or with a `window` the most q blocks that one k block's
    band holds: a head's steps then start at the k block's first q
    block, and a q block's rows of dq open at the first k block of ITS
    band and close at the last."""
    # outputs dk, dv[, db][, dq], then scratch dk_acc, dv_acc[, dq_acc]
    refs = list(refs)
    dq_acc = refs.pop() if fused else None
    dv_acc, dk_acc = refs.pop(), refs.pop()
    dq_ref = refs.pop() if fused else None
    db_ref = refs.pop() if has_bias else None
    dk_ref, dv_ref = refs
    # the innermost axis walks the q blocks of every query head that
    # shares this key-value head: `steps` a head, one head after another
    k_idx, step = pl.program_id(1), pl.program_id(2)
    bk, bq = k_ref.shape[0], q_ref.shape[0]
    if window is None:
        q_idx, inside = step % n_q, None
        rows = pl.ds(pl.multiple_of(step * bq, bq), bq)  # of dq_acc
        opens, closes = k_idx == 0, k_idx == n_k - 1
    else:
        q_idx = _first_q(k_idx, bq, bk, offset, n_q) + step % steps
        inside = q_idx <= _last_q(k_idx, bq, bk, offset, window, n_q)
        q_idx = jnp.minimum(q_idx, n_q - 1)
        rows = pl.ds(pl.multiple_of(
            ((step // steps) * n_q + q_idx) * bq, bq), bq)
        opens = inside & (k_idx == _first_k(q_idx, bq, bk, offset, window))
        closes = inside & (k_idx == _last_k(q_idx, bq, bk, offset, n_k))

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    if fused:
        @pl.when(opens)
        def _init_dq():
            dq_acc[rows, :] = jnp.zeros((bq, dq_acc.shape[1]), jnp.float32)

    def _compute(mask):
        p, ds = _p_and_ds(q_ref, k_ref, v_ref, b_ref, do_ref, lse_ref,
                          dl_ref, mask, scale=scale, p_dtype=p_dtype,
                          has_bias=has_bias)
        dv_acc[...] = dv_acc[...] + _dot(
            p.astype(do_ref.dtype).T, do_ref[...])
        if has_bias:
            db_ref[0, :] = db_ref[0, :] + jnp.sum(ds, axis=0)
        ds = ds.astype(q_ref.dtype)
        dk_acc[...] = dk_acc[...] + _dot(ds.T, q_ref[...]) * scale
        if fused:
            dq_acc[rows, :] = dq_acc[rows, :] + _dot(ds, k_ref[...]) * scale

    _on_block(causal, q_idx, k_idx, bq, bk, offset, _compute, window, inside)

    @pl.when(step == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    if fused:
        @pl.when(closes)
        def _flush_dq():
            dq_ref[rows, :] = dq_acc[rows, :].astype(dq_ref.dtype)


def _bwd_resident_bytes(group, T, D, itemsize):
    """VMEM the fused backward holds for a whole key-value head: dq of
    its `group` query heads as the float32 accumulator and as the output
    block in the operands' dtype (Pallas keeps two buffers of it), the
    minor dimension padded to a vreg's 128 lanes. Which backward runs is
    a function of the shapes alone: this against FUSED_BWD_VMEM."""
    return group * T * -(-D // _LANES) * _LANES * (4 + 2 * itemsize)


def _bwd_call(res, g, n_heads, causal, scale, block_q, block_k, interpret,
              g_lse=None, p_dtype=jnp.float32, causal_offset=0,
              has_bias=True, window=None):
    q, k, v, bias, out, lse = res
    BH, T, D = q.shape
    BKV, S = k.shape[:2]
    DV = v.shape[-1]
    H = n_heads
    group = BH // BKV
    do = g.astype(jnp.float32)
    # delta_i = rowsum(dO * O): the softmax-normalization correction term.
    # An lse cotangent folds in here: d s_ij gets p_ij * g_lse_i, i.e.
    # ds = p * (dp - (delta - g_lse)).
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1,
                    keepdims=True).transpose(0, 2, 1)        # [BH, 1, T]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    n_k = S // block_k
    n_q = T // block_q
    offset = S - T + causal_offset
    static = dict(causal=causal, scale=scale, offset=offset,
                  p_dtype=p_dtype, has_bias=has_bias, window=window)
    resident = _bwd_resident_bytes(group, T, D, q.dtype.itemsize)
    fused = resident <= FUSED_BWD_VMEM
    STATS["tiled_bwd_fused" if fused else "tiled_bwd_split"] += 1
    if window is not None:
        STATS["tiled_window"] += 1

    if not fused:
        k_steps = _k_steps(n_q, n_k, block_q, block_k, offset, window)
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, n_k=n_k, steps=k_steps, **static),
            grid=(BH, n_q, k_steps),
            in_specs=_k_innermost_specs(_kv_row(group), H, block_q, block_k,
                                        D, DV, causal, offset, n_k,
                                        window) + [
                pl.BlockSpec((None, block_q, DV), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
                pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((None, block_q, D),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name=_kernel_name("dq", window),
            interpret=interpret,
        )(q, k, v, bias, g, lse, delta)

    out_specs = [
        pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((None, block_k, DV), lambda b, j, i: (b, j, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
        jax.ShapeDtypeStruct((BKV, S, DV), v.dtype),
    ]
    if has_bias:
        out_specs.append(
            pl.BlockSpec((None, 1, block_k), lambda b, j, i: (b, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((BKV, 1, S), jnp.float32))
    scratch_shapes = [
        pltpu.VMEM((block_k, D), jnp.float32),
        pltpu.VMEM((block_k, DV), jnp.float32),
    ]
    if fused:
        # the dq of a key-value head's query heads, [group * T, D], as one
        # block: resident over both inner grid axes
        out_specs.append(pl.BlockSpec((None, group * T, D),
                                      lambda b, j, i: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BKV, group * T, D), q.dtype))
        scratch_shapes.append(pltpu.VMEM((group * T, D), jnp.float32))

    # grid row b is a key-value head; step i of the innermost axis is q
    # block i % n_q of query head b * group + i // n_q. Under a causal
    # diagonal the steps above it (the first of each head) are handed the
    # head's first active block. With a window a head has the band's
    # steps only: they start at the k block's first q block, and those
    # past its last are handed the last
    if window is None:
        steps = n_q
    else:
        def last_q(j):
            return _last_q(j, block_q, block_k, offset, window, n_q)
        steps = _band_steps(
            n_k, lambda j: _first_q(j, block_q, block_k, offset, n_q),
            last_q)

    def qrow(b, j, i):
        qi = i % steps
        if window is not None:
            qi = jnp.minimum(
                qi + _first_q(j, block_q, block_k, offset, n_q), last_q(j))
        elif causal:
            qi = jnp.maximum(qi, _first_q(j, block_q, block_k, offset, n_q))
        return (b if group == 1 else b * group + i // steps), qi

    def qblock(b, j, i):             # of q, dO: [BH, T, D]
        return qrow(b, j, i) + (0,)

    def qvec(b, j, i):               # of lse, delta: [BH, 1, T]
        row, qi = qrow(b, j, i)
        return row, 0, qi
    KVH = H // group
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, n_q=n_q, n_k=n_k, fused=fused,
                          steps=steps, **static),
        grid=(BKV, n_k, group * steps),
        in_specs=[
            pl.BlockSpec((None, block_q, D), qblock),
            pl.BlockSpec((None, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, block_k, DV), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, 1, block_k),
                         lambda b, j, i: (b // KVH, 0, j)),
            pl.BlockSpec((None, block_q, DV), qblock),
            pl.BlockSpec((None, 1, block_q), qvec),
            pl.BlockSpec((None, 1, block_q), qvec),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
            if fused else ("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_TILED_VMEM + resident if fused else None),
        name=_kernel_name("bwd" if fused else "dkv", window),
        interpret=interpret,
    )(q, k, v, bias, g, lse, delta)
    outs = list(outs)
    if fused:
        dq = outs.pop().reshape(BH, T, D)
    if not has_bias:
        dk, dv = outs
        return dq, dk, dv, None
    dk, dv, db_bh = outs
    # per-head bias-grad rows → the [B, 1, S] layout the kernel consumed
    db = db_bh.reshape(BH // H, KVH, S).sum(axis=1, keepdims=True)
    return dq, dk, dv, db


# ---------------------------------------------------------------------------
# custom_vjp wrapper (flat [BH, T, D] layout)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, bias, n_heads, causal, scale, block_q, block_k,
           interpret, p_dtype, causal_offset, has_bias, window):
    out, _ = _fwd_call(q, k, v, bias, n_heads, causal, scale, block_q,
                       block_k, interpret, p_dtype, causal_offset,
                       has_bias, window)
    return out


def _flash_fwd(q, k, v, bias, n_heads, causal, scale, block_q, block_k,
               interpret, p_dtype, causal_offset, has_bias, window):
    out, lse = _fwd_call(q, k, v, bias, n_heads, causal, scale, block_q,
                         block_k, interpret, p_dtype, causal_offset,
                         has_bias, window)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(n_heads, causal, scale, block_q, block_k, interpret, p_dtype,
               causal_offset, has_bias, window, res, g):
    dq, dk, dv, db = _bwd_call(res, g, n_heads, causal, scale, block_q,
                               block_k, interpret, p_dtype=p_dtype,
                               causal_offset=causal_offset,
                               has_bias=has_bias, window=window)
    if db is None:  # fabricated zeros bias: no gradient to report
        return dq, dk, dv, jnp.zeros_like(res[3])
    return dq, dk, dv, db.astype(res[3].dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash_lse(q, k, v, bias, n_heads, causal, scale, block_q, block_k,
               interpret, p_dtype, causal_offset, has_bias):
    """Like _flash but also returns the per-row logsumexp — the merge
    currency of ring attention (parallel/ring_attention.py)."""
    return _fwd_call(q, k, v, bias, n_heads, causal, scale, block_q,
                     block_k, interpret, p_dtype, causal_offset, has_bias)


def _flash_lse_fwd(q, k, v, bias, n_heads, causal, scale, block_q, block_k,
                   interpret, p_dtype, causal_offset, has_bias):
    out, lse = _fwd_call(q, k, v, bias, n_heads, causal, scale, block_q,
                         block_k, interpret, p_dtype, causal_offset,
                         has_bias)
    return (out, lse), (q, k, v, bias, out, lse)


def _flash_lse_bwd(n_heads, causal, scale, block_q, block_k, interpret,
                   p_dtype, causal_offset, has_bias, res, g):
    g_out, g_lse = g
    dq, dk, dv, db = _bwd_call(res, g_out, n_heads, causal, scale, block_q,
                               block_k, interpret, g_lse=g_lse,
                               p_dtype=p_dtype, causal_offset=causal_offset,
                               has_bias=has_bias)
    if db is None:
        return dq, dk, dv, jnp.zeros_like(res[3])
    return dq, dk, dv, db.astype(res[3].dtype)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(q, k, v, bias=None, causal=False, scale=None,
                             block_q=None, block_k=None, interpret=False,
                             softmax_dtype=None, causal_offset=0):
    """q/k/v [B,H,T,D] → (out [B,H,T,Dv], lse [B,H,T]).

    Differentiable (incl. the lse output); the unnormalized-merge entry
    point for ring attention's cross-device online softmax."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    STATS["pallas_calls"] += 1
    B, H, T, _ = q.shape
    qr, kr, vr, br, H, scale, block_q, block_k = _prep(
        q, k, v, bias, scale, block_q, block_k, causal)
    p_dtype = jnp.dtype(softmax_dtype or _SOFTMAX_DTYPE)
    out, lse = _flash_lse(qr, kr, vr, br, H, bool(causal), scale, block_q,
                          block_k, bool(interpret), p_dtype,
                          int(causal_offset), bias is not None)
    return out.reshape(B, H, T, vr.shape[-1]), lse.reshape(B, H, T)


# ---------------------------------------------------------------------------
# short sequences, the op's own [B, T, H*D] layout: one tile, no online
# softmax, one backward kernel; q, k, v apart or packed as the fused
# projection writes them
# ---------------------------------------------------------------------------
# The whole key axis is one block and the [tq, S] scores of one head live
# in VMEM only. A block is over [B, T, H*D] (lane-dense: no 64-wide minor
# dimension, no swapaxes around the call); the heads are walked inside
# the body, one 128-lane group at a time (two heads at D = 64). A head
# inside its group is picked by a lane mask on ONE operand of each
# product (the contraction then runs over 128 lanes, the other head's
# contributing zeros) and by a lane select on the [*, 128] result, so no
# 64-lane slice, shift or concat is ever made. Where q, k and v come
# packed (_PACKS) the block is the H*D-lane segment of the packed array
# that the index map picks, and the backward's block of a packed
# gradient is the whole row, dq, dk and dv stored into their segments
# (_LaneSegment): the bodies are the same, XLA slices nothing.
_SHORT_FWD_ROWS = 256         # rows of q per scores tile, forward
_SHORT_BWD_ROWS = 256         # rows of q and of k per tile, backward
_SHORT_BWD_KEYS = 256
# VMEM of one grid step: under _SHORT_VMEM_FREE the kernels ask for
# nothing and live in the compiler's own 16 MiB of scoped VMEM (the rest
# is XLA's, which keeps neighbouring ops' operands there); past it they
# ask for what they need, up to _SHORT_VMEM_MAX.
_SHORT_VMEM_FREE = 12 * 1024 * 1024
_SHORT_VMEM_MAX = 96 * 1024 * 1024
# Where q, k and v lie in the arrays a short call is handed, for each the
# array (its place among the distinct ones) and the lane segment, in units
# of H*D. None: three arrays. "qkv": one [B, T, 3*H*D], the fused
# self-attention projection as it comes out of its matmul, handed in three
# times. "kv": q and one [B, S, 2*H*D], the fused cross-attention
# projection. A block's index map picks the segment, so XLA slices
# nothing; the backward writes one gradient an array, each of dq, dk, dv
# into its segment, so XLA pads and adds nothing either.
_PACKS = {None: ((0, 0), (1, 0), (2, 0)),
          "kv": ((0, 0), (1, 0), (1, 1)),
          "qkv": ((0, 0), (0, 1), (0, 2))}


def _n_arrays(packed):
    return _PACKS[packed][-1][0] + 1


def _short_group(H, D):
    """Lanes of one head group: the whole H*D where that is under a
    vreg's 128 lanes, else 128 (D | 128) or D (128 | D); 0 = no legal
    grouping."""
    if H * D <= 128:
        return H * D
    if 128 % D == 0:
        return 128
    if D % 128 == 0:
        return D
    return 0


def _lane_mask(width, d, a):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= a * d) & (lane < (a + 1) * d)


def _short_add(b_ref, r0, tq, k0, tk, causal, offset, has_bias):
    """The additive [tq, tk] (or [1, tk]) float32 term of the batch row's
    scores tile at (r0, k0), shared by its heads: key-padding bias and
    causal mask."""
    add = b_ref[:, k0:k0 + tk].astype(jnp.float32) if has_bias \
        else None                                              # [1, tk]
    if causal:
        q_pos = r0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        keep = q_pos + offset >= k_pos
        add = jnp.where(keep, 0.0 if add is None else add, _NEG_INF)
    return add


def _pick_lanes(masks, parts):
    """One [*, W] array whose head-a lanes come from parts[a]."""
    out = parts[-1]
    for a in range(len(parts) - 2, -1, -1):
        out = jnp.where(masks[a], parts[a], out)
    return out


def _short_fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, *,
                      causal, scale, offset, d, tq, has_bias):
    """One grid step = one batch row. q_ref/o_ref [T, H*D]; k_ref/v_ref
    [S, H*D]; b_ref [1, S]; lse_ref [H, T]. The float32 scores of one
    head are made tq rows at a time, [tq, S], and never leave VMEM. The
    per-row logsumexp of a head is a column; the columns of all heads
    are gathered lane by lane into one [T, 128] array and turned into
    lse_ref's rows by ONE transpose (a relayout per head cost 40% of
    the kernel)."""
    T, HD = q_ref.shape
    S = k_ref.shape[0]
    H = HD // d
    W = _short_group(H, d)
    per = W // d
    masks = [_lane_mask(W, d, a) for a in range(per)]
    batched_lse = T % 128 == 0 and H <= 128
    hlane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    cols = []
    for c in range(T // tq):
        r0 = c * tq
        add = _short_add(b_ref, r0, tq, 0, S, causal, offset, has_bias)
        col = jnp.zeros((tq, 128), jnp.float32)
        for g in range(HD // W):
            lanes = slice(g * W, (g + 1) * W)
            q2 = q_ref[r0:r0 + tq, lanes]
            k2 = k_ref[:, lanes]
            v2 = v_ref[:, lanes]
            outs = []
            for a in range(per):
                qa = q2 if per == 1 else jnp.where(
                    masks[a], q2, jnp.zeros_like(q2))
                s = _dot_t(qa, k2) * scale                   # [tq, S] f32
                if add is not None:
                    s = s + add
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                outs.append(_dot(p.astype(v2.dtype), v2) / l)
                lse = m + jnp.log(l)                         # [tq, 1]
                if batched_lse:
                    col = jnp.where(hlane == g * per + a, lse, col)
                else:
                    lse_ref[g * per + a, r0:r0 + tq] = lse[:, 0]
            o_ref[r0:r0 + tq, lanes] = _pick_lanes(
                masks, outs).astype(o_ref.dtype)
        cols.append(col)
    if batched_lse:
        rows = (cols[0] if len(cols) == 1
                else jnp.concatenate(cols, axis=0)).T        # [128, T]
        lse_ref[...] = rows[:H]


class _LaneSegment:
    """Lanes [off, off + H*D) of a packed gradient's block, stored into
    as if they were a block of their own."""

    def __init__(self, ref, off):
        self.ref, self.off, self.dtype = ref, off, ref.dtype

    def __setitem__(self, idx, val):
        rows, lanes = idx
        self.ref[rows, self.off + lanes.start:self.off + lanes.stop] = val


def _short_bwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, do_ref, lse_ref,
                      *out_refs, causal, scale, offset, d, tq, tk, has_bias,
                      packed):
    """dq, dk, dv from ONE recomputation of p, in [tq, tk] tiles (given
    lse and delta the backward is pointwise in the scores, so keys tile
    too). Blocks as the forward's; delta = rowsum(dO * O) is taken here,
    per head, from the lanes the head owns. out_refs: one gradient block
    per array the call was handed (_PACKS[packed]: dq, dk and dv land in
    their lane segments of it), then db [1, S] (float32) only where the
    bias itself is being differentiated: the column sums of ds over the
    batch row's heads and queries."""
    T, HD = q_ref.shape
    dq_ref, dk_ref, dv_ref = (
        out_refs[a] if not s else _LaneSegment(out_refs[a], s * HD)
        for a, s in _PACKS[packed])
    db_ref = out_refs[_n_arrays(packed)] \
        if len(out_refs) > _n_arrays(packed) else None
    S = k_ref.shape[0]
    W = _short_group(HD // d, d)
    per = W // d
    masks = [_lane_mask(W, d, a) for a in range(per)]
    f32 = jnp.float32
    nq, nk = T // tq, S // tk

    def pick(x, a):
        return x if per == 1 else jnp.where(masks[a], x, jnp.zeros_like(x))

    dbs = [jnp.zeros((1, tk), f32) for _ in range(nk)]
    for g in range(HD // W):
        lanes = slice(g * W, (g + 1) * W)
        dqs = [None] * nq
        for kc in range(nk):
            keys = slice(kc * tk, (kc + 1) * tk)
            k2 = k_ref[keys, lanes]
            v2 = v_ref[keys, lanes]
            dk2 = dv2 = None
            for qc in range(nq):
                rows = slice(qc * tq, (qc + 1) * tq)
                add = _short_add(b_ref, qc * tq, tq, kc * tk, tk, causal,
                                 offset, has_bias)
                q2 = q_ref[rows, lanes]
                do2 = do_ref[rows, lanes]
                dd = do2.astype(f32) * o_ref[rows, lanes].astype(f32)
                dv_h, dk_h, dq_h = [], [], []
                for a in range(per):
                    delta = jnp.sum(pick(dd, a), axis=-1,
                                    keepdims=True)               # [tq, 1]
                    lse = lse_ref[g * per + a, rows][:, None]
                    s = _dot_t(pick(q2, a), k2) * scale
                    if add is not None:
                        s = s + add
                    p = jnp.exp(s - lse)                         # [tq, tk]
                    dp = _dot_t(pick(do2, a), v2)
                    ds = p * (dp - delta)
                    if db_ref is not None:
                        dbs[kc] = dbs[kc] + jnp.sum(ds, axis=0,
                                                    keepdims=True)
                    ds = ds.astype(q2.dtype)
                    dv_h.append(_dot(p.astype(do2.dtype).T, do2))
                    dk_h.append(_dot(ds.T, q2))                  # [tk, W]
                    dq_h.append(_dot(ds, k2))                    # [tq, W]
                dv_t = _pick_lanes(masks, dv_h)
                dk_t = _pick_lanes(masks, dk_h)
                dq_t = _pick_lanes(masks, dq_h)
                dv2 = dv_t if dv2 is None else dv2 + dv_t
                dk2 = dk_t if dk2 is None else dk2 + dk_t
                dqs[qc] = dq_t if dqs[qc] is None else dqs[qc] + dq_t
            dk_ref[keys, lanes] = (dk2 * scale).astype(dk_ref.dtype)
            dv_ref[keys, lanes] = dv2.astype(dv_ref.dtype)
        for qc in range(nq):
            dq_ref[qc * tq:(qc + 1) * tq, lanes] = (
                dqs[qc] * scale).astype(dq_ref.dtype)
    if db_ref is not None:
        db_ref[...] = dbs[0] if nk == 1 else jnp.concatenate(dbs, axis=1)


def _chunk(n, pref):
    """The rows of one scores tile along an axis of n: `pref` where it
    divides n, else the whole axis."""
    return pref if n % pref == 0 else n


def _short_vmem(T, S, HD, itemsize):
    """Bytes of scoped VMEM one grid step (a batch row) needs, the larger
    of the two kernels' (read off compiles for a described v5e at
    T = S = 256 to 1024, H*D = 512, bf16: 4.0 to 42.2 MiB forward, 5.1
    to 9.5 backward). Forward: four blocks, double-buffered, and eight
    float32 [T, S] arrays (the row chunks' temporaries are not reused
    from chunk to chunk). Backward: eight blocks, double-buffered, and
    some eight [tq, tk] tiles."""
    fwd = 2 * (2 * T + 2 * S) * HD * itemsize + 8 * 4 * T * S
    bwd = 2 * (5 * T + 3 * S) * HD * itemsize + 8 * 4 * _chunk(
        T, _SHORT_BWD_ROWS) * _chunk(S, _SHORT_BWD_KEYS)
    return max(fwd, bwd)


def _short_params(T, S, HD, itemsize):
    """One batch row a grid step (2, 4 or 8 rows a step read the same
    time on the chip); VMEM asked for only past _SHORT_VMEM_FREE, half
    as much again as the step needs."""
    need = _short_vmem(T, S, HD, itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=None if need <= _SHORT_VMEM_FREE
        else 3 * need // 2)


def _row_spec(*shape):
    """BlockSpec of one batch row of a [B, *shape] array."""
    return pl.BlockSpec((None,) + shape, lambda i: (i,) + (0,) * len(shape))


def _width(arrays, packed):
    """H*D: the lanes of one of q, k, v in the arrays a call is handed."""
    return arrays[0].shape[-1] // sum(a == 0 for a, _ in _PACKS[packed])


def _qkv_specs(arrays, packed, HD):
    """q, k, v as the arrays hold them (_PACKS), and the specs of their
    blocks: one batch row's [L, H*D] lane segment, picked by the index
    map."""
    ops, specs = [], []
    for a, seg in _PACKS[packed]:
        ops.append(arrays[a])
        specs.append(pl.BlockSpec((None, arrays[a].shape[1], HD),
                                  lambda i, seg=seg: (i, 0, seg)))
    return ops, specs


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _short_fwd_call(arrays, bias, packed, n_heads, causal, scale, interpret,
                    has_bias):
    """arrays: q, k, v as _PACKS[packed] lays them out (q [B, T, H*D], k/v
    [B, S, H*D] where packed is None); bias [B, 1, S] float32. Returns
    (out [B, T, H*D], lse [B, H, T]). Jitted so that a model's many
    attentions of one shape trace and lower the unrolled body once."""
    HD = _width(arrays, packed)
    (q, k, v), specs = _qkv_specs(arrays, packed, HD)
    B, T = q.shape[:2]
    S = k.shape[1]
    return pl.pallas_call(
        functools.partial(_short_fwd_kernel, causal=causal, scale=scale,
                          offset=S - T, d=HD // n_heads,
                          tq=_chunk(T, _SHORT_FWD_ROWS),
                          has_bias=has_bias),
        grid=(B,),
        in_specs=specs + [_row_spec(1, S)],
        out_specs=[_row_spec(T, HD), _row_spec(n_heads, T)],
        out_shape=[jax.ShapeDtypeStruct((B, T, HD), q.dtype),
                   jax.ShapeDtypeStruct((B, n_heads, T), jnp.float32)],
        compiler_params=_short_params(T, S, HD, q.dtype.itemsize),
        name="flash_attention_short_fwd",
        interpret=interpret,
    )(q, k, v, bias)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _short_bwd_call(res, g, packed, n_heads, causal, scale, interpret,
                    has_bias):
    """One gradient an array the call was handed, of its shape (a packed
    array's is packed the same way), then db: [B, 1, S] float32 where the
    residuals say the bias was being differentiated (`want_db` is not
    None), else None."""
    arrays, bias, out, lse, want_db = res
    HD = _width(arrays, packed)
    (q, k, v), specs = _qkv_specs(arrays, packed, HD)
    B, T = q.shape[:2]
    S = k.shape[1]
    t = _row_spec(T, HD)
    out_specs = [_row_spec(*x.shape[1:]) for x in arrays]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in arrays]
    if want_db is not None:
        out_specs.append(_row_spec(1, S))
        out_shape.append(jax.ShapeDtypeStruct((B, 1, S), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_short_bwd_kernel, causal=causal, scale=scale,
                          offset=S - T, d=HD // n_heads,
                          tq=_chunk(T, _SHORT_BWD_ROWS),
                          tk=_chunk(S, _SHORT_BWD_KEYS),
                          has_bias=has_bias, packed=packed),
        grid=(B,),
        in_specs=specs + [_row_spec(1, S), t, t, _row_spec(n_heads, T)],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_short_params(T, S, HD, q.dtype.itemsize),
        name="flash_attention_short_bwd",
        interpret=interpret,
    )(q, k, v, bias, out, g, lse)
    return tuple(outs) if want_db is not None else tuple(outs) + (None,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _flash_short(arrays, bias, packed, n_heads, causal, scale, interpret,
                 has_bias):
    return _short_fwd_call(arrays, bias, packed, n_heads, causal, scale,
                           interpret, has_bias)[0]


def _flash_short_fwd(arrays, bias, packed, n_heads, causal, scale, interpret,
                     has_bias):
    # symbolic_zeros: each primal says whether it is being differentiated.
    # The model's bias comes from integer lengths and is not; only a
    # learnable bias pays for the db output.
    want_db = jnp.zeros((0,)) if has_bias and bias.perturbed else None
    arrays, bias = tuple(x.value for x in arrays), bias.value
    out, lse = _short_fwd_call(arrays, bias, packed, n_heads, causal, scale,
                               interpret, has_bias)
    # the residual is the arrays as handed in: a packed projection is
    # kept whole (it is alive anyway), never as three slices
    return out, (arrays, bias, out, lse, want_db)


def _flash_short_bwd(packed, n_heads, causal, scale, interpret, has_bias, res,
                     g):
    if isinstance(g, jax.custom_derivatives.SymbolicZero):
        g = jnp.zeros(g.shape, g.dtype)
    *grads, db = _short_bwd_call(res, g, packed, n_heads, causal, scale,
                                 interpret, has_bias)
    return tuple(grads), jnp.zeros_like(res[1]) if db is None else db


_flash_short.defvjp(_flash_short_fwd, _flash_short_bwd, symbolic_zeros=True)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def _bias_rows(bias, B, S):
    """A key-padding bias ([B,S], [B,1,1,S], [1,1,1,S], [1,S]) as the
    [B, 1, S] float32 the kernels read; None -> zeros (never read)."""
    if bias is None:
        return jnp.zeros((B, 1, S), jnp.float32)
    br = bias.reshape(bias.shape[0], S).astype(jnp.float32)
    if br.shape[0] == 1 and B > 1:
        br = jnp.broadcast_to(br, (B, S))
    return br.reshape(B, 1, S)


def _bias_ok(bias, B, S):
    return bias is None or tuple(bias.shape) in (
        (B, S), (B, 1, 1, S), (1, 1, 1, S), (1, S))


def supports_short(q, k, v, bias=None):
    """True if the short-sequence kernel can take q [B,T,H,D], k/v
    [B,S,H,D] (the op's `bthd` layout): one dtype, heads that group into
    whole vregs, sublane-aligned lengths, a key-padding bias, and the
    scores tile of one head plus a step's blocks inside the VMEM asked
    for."""
    if not _HAS_PALLAS or q.ndim != 4:
        return False
    B, T, H, D = q.shape
    S = k.shape[1]
    if k.shape != (B, S, H, D) or v.shape != k.shape \
            or not (q.dtype == k.dtype == v.dtype):
        return False
    W = _short_group(H, D)
    if not W or (H * D) % W:
        return False
    if T % 8 or S % 8 or T < 8 or S < 8 or not _bias_ok(bias, B, S):
        return False
    return 3 * _short_vmem(T, S, H * D, q.dtype.itemsize) // 2 \
        <= _SHORT_VMEM_MAX


def flash_attention_bthd(q, k, v, bias=None, causal=False, scale=None,
                         interpret=False):
    """The short-sequence kernel: q [B,T,H,D], k/v [B,S,H,D] ->
    [B,T,H,D], no transpose on either side. Differentiable
    (custom_vjp)."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    STATS["pallas_calls"] += 1
    B, T, H, D = q.shape
    S = k.shape[1]
    scale = float(scale) if scale is not None else D ** -0.5
    out = _flash_short((q.reshape(B, T, H * D), k.reshape(B, S, H * D),
                        v.reshape(B, S, H * D)), _bias_rows(bias, B, S),
                       None, H, bool(causal), scale, bool(interpret),
                       bias is not None)
    return out.reshape(B, T, H, D)


def packed_segments(q, k, v, packed, n_heads):
    """Shape structs of q, k, v [B, L, H, D] as `unpack` would slice them
    out of the arrays that hold them (_PACKS[packed])."""
    arrays = (q, k, v)
    HD = _width(arrays, packed)
    return tuple(jax.ShapeDtypeStruct(
        tuple(arrays[a].shape[:2]) + (n_heads, HD // n_heads),
        arrays[a].dtype) for a, _ in _PACKS[packed])


def unpack(q, k, v, packed, n_heads):
    """q, k, v [B, L, H, D] sliced out of the arrays that hold them: what
    the tiled kernel and the composition take where the short kernel
    does not take a packed call."""
    arrays = (q, k, v)
    HD = _width(arrays, packed)
    return tuple(
        arrays[a][:, :, s * HD:(s + 1) * HD].reshape(
            arrays[a].shape[:2] + (n_heads, HD // n_heads))
        for a, s in _PACKS[packed])


def picks_packed(q, k, v, packed, n_heads, bias=None, interpret=False,
                 window=None):
    """try_flash's test for a packed call: the short kernel takes the
    segments (picks_short on their shapes), and a segment is whole
    128-lane vregs, so that a block's index map can pick it."""
    segs = packed_segments(q, k, v, packed, n_heads)
    return _width((q, k, v), packed) % 128 == 0 and picks_short(
        *segs, bias, layout="bthd", interpret=interpret, window=window)


def flash_attention_packed(q, k, v, packed, n_heads, bias=None,
                           causal=False, scale=None, interpret=False):
    """The short-sequence kernel over the fused projection as its matmul
    wrote it: `packed` "qkv": q, k and v are one [B, T, 3*H*D] array (in
    that order of lane segments); "kv": q [B, T, H*D] and k, v one
    [B, S, 2*H*D]. -> [B, T, H*D]. Differentiable (custom_vjp): the
    gradient of each distinct array is one array of its shape, written
    by the kernel, so XLA neither slices the projection nor pads and adds
    its gradient."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    STATS["pallas_calls"] += 1
    STATS["short_packed"] += 1
    arrays = (q, k, v)[:_n_arrays(packed)]
    HD = _width(arrays, packed)
    B, S = k.shape[:2]
    scale = float(scale) if scale is not None else (HD // n_heads) ** -0.5
    return _flash_short(arrays, _bias_rows(bias, B, S), packed, n_heads,
                        bool(causal), scale, bool(interpret),
                        bias is not None)


def supports(q, k, v, bias=None, block_q=None, block_k=None):
    """True if (shapes, bias layout) can run on the Pallas path."""
    if not _HAS_PALLAS or q.ndim != 4:
        return False
    B, H, T, D = q.shape
    S = k.shape[2]
    # grouped-query attention: k and v may have fewer heads, each shared
    # by H / KVH query heads that follow one another
    if k.shape[1] != v.shape[1] or H % k.shape[1]:
        return False
    bq, bk = _choose_blocks(T, S, D, v.shape[-1], block_q, block_k)
    if not bq or not bk or T < 8 or S < 8:
        return False
    # accept [B,S] or [B,1,1,S] key-padding bias only
    return _bias_ok(bias, B, S)


def _prep(q, k, v, bias, scale, block_q, block_k, causal):
    """Shared dispatch prep: block picking, [B,H,T,D]→[BH,T,D] flatten,
    [B,1,S] bias normalization — ONE place so flash_attention and
    flash_attention_with_lse (and supports()) cannot drift."""
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = float(scale) if scale is not None else D ** -0.5
    block_q, block_k = _choose_blocks(T, S, D, v.shape[-1],
                                      block_q, block_k, causal)
    if not block_q or not block_k:
        raise NotImplementedError("seq len must tile")
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * k.shape[1], S, D)
    vr = v.reshape(B * v.shape[1], S, v.shape[-1])
    return qr, kr, vr, _bias_rows(bias, B, S), H, scale, block_q, block_k


def _window(window, causal, S, causal_offset=0):
    """The window as the kernels take it: None where there is none or it
    covers every key anyway (a window at or over the key length is no
    window). A window is the causal band `t - window < s <= t`; the
    kernels take it on an unshifted diagonal only."""
    if window is None:
        return None
    window = int(window)
    if window < 1 or not causal or causal_offset:
        raise NotImplementedError(
            "a window is a causal band of at least one key on an "
            "unshifted diagonal")
    return None if window >= S else window


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    block_q=None, block_k=None, interpret=False,
                    softmax_dtype=None, causal_offset=0, window=None):
    """q/k/v: [B, H, T, D] → [B, H, T, D]. Differentiable (custom_vjp);
    bias is an additive key-padding bias [B, S] or [B,1,1,S]. `window`
    (with `causal`): query t sees the `window` keys `t - window < s <= t`
    only, and the kernels (flash_attention_win_*) neither compute nor
    fetch a block outside that band."""
    if not _HAS_PALLAS:
        raise NotImplementedError("pallas unavailable")
    STATS["pallas_calls"] += 1
    B, H, T, _ = q.shape
    window = _window(window, causal, k.shape[2], causal_offset)
    qr, kr, vr, br, H, scale, block_q, block_k = _prep(
        q, k, v, bias, scale, block_q, block_k, causal)
    # per-batch bias row is shared across heads via the kernel index_map
    p_dtype = jnp.dtype(softmax_dtype or _SOFTMAX_DTYPE)
    out = _flash(qr, kr, vr, br, H, bool(causal), scale, block_q, block_k,
                 bool(interpret), p_dtype, int(causal_offset),
                 bias is not None, window)
    return out.reshape(B, H, T, vr.shape[-1])


def flash_attention_reference(q, k, v, bias=None, causal=False, scale=None,
                              causal_offset=0, layout="bhtd", window=None):
    """Unfused jnp reference (for tests), in the caller's `layout`."""
    if layout == "bthd":
        return flash_attention_reference(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), bias,
            causal, scale, causal_offset, window=window).swapaxes(1, 2)
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    group = q.shape[1] // k.shape[1]
    if group > 1:       # grouped-query attention: share each k / v head
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        b = bias.reshape(bias.shape[0], 1, 1, k.shape[2])
        s = s + b.astype(jnp.float32)
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((T, S), dtype=bool),
                      k=S - T + causal_offset)
        if window is not None:
            cm = cm & ~jnp.tril(jnp.ones((T, S), dtype=bool),
                                k=S - T + causal_offset - window)
        s = jnp.where(cm, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(q.dtype)


def _tiled_dims(q, k, layout):
    """(T, S) of the arrays as the tiled kernel would see them."""
    return (q.shape[1], k.shape[1]) if layout == "bthd" \
        else (q.shape[2], k.shape[2])


def picks_short(q, k, v, bias=None, with_lse=False, causal_offset=0,
                layout="bhtd", interpret=False, window=None):
    """try_flash's first choice, as a static test (the kern registry's
    probe asks it too): `bthd` arrays the short-sequence kernel can take,
    at lengths where the chip compiled it and it won (the table above
    SHORT_MIN_SEQ_LEN; interpret mode skips that gate), from a caller
    that wants neither the lse, a shifted diagonal nor a window."""
    if layout != "bthd" or with_lse or causal_offset or window is not None:
        return False
    T, S = _tiled_dims(q, k, layout)
    wins = SHORT_MIN_SEQ_LEN <= min(T, S) \
        and max(T, S) <= SHORT_MAX_SEQ_LEN and T % 128 == S % 128 == 0
    return (interpret or wins) and supports_short(q, k, v, bias=bias)


def tiled_min_len(with_lse=False, layout="bhtd"):
    """The key length from which try_flash hands out the tiled kernel."""
    return MIN_SEQ_LEN_BTHD if layout == "bthd" and not with_lse \
        else MIN_SEQ_LEN


def try_flash(q, k, v, bias=None, causal=False, scale=None, with_lse=False,
              causal_offset=0, layout="bhtd", window=None, packed=None,
              n_heads=None):
    """THE dispatch policy, in one place (used by ops/kernels_nn.py,
    parallel/ring_attention.py, parallel/ulysses.py): returns a Pallas
    kernel's result (`out`, or `(out, lse)` with `with_lse`) in the
    caller's `layout`, or None and the caller runs its own fused-XLA
    composition. The choice is a function of what can be seen here
    (shapes, layout, dtype), by the crossovers the chip showed (the
    table above SHORT_MIN_SEQ_LEN):

    - `bthd` arrays the short-sequence kernel can take
      (supports_short) at lengths where it won (picks_short): that
      kernel, which reads the layout as it is;
    - else S >= MIN_SEQ_LEN (MIN_SEQ_LEN_BTHD for the op's `bthd` arrays
      without `with_lse`) and a legal tiling: the tiled online-softmax
      kernel (on `bhtd`; `bthd` arrays are transposed for it here);
    - else None.

    Interpret mode (CPU tests) bypasses the performance gates, not the
    shape tests. `with_lse` and `causal_offset` callers (ring attention)
    are served by the tiled kernel only, and so is a `window` (a causal
    band; one at or over the key length is no window), which neither of
    the other two can carry.

    `packed` ("qkv" or "kv", with `n_heads`; the op's fused projections):
    q, k, v are the [B, L, n*H*D] arrays that hold them (_PACKS: the one
    qkv array three times, or q and the kv array twice) and the result
    is [B, T, H*D]. Where picks_packed, the short kernel reads them in
    place and writes their gradients packed; else they are sliced here
    (`unpack`) and go the way `bthd` arrays go."""
    use_pallas, interpret = active()
    if not use_pallas:
        return None
    if packed is not None:
        if picks_packed(q, k, v, packed, n_heads, bias, interpret, window):
            return flash_attention_packed(q, k, v, packed, n_heads, bias,
                                          causal, scale, interpret)
        out = try_flash(*unpack(q, k, v, packed, n_heads), bias=bias,
                        causal=causal, scale=scale, layout="bthd",
                        window=window)
        return None if out is None else out.reshape(q.shape[:2] + (-1,))
    bthd = layout == "bthd"
    if window is not None:
        if with_lse or causal_offset or not causal:
            return None
        window = _window(window, causal, _tiled_dims(q, k, layout)[1])
    if picks_short(q, k, v, bias, with_lse, causal_offset, layout,
                   interpret, window):
        return flash_attention_bthd(q, k, v, bias=bias, causal=causal,
                                    scale=scale, interpret=interpret)
    if not interpret \
            and _tiled_dims(q, k, layout)[1] < tiled_min_len(with_lse,
                                                             layout):
        return None
    if bthd:
        q, k, v = q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2)
    if not supports(q, k, v, bias=bias):
        return None
    if with_lse:
        out, lse = flash_attention_with_lse(
            q, k, v, bias=bias, causal=causal, scale=scale,
            interpret=interpret, causal_offset=causal_offset)
        return (out.swapaxes(1, 2) if bthd else out), lse
    out = flash_attention(q, k, v, bias=bias, causal=causal, scale=scale,
                          interpret=interpret,
                          causal_offset=causal_offset, window=window)
    return out.swapaxes(1, 2) if bthd else out
