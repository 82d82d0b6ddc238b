"""gradsync — bucketed, quantized, and overlapped gradient synchronization.

Parity: the reference's BuildStrategy.fuse_all_reduce_ops +
fuse_grad_size_in_MB (NCCL fused all-reduce) and the DGC/fp16 allreduce
strategies, rebuilt as a TPU-native policy layer (ROADMAP item 2,
EQuARX in PAPERS.md).

Without a policy, dp gradient sync is implicit: ParallelExecutor jits
the step over a dp-sharded batch and XLA inserts one fp32 all-reduce
per parameter gradient behind the whole backward pass. With a policy,
the executor runs the SAME traced step under shard_map over the dp
axis, so gradients come out of value_and_grad as per-member partials
and the sync becomes an explicit, controllable sequence of collectives
with three composable levers:

- **bucketing**: gradients are flattened and concatenated into
  fixed-size fusion buffers (default 4 MiB) in reverse-topological
  (last-layer-first) order, so N params cost ceil(total/bucket)
  collectives instead of N.
- **quantization**: `bf16` cast-reduce-cast, or `int8` blockwise
  quantized all-reduce (per-block fp32 scales, accumulation in fp32
  after dequantize) with **error feedback** — the quantization residual
  is carried as persistable per-member state in the scope (one
  `gradsync.ef.<bucket>` var per bucket, dp-sharded) so it rides the
  executor's existing donate/sharding path.
- **overlap**: each bucket's collective depends only on that bucket's
  gradients, so XLA's async collectives can overlap bucket N's sync
  with the rest of the step; `overlap=0` chains buckets through
  optimization barriers to serialize them (the A/B baseline).

Selection: `ParallelExecutor(grad_sync="int8")`, the
`PADDLE_TPU_GRAD_SYNC` env var, or `optimizer.minimize(loss,
grad_sync=...)`. Spec grammar: `mode[:k=v,...]` with mode one of
fp32/bf16/int8 and knobs `bucket_mb`/`bucket_kb`/`bucket_bytes`,
`block` (int8 block size), `ef` (0/1 error feedback), `overlap` (0/1),
`reduce` (mean/sum — must match how the loss reduces over the batch;
`mean` matches `layers.mean(...)` losses and the implicit-sync
numerics). Unset/"off" keeps today's implicit path bit-identical.

Numerics contract: the explicit path assumes pure data parallelism
(replicated params; rejected when a transpiler shards them) and a
batch-`mean` (or `sum`) loss. `fp32` is exact up to summation order;
`bf16`/`int8` are lossy by design, with error feedback keeping the
*accumulated* update unbiased (residuals are re-fed into the next
step's quantizer).

Telemetry (trace-time, like collective.*): `gradsync.buckets`,
`gradsync.raw_bytes` / `gradsync.wire_bytes` counters and the
`gradsync.compression_ratio` gauge — surfaced per rank in
`tpustat --fleet`.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _tm
from . import collective as C

__all__ = ["GradSyncPolicy", "parse_policy", "resolve_policy",
           "plan_buckets", "state_entries", "ef_footprint_bytes",
           "sync_gradients",
           "make_grad_transform", "make_probe_transform",
           "quantize_int8_blockwise", "dequantize_int8_blockwise",
           "EF_PREFIX"]

EF_PREFIX = "gradsync.ef."
ENV_VAR = "PADDLE_TPU_GRAD_SYNC"

_MODES = ("fp32", "bf16", "int8")


class GradSyncPolicy:
    """One resolved gradient-sync policy (see module docstring)."""

    def __init__(self, mode="fp32", bucket_bytes=4 << 20, block_size=256,
                 error_feedback=None, overlap=True, reduce="mean",
                 axis_name="dp"):
        if mode not in _MODES:
            raise ValueError(f"grad_sync mode {mode!r} not in {_MODES}")
        if reduce not in ("mean", "sum"):
            raise ValueError(f"grad_sync reduce {reduce!r} not in "
                             "('mean', 'sum')")
        if bucket_bytes < 1024:
            raise ValueError(f"grad_sync bucket_bytes {bucket_bytes} "
                             "too small (min 1024)")
        if block_size < 1:
            raise ValueError("grad_sync block size must be >= 1")
        self.mode = mode
        self.bucket_bytes = int(bucket_bytes)
        self.block_size = int(block_size)
        # error feedback defaults on only where the wire is lossy enough
        # to need it (int8); bf16 can opt in
        self.error_feedback = (mode == "int8") if error_feedback is None \
            else bool(error_feedback)
        if mode == "fp32":
            self.error_feedback = False
        self.overlap = bool(overlap)
        self.reduce = reduce
        self.axis_name = axis_name

    def key(self):
        """Hashable identity for the executor's compile cache."""
        return ("gradsync", self.mode, self.bucket_bytes,
                self.block_size, self.error_feedback, self.overlap,
                self.reduce, self.axis_name)

    def __repr__(self):
        return (f"GradSyncPolicy(mode={self.mode!r}, "
                f"bucket_bytes={self.bucket_bytes}, "
                f"block_size={self.block_size}, "
                f"error_feedback={self.error_feedback}, "
                f"overlap={self.overlap}, reduce={self.reduce!r})")


def parse_policy(spec):
    """Parse a policy spec (string / GradSyncPolicy / None) — returns a
    GradSyncPolicy or None for off. Grammar: `mode[:k=v,...]`."""
    if spec is None or isinstance(spec, GradSyncPolicy):
        return spec
    s = str(spec).strip().lower()
    if s in ("", "0", "off", "none", "false"):
        return None
    mode, _, opts = s.partition(":")
    kw = {}
    for item in filter(None, (t.strip() for t in opts.split(","))):
        k, eq, v = item.partition("=")
        if not eq:
            raise ValueError(f"grad_sync option {item!r} is not k=v")
        if k == "bucket_mb":
            kw["bucket_bytes"] = int(float(v) * (1 << 20))
        elif k == "bucket_kb":
            kw["bucket_bytes"] = int(float(v) * 1024)
        elif k == "bucket_bytes":
            kw["bucket_bytes"] = int(v)
        elif k == "block":
            kw["block_size"] = int(v)
        elif k == "ef":
            kw["error_feedback"] = v not in ("0", "false", "off")
        elif k == "overlap":
            kw["overlap"] = v not in ("0", "false", "off")
        elif k == "reduce":
            kw["reduce"] = v
        else:
            raise ValueError(f"unknown grad_sync option {k!r}")
    return GradSyncPolicy(mode=mode, **kw)


def resolve_policy(arg=None, program=None):
    """Executor-side resolution: explicit arg (including "off") beats
    the PADDLE_TPU_GRAD_SYNC env var beats the program's minimize-time
    hint. Returns GradSyncPolicy or None."""
    if arg is not None:
        return parse_policy(arg)
    env = os.environ.get(ENV_VAR)
    if env is not None and env.strip():
        return parse_policy(env)
    hint = getattr(program, "_grad_sync", None)
    if hint is not None:
        return parse_policy(hint)
    return None


# --------------------------------------------------------------- buckets

class Bucket:
    """One fusion buffer: `entries` = [(name, shape, n_elems)] in sync
    order, `n_elems` their total, `padded` the flat length rounded up
    to the quantization block."""

    def __init__(self, index, dtype, block_size):
        self.index = index
        self.dtype = dtype
        self.block_size = block_size
        self.entries = []
        self.n_elems = 0

    @property
    def padded(self):
        b = self.block_size
        return max(-(-self.n_elems // b) * b, b)

    def add(self, name, shape, n):
        self.entries.append((name, tuple(shape), int(n)))
        self.n_elems += int(n)


def plan_buckets(named_shapes, bucket_bytes=4 << 20, block_size=256):
    """Partition params into buckets. `named_shapes` is
    [(name, shape, dtype)] in FORWARD declaration order; buckets are
    built over the REVERSED list (reverse-topological: the backward
    pass produces last-declared grads first, so bucket 0 can start
    syncing while earlier layers' grads are still being computed).
    Buckets are dtype-homogeneous; a param larger than `bucket_bytes`
    gets a bucket of its own."""
    buckets = []
    cur = None
    for name, shape, dtype in reversed(list(named_shapes)):
        dt = np.dtype(jnp.dtype(dtype).name if hasattr(dtype, "name")
                      else dtype)
        n = int(np.prod(shape)) if len(tuple(shape)) else 1
        nbytes = n * dt.itemsize
        if (cur is None or cur.dtype != dt
                or (cur.n_elems * dt.itemsize + nbytes > bucket_bytes
                    and cur.entries)):
            cur = Bucket(len(buckets), dt, block_size)
            buckets.append(cur)
        cur.add(name, shape, n)
    return buckets


def state_entries(plan, policy):
    """[(name, local_len)] of the error-feedback residual buffers this
    policy carries (empty for fp32 / ef=off). The executor stores each
    as a dp-sharded persistable of global shape (dp * local_len,)."""
    if policy is None or not policy.error_feedback:
        return []
    return [(EF_PREFIX + str(b.index), b.padded) for b in plan]


def ef_footprint_bytes(plan, policy, dp=1):
    """Analytic device bytes of the error-feedback state this policy
    carries (fp32 residual per bucket element, dp members). The memory
    ledger's gradsync_ef bucket should reconcile against this — the
    runtime analog of meshlint's static gradsync_ef floor."""
    if policy is None or not policy.error_feedback:
        return 0
    return sum(b.padded for b in plan) * 4 * max(1, int(dp))


# ---------------------------------------------------------- quantization
#
# The scheme (per-block absmax/127 fp32 scales, zero blocks keep unit
# scale, codes clipped to ±127 int8) moved to ops/kern/quant.py — ONE
# implementation shared by these buckets, the decode KV cache, and the
# collective wire, with a fused Pallas kernel behind the registry.
# These names stay importable (public API + the KV cache imports them).

def quantize_int8_blockwise(flat, block_size=256):
    """flat fp32 [padded] -> (q int8 [n_blocks, block], scales fp32
    [n_blocks, 1]) with per-block absmax/127 scales (zero blocks get a
    unit scale so the codes stay 0)."""
    from ..ops.kern.quant import quantize_int8_blockwise as impl
    return impl(flat, block_size)


def dequantize_int8_blockwise(q, scales):
    from ..ops.kern.quant import dequantize_int8_blockwise as impl
    return impl(q, scales)


# ----------------------------------------------------------------- sync

def _flatten(grads, bucket):
    parts = [grads[name].reshape(-1) for name, _, _ in bucket.entries]
    flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    pad = bucket.padded - bucket.n_elems
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _unflatten(flat, bucket):
    out = {}
    off = 0
    for name, shape, n in bucket.entries:
        out[name] = flat[off:off + n].reshape(shape)
        off += n
    return out


def _tie(x, token):
    """Serialize: make x depend on the previous bucket's result so its
    collective cannot be hoisted to overlap (the overlap=0 baseline)."""
    if token is None:
        return x
    x, _ = lax.optimization_barrier((x, token))
    return x


def sync_gradients(grads, env, policy, plan=None, dp=None):
    """Synchronize `grads` (name -> per-member partial gradient) over
    the policy's mesh axis. MUST run inside shard_map with the axis
    bound. `env` supplies the error-feedback residuals under
    `gradsync.ef.<bucket>` (absent -> residual treated as zero and not
    carried). Returns (synced_grads, new_state)."""
    if plan is None:
        plan = plan_buckets([(n, g.shape, g.dtype)
                             for n, g in grads.items()],
                            policy.bucket_bytes, policy.block_size)
    axis = policy.axis_name
    if dp is None:
        dp = jax.lax.axis_size(axis)
    out = {}
    new_state = {}
    raw_bytes = wire_bytes = 0
    token = None
    for b in plan:
        flat = _tie(_flatten(grads, b), token)
        raw_bytes += b.n_elems * b.dtype.itemsize
        if policy.mode == "fp32":
            wire_bytes += b.padded * 4
            total = C.all_reduce(flat.astype(jnp.float32), op="sum",
                                 axis_name=axis)
        else:
            work = flat.astype(jnp.float32)
            ef_name = EF_PREFIX + str(b.index)
            carry = policy.error_feedback and ef_name in env
            if carry:
                work = work + env[ef_name]
            if policy.mode == "bf16":
                wire_bytes += b.padded * 2
                total = C.all_reduce_bf16(work, axis_name=axis)
                if carry:
                    new_state[ef_name] = \
                        work - work.astype(jnp.bfloat16).astype(
                            jnp.float32)
            else:  # int8
                q, scales = quantize_int8_blockwise(work, b.block_size)
                wire_bytes += b.padded + scales.size * 4
                total = C.all_reduce_int8_blockwise(
                    q, scales, axis_name=axis).reshape(-1)
                if carry:
                    new_state[ef_name] = \
                        work - dequantize_int8_blockwise(q, scales)
        if policy.reduce == "mean":
            total = total / dp
        total = total.astype(flat.dtype)
        out.update(_unflatten(total, b))
        token = total[0]
    if _tm.enabled():
        _tm.counter("gradsync.sync_count").inc()
        _tm.gauge("gradsync.buckets").set(len(plan))
        _tm.counter("gradsync.raw_bytes").inc(raw_bytes)
        _tm.counter("gradsync.wire_bytes").inc(wire_bytes)
        if wire_bytes:
            _tm.gauge("gradsync.compression_ratio").set(
                raw_bytes / wire_bytes)
    return out, new_state


def make_grad_transform(policy, plan, dp, sparse_taps=()):
    """The build_step_fn grad_transform hook: (grads, env) ->
    (synced_grads, extra_persist).

    Dense grads (the ones `plan` buckets) sync through the policy's
    bucketed/quantized collectives. `sparse_taps` names the is_sparse
    row-grad taps this policy must NOT bucket but still make globally
    consistent: each tap's per-member row grads and its ids are
    all-gathered over the dp axis (scaled 1/dp for `mean` losses), so
    the replicated table's row-sparse tail update computes the SAME
    merged update on every member — sparse grads skip the quantized
    wire (they belong to the sparse engine; a ShardedTable handles its
    own taps and is excluded from this list)."""
    def transform(grads, env):
        synced, state = sync_gradients(grads, env, policy, plan=plan,
                                       dp=dp)
        for tap in sparse_taps:
            g = C.all_gather(grads[tap["delta"]],
                             axis_name=policy.axis_name, axis=0,
                             tiled=True)
            if policy.reduce == "mean":
                g = g / dp
            synced[tap["delta"]] = g
            env[tap["ids"]] = C.all_gather(env[tap["ids"]],
                                           axis_name=policy.axis_name,
                                           axis=0, tiled=True)
        return synced, state
    return transform


def make_probe_transform(policy, plan, dp, sparse_taps=()):
    """Axis-free shape twin of make_grad_transform for jax.eval_shape
    (the executor's fetch-classification probe): dense grads pass
    through, error-feedback state is zeros of the planned sizes, and
    the sparse-tap all-gathers become dp-fold tiles."""
    ef_entries = state_entries(plan, policy)

    def tile(x):
        return jnp.concatenate([x] * dp, axis=0) if dp > 1 else x

    def transform(grads, env):
        out = {}
        for tap in sparse_taps:
            out[tap["delta"]] = tile(grads[tap["delta"]])
            env[tap["ids"]] = tile(env[tap["ids"]])
        return out, {n: jnp.zeros((l,), jnp.float32)
                     for n, l in ef_entries}
    return transform
