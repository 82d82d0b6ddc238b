"""tpuscope attribution: runtime MFU / goodput / recompiles.

The registry (PR 2) records *what happened* — step counts, wall-time
histograms, spans. This layer answers *how well*: it captures each
compile key's FLOPs once at compile time via XLA's own
``cost_analysis`` (the same source bench.py trusts for its offline MFU)
and folds step wall-time into live ``perf.mfu`` and
``perf.goodput.{examples,tokens}_per_s`` gauges, and — when a new
compile key shows up mid-run — diffs it field-by-field against its
nearest previously-seen neighbor to say exactly which component busted
the cache (the dynamic counterpart of proglint's static
``recompile-hazard`` pass).

Never imported on the telemetry-off path: the executor pulls this in
lazily, only under ``telemetry.enabled()``, and the bench contract pins
that a disabled run neither imports this module nor calls
``cost_analysis`` (tests/test_bench_contract.py).

No jax import at module level — jax is only touched inside functions
that already run with a live backend.
"""
import logging
import os
import threading
import time

from . import registry as _registry
from . import spans as _spans

__all__ = ["peak_flops", "instrument_compile", "on_step",
           "reset_window", "explain_recompile", "executor_ckey_fields",
           "pexe_ckey_fields", "compile_info"]

_LOG = logging.getLogger("paddle_tpu.telemetry.attribution")

# THE peak table: dense bf16 FLOP/s of one chip, matched against
# jax's `device_kind` (lower-cased substring, first hit wins). Source:
# Google Cloud TPU documentation, system-architecture page of each
# generation (v5e: 197 TFLOP/s). A device that matches nothing has NO
# peak — PADDLE_TPU_PEAK_FLOPS is the only way to supply one.
_PEAK_BF16 = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5litepod", 197e12), ("v5e", 197e12), ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12), ("v2", 45e12),
)

_lock = threading.Lock()
# compile key -> {"flops", "examples", "tokens"}; capture happens once
# per key at compile time, cache-hit steps only do a dict lookup
_info = {}
# the accumulation window behind the perf.* gauges; starts at the end
# of the first compile step (compile time is excluded, matching
# bench.py's warmup exclusion) and resets with telemetry.reset()
_win = {"t0": None, "flops": 0.0, "examples": 0, "tokens": 0,
        "steps": 0}
# one-shot capability probe: backends whose AOT lower/compile path
# fails (or lacks cost_analysis) are never retried
_aot_ok = True


def peak_flops(device=None):
    """Peak bf16 FLOP/s for `device` (default: jax.devices()[0]).
    PADDLE_TPU_PEAK_FLOPS overrides — required for a perf.mfu
    anywhere the table has no entry (CPU runs, new chips). Returns
    None for an unknown device_kind: no peak, no MFU gauge."""
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            _LOG.warning("PADDLE_TPU_PEAK_FLOPS=%r is not a number",
                         env)
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:
            return None
    kind = getattr(device, "device_kind", "").lower()
    for tag, peak in _PEAK_BF16:
        if tag in kind:
            return peak
    return None


def _feed_shape_stats(feed_arrays):
    """(examples, tokens) per step from the feed dict: examples = the
    largest leading dim (the batch axis), tokens = the largest
    integer-typed feed's element count (token-id tensors are B*T int
    arrays; dense-only models fall back to examples)."""
    examples = 0
    tokens = 0
    for v in (feed_arrays or {}).values():
        shape = getattr(v, "shape", ())
        if shape:
            examples = max(examples, int(shape[0]))
        dt = str(getattr(v, "dtype", ""))
        if dt.startswith(("int", "uint")) and shape:
            size = 1
            for d in shape:
                size *= int(d)
            tokens = max(tokens, size)
    return examples, tokens or examples


class _AotFn:
    """AOT-compiled executable with the original jit fn as a safety
    net: a same-ckey call whose avals still mismatch (e.g. a scope
    buffer swapped for one of a different dtype) permanently falls
    back to the retrace-capable jit path instead of erroring."""
    __slots__ = ("compiled", "fallback", "dead")

    def __init__(self, compiled, fallback):
        self.compiled = compiled
        self.fallback = fallback
        self.dead = False

    def __call__(self, *args):
        if not self.dead:
            try:
                return self.compiled(*args)
            except TypeError as e:
                # aval mismatch is raised before any buffer is donated
                self.dead = True
                _registry.counter("perf.aot_fallbacks").inc()
                _LOG.warning("AOT executable rejected its inputs "
                             "(%s); falling back to jit", e)
        return self.fallback(*args)


def instrument_compile(jfn, args, ckey, feed_arrays, kind="executor"):
    """Compile-time capture: AOT-lower `jfn` for `args`, read the
    executable's cost_analysis FLOPs, register per-ckey attribution
    info, and return the compiled executable (wrapped in a jit
    fallback shim) so the capture costs no second compile — bench.py's
    ``_aot_compile`` pattern. Any failure downgrades to the plain jit
    fn and disarms further attempts (capability probe)."""
    global _aot_ok
    flops = None
    fn = jfn
    if _aot_ok:
        try:
            compiled = jfn.lower(*args).compile()
            try:
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                f = ca.get("flops")
                flops = float(f) if f and f > 0 else None
            except Exception:
                pass
            fn = _AotFn(compiled, jfn)
        except Exception as e:
            _aot_ok = False
            _LOG.info("backend does not support AOT cost capture "
                      "(%s: %s); perf.mfu will be unavailable",
                      type(e).__name__, e)
    examples, tokens = _feed_shape_stats(feed_arrays)
    with _lock:
        _info[ckey] = {"flops": flops, "examples": examples,
                       "tokens": tokens, "kind": kind}
    if flops:
        _registry.gauge("perf.flops_per_step").set(flops)
    return fn


def compile_info(ckey):
    with _lock:
        return dict(_info[ckey]) if ckey in _info else None


def on_step(ckey, dt, compile_run=False, feed_arrays=None):
    """Fold one completed step into the window and refresh the perf
    gauges. Compile steps only (re)anchor the window start — their
    wall time is compile, not throughput."""
    now = time.perf_counter()
    with _lock:
        info = _info.get(ckey)
        if info is None and feed_arrays is not None:
            # compiled before telemetry was enabled: no FLOPs on
            # record, but goodput can still be attributed
            examples, tokens = _feed_shape_stats(feed_arrays)
            info = _info[ckey] = {"flops": None, "examples": examples,
                                  "tokens": tokens, "kind": "late"}
        if compile_run:
            _win["t0"] = now
            return
        if _win["t0"] is None:
            _win["t0"] = now - dt
        _win["steps"] += 1
        if info:
            if info["flops"]:
                _win["flops"] += info["flops"]
            _win["examples"] += info["examples"]
            _win["tokens"] += info["tokens"]
        elapsed = now - _win["t0"]
        flops = _win["flops"]
        examples = _win["examples"]
        tokens = _win["tokens"]
    if elapsed <= 0:
        return
    _registry.gauge("perf.goodput.examples_per_s").set(
        examples / elapsed)
    _registry.gauge("perf.goodput.tokens_per_s").set(tokens / elapsed)
    if flops:
        peak = peak_flops()
        if peak:
            _registry.gauge("perf.mfu").set(flops / elapsed / peak)


def reset_window():
    """Restart the accumulation window (telemetry.reset() calls this
    when the module is loaded, so tpustat-style 'reset after warmup'
    scoping applies to the perf gauges too). Per-ckey compile info
    survives — FLOPs don't change when metrics are scoped."""
    with _lock:
        _win.update(t0=None, flops=0.0, examples=0, tokens=0, steps=0)


def _reset_for_tests():
    global _aot_ok
    reset_window()
    with _lock:
        _info.clear()
    _aot_ok = True


# ------------------------------------------------------- recompile explainer

_EXECUTOR_CKEY_NAMES = (
    "program_id", "program_version", "feed_signature", "fetch_names",
    "is_test", "seed", "fuse_optimizer_tail", "fuse_max_elems")
_PEXE_CKEY_NAMES = (
    "program_id", "program_version", "feed_signature", "fetch_names",
    "is_test", "fuse_optimizer_tail", "fuse_max_elems")

# ckey field -> component name: ONE vocabulary shared with meshlint's
# static recompile-hazard pass (telemetry/ckey_vocab.py), so the static
# warning and the runtime explanation lead with the same words —
# regression-tested by tests/test_meshlint.py
from .ckey_vocab import (COMPONENT as _COMPONENT,
                         diff_feed_signature as _diff_feed_signature,
                         fmt_field as _fmt_field)


def executor_ckey_fields(ckey):
    """Executor.run compile key -> named fields. The historical key is
    the 8-tuple; donate_state=False appends 'nodonate' (the only way
    the default key ever grows — bench-contract pin)."""
    d = dict(zip(_EXECUTOR_CKEY_NAMES, ckey))
    d["donate"] = "nodonate" not in ckey[8:]
    return d


def pexe_ckey_fields(ckey, policy_key=None, engine_key=None):
    """ParallelExecutor compile key -> named fields. The optional
    grad_sync/engine suffixes are positional in the tuple, so the call
    site passes what it knows; historical keys keep the interpretation
    they were recorded with."""
    d = dict(zip(_PEXE_CKEY_NAMES, ckey))
    d["grad_sync"] = policy_key
    d["engine"] = engine_key
    return d


def explain_recompile(kind, fields, seen_fields, step=None):
    """A NEW compile key arrived while others were already cached —
    explain why. Diffs `fields` against its nearest neighbor (the
    previously-seen key sharing the most fields) and emits
    `<kind>.recompile.explained` naming exactly which component busted
    the cache. Returns the explanation dict (Executor.last_recompile)."""
    if not seen_fields:
        return None
    names = list(fields)

    def matches(s):
        return sum(1 for k in names if s.get(k) == fields.get(k))

    best = max(seen_fields, key=matches)
    changed = [k for k in names if best.get(k) != fields.get(k)]
    details = [_fmt_field(k, best.get(k), fields.get(k))
               for k in changed]
    components = sorted({_COMPONENT.get(k, k) for k in changed})
    detail = "; ".join(details) if details else \
        "no field differs from the nearest neighbor (hash-only miss)"
    out = {"kind": kind, "step": step, "changed": changed,
           "components": components, "detail": detail,
           "matched_fields": matches(best),
           "seen_keys": len(seen_fields)}
    _registry.counter(f"{kind}.recompile.count").inc()
    _spans.instant_event(
        f"{kind}.recompile.explained", step=step,
        changed=",".join(changed), detail=detail[:400])
    _LOG.warning("%s recompile at step %s: cache busted by %s — %s",
                 kind, step, ", ".join(components) or "nothing visible",
                 detail)
    return out
