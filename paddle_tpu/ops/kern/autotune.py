"""Block-size autotuner: tuned configs per (shape, dtype, platform) key.

Mirrors the compile cache's keying discipline exactly: the cache key is
the full identity of what the tuned numbers depend on — kernel name,
the spec's shape signature, operand dtype, and the platform the timing
ran on ("tpu", "cpu", or "interpret" when the Pallas interpreter is
forced). Collisions across dtype/platform are impossible twice over:
the digest covers the whole key AND every persisted entry stores the
key it was tuned for, verified on load.

Persistence follows the checkpoint discipline (resilience/checkpoint):
each entry is a directory under $PADDLE_TPU_KERN_CACHE written with
write_payload (fsync'd files + SHA-256 manifest) and made visible with
atomic_publish — a torn write never yields a half-entry, it yields an
entry that fails validate() and is skipped. Warm start comes from the
committed KERN_TUNED.json baseline at the repo root; a corrupted or
torn baseline is skipped the same way (checkpoint-validate semantics:
unreadable -> ignored, never a crash), and a tuned config that fails
its kernel's config_ok probe at load falls back to the default block
sizes.

Telemetry: kern.tuned_hits / kern.tuned_miss counters and the
kern.autotune_ms cost of explicit searches.
"""
import functools
import hashlib
import json
import os
import time

from ... import telemetry as _tm

__all__ = ["tuned_config", "autotune", "cache_key", "reset",
           "baseline_path", "load_baseline", "publish", "STATS",
           "ENV_CACHE", "ENV_BASELINE", "ENV_AUTOTUNE", "SCHEMA"]

ENV_CACHE = "PADDLE_TPU_KERN_CACHE"
ENV_BASELINE = "PADDLE_TPU_KERN_BASELINE"
ENV_AUTOTUNE = "PADDLE_TPU_KERN_AUTOTUNE"
SCHEMA = "paddle_tpu.kern.tuned.v1"

STATS = {"tuned_hits": 0, "tuned_miss": 0, "autotune_runs": 0,
         "baseline_skipped": 0, "entries_rejected": 0}

_MEM = {}          # key tuple -> config dict (validated)
_BASELINE = None   # cached {key json -> entry} or None (not loaded)


def reset():
    """Drop the in-memory caches (tests; env changes)."""
    global _BASELINE
    _MEM.clear()
    _BASELINE = None


def platform():
    """The timing platform component of the key. Interpret mode is its
    OWN platform: interpreter timings must never warm a hardware key."""
    import jax
    from ..pallas import flash_attention as fa
    use, interpret = fa.active()
    if use:
        return "interpret" if interpret else "tpu"
    return jax.default_backend()


def _dtype_of(args):
    for a in args:
        dt = getattr(a, "dtype", None)
        if dt is not None:
            return str(dt)
    return "none"


def cache_key(spec, args, kwargs):
    """(kernel, shape-sig, dtype, platform) — or None for untunable
    specs (no signature fn)."""
    if spec.signature is None:
        return None
    sig = spec.signature(*args, **kwargs)
    return (spec.name, tuple(sig), _dtype_of(args), platform())


def _key_json(key):
    return [key[0], list(key[1]), key[2], key[3]]


def _digest(key):
    blob = json.dumps(_key_json(key), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


# --------------------------------------------------------------- disk
def _entry_dir(key):
    root = os.environ.get(ENV_CACHE)
    if not root:
        return None
    return os.path.join(root, key[0], _digest(key))


def publish(key, config, source="autotune", ms=None):
    """Atomically publish one tuned entry (write_payload into a tmp
    sibling, rename into place). No-op without $PADDLE_TPU_KERN_CACHE."""
    final = _entry_dir(key)
    if final is None:
        return None
    from ...resilience import checkpoint as ckpt
    entry = {"schema": SCHEMA, "key": _key_json(key), "config": config,
             "source": source, "ms": ms}
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    ckpt.write_payload(tmp, {}, entry, "params.npz", "tuned.json")
    ckpt.atomic_publish(tmp, final)
    return final


def _load_disk(key):
    d = _entry_dir(key)
    if d is None or not os.path.isdir(d):
        return None
    from ...resilience import checkpoint as ckpt
    ok, _reason = ckpt.validate(d, "params.npz", "tuned.json")
    if not ok:
        STATS["entries_rejected"] += 1
        return None
    try:
        with open(os.path.join(d, "tuned.json")) as f:
            entry = json.load(f)
    except (ValueError, OSError):
        STATS["entries_rejected"] += 1
        return None
    # the stored key must be the one we asked for — a digest collision
    # (or a hand-moved entry) can never smuggle a config across
    # shape/dtype/platform boundaries
    if entry.get("schema") != SCHEMA or entry.get("key") != _key_json(key):
        STATS["entries_rejected"] += 1
        return None
    cfg = entry.get("config")
    return cfg if isinstance(cfg, dict) else None


# ----------------------------------------------------------- baseline
def baseline_path():
    override = os.environ.get(ENV_BASELINE)
    if override:
        return override
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(root, "KERN_TUNED.json")


def load_baseline(path=None):
    """{key-json-string -> entry} from the committed baseline, {} when
    the file is missing, torn, or not ours — skipped, never fatal
    (checkpoint-validate semantics). Malformed entries are dropped
    individually."""
    path = path or baseline_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (ValueError, OSError):
        if os.path.exists(path):
            STATS["baseline_skipped"] += 1
        return {}
    if not isinstance(doc, dict) or doc.get("schema") != SCHEMA:
        STATS["baseline_skipped"] += 1
        return {}
    index = {}
    for e in doc.get("entries") or []:
        if not isinstance(e, dict) or not isinstance(e.get("config"),
                                                     dict):
            STATS["entries_rejected"] += 1
            continue
        kj = [e.get("kernel"), list(e.get("sig") or []),
              e.get("dtype"), e.get("platform")]
        index[json.dumps(kj, sort_keys=True)] = e
    return index


def _baseline():
    global _BASELINE
    if _BASELINE is None:
        _BASELINE = load_baseline()
    return _BASELINE


# ----------------------------------------------------------- dispatch
def tuned_config(spec, args, kwargs):
    """The read path dispatch() consults: memory -> disk cache ->
    committed baseline -> {} (default blocks). Every loaded config is
    re-probed with spec.config_ok against the actual args — a stale
    config (tuned for a shape this key no longer describes, or
    hand-edited) falls back to the defaults instead of crashing the
    kernel."""
    key = cache_key(spec, args, kwargs)
    if key is None:
        return {}
    if key in _MEM:
        cfg = _MEM[key]
        if cfg:
            STATS["tuned_hits"] += 1
            if _tm.enabled():
                _tm.counter("kern.tuned_hits").inc()
        return cfg
    cfg = _load_disk(key)
    source = "cache"
    if cfg is None:
        entry = _baseline().get(json.dumps(_key_json(key),
                                           sort_keys=True))
        cfg = entry.get("config") if entry else None
        source = "baseline"
    if cfg is not None and not spec.config_ok(cfg, *args, **kwargs):
        STATS["entries_rejected"] += 1
        cfg = None
    if cfg is None and os.environ.get(ENV_AUTOTUNE, "") not in ("", "0"):
        cfg = autotune(spec, args, kwargs) or None
        source = "autotune"
    if cfg is None:
        STATS["tuned_miss"] += 1
        if _tm.enabled():
            _tm.counter("kern.tuned_miss").inc()
        _MEM[key] = {}
        return {}
    STATS["tuned_hits"] += 1
    if _tm.enabled():
        _tm.counter("kern.tuned_hits").inc()
        _tm.gauge(f"kern.{spec.name}.tuned_from_{source}").set(1)
    _MEM[key] = dict(cfg)
    return _MEM[key]


# ----------------------------------------------------------- search
def autotune(spec, args, kwargs=None, repeats=3, inner=1):
    """Time every legal candidate in the spec's tune space on the live
    backend and persist the winner. Returns the best config ({} when
    the space is empty or nothing ran). Explicit-call only — dispatch
    never times implicitly unless PADDLE_TPU_KERN_AUTOTUNE=1."""
    import jax
    kwargs = dict(kwargs or {})
    key = cache_key(spec, args, kwargs)
    if key is None:
        return {}
    t_all = time.perf_counter()
    best, best_ms = None, None
    report = []
    # jit only the array operands; scalars/flags (eps, axis indices)
    # stay static so the try_* entries can branch on them
    arr_idx = [i for i, a in enumerate(args)
               if hasattr(a, "shape") and hasattr(a, "dtype")]
    arrs = [args[i] for i in arr_idx]
    for cfg in spec.tune_space(*args, **kwargs):
        if not spec.config_ok(cfg, *args, **kwargs):
            continue

        def run(*a, _cfg=cfg):
            full = list(args)
            for i, v in zip(arr_idx, a):
                full[i] = v
            return spec.fn(*full, **kwargs, **_cfg)

        jrun = jax.jit(run)
        try:
            out = jrun(*arrs)
        except Exception as e:  # an illegal tile the probe missed
            report.append({"config": cfg, "error": f"{type(e).__name__}"})
            continue
        if out is None or (isinstance(out, (tuple, list))
                           and all(o is None for o in out)):
            continue  # fn's own gate rejected under this config
        jax.block_until_ready(out)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(inner):
                out = jrun(*arrs)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / inner)
        ms = sorted(times)[len(times) // 2] * 1e3
        report.append({"config": cfg, "ms": round(ms, 3)})
        if best_ms is None or ms < best_ms:
            best, best_ms = cfg, ms
    spent_ms = (time.perf_counter() - t_all) * 1e3
    STATS["autotune_runs"] += 1
    if _tm.enabled():
        _tm.counter("kern.autotune_ms").inc(int(spent_ms))
        _tm.counter("kern.autotune_runs").inc()
    autotune.last_report = {"kernel": spec.name, "key": _key_json(key),
                            "candidates": report,
                            "autotune_ms": round(spent_ms, 1)}
    if best is None:
        return {}
    _MEM[key] = dict(best)
    publish(key, best, source="autotune", ms=round(best_ms, 3))
    return dict(best)


autotune.last_report = None
