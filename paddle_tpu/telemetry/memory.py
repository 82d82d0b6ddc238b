"""Device-memory watermark gauges.

jax exposes per-device allocator stats through `Device.memory_stats()`,
but support varies by backend AND device: TPU returns a populated
dict, CPU devices return None, some plugin backends raise, and a
mixed-platform process (cpu host
devices alongside an accelerator) supports it on some local devices
only. The capability probe is therefore PER DEVICE — each device is
probed once (cached per process) and degrades individually, so one
stats-less device never blinds sampling for the rest.

jax is imported lazily: the telemetry package must stay importable
(and cheap) from modules that load before the backend is up.
"""
import threading

from . import registry as _registry

__all__ = ["device_memory_supported", "sample_device_memory",
           "reset_memory_probe"]

_probe = {}            # device label -> True/False cached verdict
_probe_lock = threading.Lock()


def _label(d):
    return f"{d.platform}:{d.id}"


def reset_memory_probe():
    """Testing hook: force the next sample to re-probe every device."""
    with _probe_lock:
        _probe.clear()


def _probe_device(d):
    """Cached per-device verdict: does THIS device report allocator
    stats? Any exception, None, or dict without bytes_in_use means
    unsupported — for that device only."""
    key = _label(d)
    verdict = _probe.get(key)
    if verdict is not None:
        return verdict
    with _probe_lock:
        verdict = _probe.get(key)
        if verdict is not None:
            return verdict
        try:
            stats = d.memory_stats()
            verdict = bool(stats) and "bytes_in_use" in stats
        except Exception:
            verdict = False
        _probe[key] = verdict
    return verdict


def device_memory_supported():
    """True when ANY local device reports allocator stats (each probed
    and cached individually)."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:
        return False
    return any(_probe_device(d) for d in devs)


def sample_device_memory():
    """Update `device.<platform>:<id>.bytes_in_use` (gauge) and
    `.peak_bytes_in_use` (high-watermark gauge) for every local device
    that supports stats; unsupported devices are skipped individually.
    Returns {device_label: bytes_in_use}, empty when nothing does."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:
        return {}
    out = {}
    for d in devs:
        if not _probe_device(d):
            continue
        try:
            stats = d.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        in_use = stats.get("bytes_in_use")
        if in_use is None:
            continue
        label = f"device.{_label(d)}"
        _registry.gauge(f"{label}.bytes_in_use").set(in_use)
        _registry.gauge(f"{label}.peak_bytes_in_use").set_max(
            stats.get("peak_bytes_in_use", in_use))
        limit = stats.get("bytes_limit")
        if limit:
            _registry.gauge(f"{label}.bytes_limit").set(limit)
        out[label] = in_use
    return out
