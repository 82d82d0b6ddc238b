"""Metrics registry: counters, gauges, fixed-bucket histograms.

The registry is process-global and thread-safe. Metrics are created
lazily at use sites (`counter(name).inc()`); instrumented hot paths
guard creation on `telemetry.enabled()`, so with telemetry off nothing
is ever registered and `snapshot()` stays `{}` — the disabled mode
costs one flag check per site, no allocation, no locking.

Deliberately dependency-free (no jax, no paddle_tpu imports): the
executor, readers, and the native predictor all import this during
package init.
"""
import math
import threading

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge",
           "histogram", "snapshot", "snapshot_with_kinds",
           "reset_metrics", "prometheus_text", "set_default_labels",
           "default_labels", "quantile_from_buckets",
           "DEFAULT_TIME_BUCKETS"]

# exponential wall-time buckets, 100µs .. 2min (seconds); the spread
# covers a cached CPU step (~1ms) through a cold whole-model compile
DEFAULT_TIME_BUCKETS = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

_metrics = {}           # name -> metric
_registry_lock = threading.Lock()

# Registry-level default labels (e.g. {"process_index": 3}): one hook
# tags EVERY metric this process exports without touching call sites —
# metric names stay identical across ranks (which is what makes the
# fleet merge line up), the labels ride along in the export envelope
# (telemetry.fleet.build_envelope) instead of being baked into names.
_default_labels = {}


def set_default_labels(labels):
    with _registry_lock:
        _default_labels.clear()
        _default_labels.update(
            {str(k): v for k, v in (labels or {}).items()})


def default_labels():
    with _registry_lock:
        return dict(_default_labels)


def _bucket_quantile(edges, counts, q, lo=None, hi=None):
    """Interpolated quantile over fixed buckets: find the bucket the
    q-rank falls in, interpolate linearly inside it. `counts` has one
    extra trailing slot (+Inf); the observed min/max tighten the open
    ends (first bucket's lower bound, +Inf's upper bound) and clamp
    the result so an estimate never leaves the observed range."""
    total = sum(counts)
    if total <= 0:
        return None
    rank = q * total
    cum = 0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if rank <= cum + c or i == len(counts) - 1:
            lower = edges[i - 1] if i > 0 else \
                (lo if lo is not None else 0.0)
            upper = edges[i] if i < len(edges) else \
                (hi if hi is not None else lower)
            frac = (rank - cum) / c
            frac = 0.0 if frac < 0.0 else (1.0 if frac > 1.0 else frac)
            v = lower + (upper - lower) * frac
            if lo is not None and v < lo:
                v = lo
            if hi is not None and v > hi:
                v = hi
            return v
        cum += c
    return None


def quantile_from_buckets(value, q):
    """Quantile estimate from a histogram's snapshot form (the
    `to_value()` dict, as found in registry snapshots and the fleet
    merge). Returns None for an empty histogram."""
    if not isinstance(value, dict) or not value.get("count"):
        return None
    buckets = value.get("buckets") or {}
    # bucket keys are floats in-process but strings after a JSON round
    # trip (fleet spool files, /metrics consumers) — coerce either way
    edges = sorted(float(k) for k in buckets if k != "+Inf")
    by_edge = {float(k): v for k, v in buckets.items() if k != "+Inf"}
    counts = [by_edge[e] for e in edges] + [buckets.get("+Inf", 0)]
    return _bucket_quantile(edges, counts, q,
                            value.get("min"), value.get("max"))


class Counter:
    """Monotonically increasing count."""
    kind = "counter"
    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n=1):
        if n < 0:
            raise ValueError(f"counter {self.name!r} cannot decrease")
        with self._lock:
            self._value += n

    @property
    def value(self):
        with self._lock:
            return self._value

    def to_value(self):
        # lock audit (fleet merge hardening): reads go through the
        # metric lock like writes do — a bare int read is atomic in
        # CPython today, but snapshot()/flush() running concurrently
        # with inc() must stay correct by contract, not by accident
        with self._lock:
            return self._value


class Gauge:
    """Last-written value, with a set_max helper for watermarks."""
    kind = "gauge"
    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v):
        with self._lock:
            self._value = v

    def set_max(self, v):
        with self._lock:
            if v > self._value:
                self._value = v

    def add(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        with self._lock:
            return self._value

    def to_value(self):
        with self._lock:
            return self._value


class Histogram:
    """Fixed-bucket histogram: per-bucket counts plus sum/min/max.

    `buckets` are inclusive upper bounds; an implicit +Inf bucket
    catches the tail. Bucket edges are frozen at creation — a second
    `histogram(name)` call with different edges raises, so two call
    sites can never silently split one metric.
    """
    kind = "histogram"
    __slots__ = ("name", "buckets", "_lock", "_counts", "_sum",
                 "_count", "_min", "_max")

    def __init__(self, name, buckets=None):
        self.name = name
        bs = tuple(float(b) for b in (buckets or DEFAULT_TIME_BUCKETS))
        if list(bs) != sorted(bs) or len(set(bs)) != len(bs):
            raise ValueError(f"histogram {name!r}: buckets must be "
                             f"strictly increasing, got {bs}")
        self.buckets = bs
        self._lock = threading.Lock()
        self._counts = [0] * (len(bs) + 1)   # [+Inf] is the last slot
        self._sum = 0.0
        self._count = 0
        self._min = math.inf
        self._max = -math.inf

    def observe(self, v):
        v = float(v)
        # the bucket search reads only the immutable edge tuple, so it
        # stays outside the lock; every mutable field (_counts, _sum,
        # _count, _min, _max) is updated in ONE critical section, and
        # to_value() reads them under the same lock — a snapshot/flush
        # racing observe() therefore always sees a consistent histogram
        # (bucket totals == count), never a torn multi-field update
        i = 0
        for i, edge in enumerate(self.buckets):
            if v <= edge:
                break
        else:
            i = len(self.buckets)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def quantile(self, q):
        """Interpolated quantile estimate from the bucket counts
        (None while empty). Exact only up to bucket resolution —
        good enough for SLO gating, not for billing."""
        with self._lock:
            return _bucket_quantile(
                self.buckets, self._counts, q,
                self._min if self._count else None,
                self._max if self._count else None)

    def to_value(self):
        with self._lock:
            d = {"count": self._count, "sum": self._sum,
                 "buckets": {le: c for le, c in
                             zip(self.buckets, self._counts)}}
            d["buckets"]["+Inf"] = self._counts[-1]
            if self._count:
                d["min"] = self._min
                d["max"] = self._max
                d["mean"] = self._sum / self._count
                d["p50"] = _bucket_quantile(
                    self.buckets, self._counts, 0.5, self._min,
                    self._max)
                d["p99"] = _bucket_quantile(
                    self.buckets, self._counts, 0.99, self._min,
                    self._max)
        return d


def _get(name, cls, **kwargs):
    m = _metrics.get(name)
    if m is None:
        with _registry_lock:
            m = _metrics.get(name)
            if m is None:
                m = cls(name, **kwargs)
                _metrics[name] = m
    if not isinstance(m, cls):
        raise TypeError(f"metric {name!r} is a {m.kind}, not a "
                        f"{cls.kind}")
    if kwargs.get("buckets") is not None \
            and m.buckets != tuple(float(b) for b in kwargs["buckets"]):
        raise ValueError(f"histogram {name!r} already registered with "
                         f"buckets {m.buckets}")
    return m


def counter(name):
    return _get(name, Counter)


def gauge(name):
    return _get(name, Gauge)


def histogram(name, buckets=None):
    return _get(name, Histogram, buckets=buckets)


def snapshot():
    """{metric_name: value} — counters/gauges as numbers, histograms as
    {count, sum, min, max, mean, buckets}. Empty when nothing was ever
    recorded (the disabled-mode contract)."""
    with _registry_lock:
        metrics = list(_metrics.values())
    return {m.name: m.to_value() for m in metrics}


def snapshot_with_kinds():
    """{name: {"kind": "counter"|"gauge"|"histogram", "value": ...}} —
    the merge-safe export: a plain snapshot() can't distinguish a
    counter from a gauge, but cross-rank merge semantics differ
    (counters sum, gauges keep per-rank values), so the fleet spool
    envelope carries the kind with every value."""
    with _registry_lock:
        metrics = list(_metrics.values())
    return {m.name: {"kind": m.kind, "value": m.to_value()}
            for m in metrics}


def reset_metrics():
    with _registry_lock:
        _metrics.clear()


def _prom_name(name):
    out = "".join(c if c.isalnum() or c in "_:" else "_" for c in name)
    return out if not out[:1].isdigit() else "_" + out


def prometheus_text():
    """Prometheus text exposition of the current registry. Histogram
    buckets are emitted cumulatively with the closing `+Inf` bucket
    equal to `_count`, per the format spec."""
    with _registry_lock:
        metrics = sorted(_metrics.values(), key=lambda m: m.name)
    lines = []
    for m in metrics:
        pname = _prom_name(m.name)
        lines.append(f"# TYPE {pname} {m.kind}")
        if m.kind == "histogram":
            v = m.to_value()
            cum = 0
            for le in m.buckets:
                cum += v["buckets"][le]
                lines.append(f'{pname}_bucket{{le="{le:g}"}} {cum}')
            lines.append(f'{pname}_bucket{{le="+Inf"}} {v["count"]}')
            lines.append(f"{pname}_sum {v['sum']:g}")
            lines.append(f"{pname}_count {v['count']}")
            if v["count"]:
                # quantile summaries alongside the raw buckets, so
                # scrape-side dashboards (and SLO rules) don't need
                # to re-derive them from _bucket counts
                lines.append(f"{pname}_p50 {v['p50']:g}")
                lines.append(f"{pname}_p99 {v['p99']:g}")
        else:
            lines.append(f"{pname} {m.to_value():g}")
    return "\n".join(lines) + ("\n" if lines else "")
