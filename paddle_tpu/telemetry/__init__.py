"""paddle_tpu.telemetry — always-on runtime metrics and spans.

proglint (PR 1) made the IR visible *before* tracing; this package
makes the runtime visible *while it runs*: the executor's compile vs
cache-hit split, feed-put and fetch-readback time, reader queue
depth/starvation, inference latency, and device-memory watermarks all
land in one process-global registry, and host spans + device op times
land on one Chrome-trace timeline.

Enablement
----------
Off by default. `PADDLE_TPU_TELEMETRY=1` (or `enable()`) turns it on;
disabled mode is the contract the hot paths are built around: every
instrumented site is gated on one flag check, no metric is ever
registered, and `snapshot()` stays `{}` (pinned by
tests/test_bench_contract.py). Two things do not wait for the flag,
because something else already gates them: `span()` always opens a
`jax.profiler.TraceAnnotation` (`pt/<name>`), which records only while
a profiler session runs, and `compile_log()` gets a record only when
something compiles.

Surfaces
--------
- `snapshot()` — plain dict of every metric
- `prometheus_text()` — text exposition format
- `span(name, **counts)` — the one span primitive: into the profiler's
  trace (host spans and device ops in one file, on one clock) whenever
  a session runs, and into the ring below when telemetry is enabled
- `chrome_trace()` / `write_chrome_trace(path)` — the ring as
  trace-event JSON
- `compile_log()` — one record per JAX compile event (trace, lower,
  backend compile, persistent-cache hit/miss/load) with the layer that
  owned it (`compile_owner(name)` marks a stretch);
  `compiled_text(owner)` is that layer's optimized HLO, through which
  a device trace's ops are joined to their `jax.named_scope`
- `flush()` — log a summary; with `PADDLE_TPU_TELEMETRY_DIR=<dir>`
  also write metrics.json / metrics.prom / trace.json there
- `fleet` — multi-rank layer: rank labels on every export, a per-rank
  snapshot spool, coordinator-side merge (FleetCollector), straggler
  detection, and multi-rank trace stitching (`tpustat --fleet`)
- `tools/tpustat.py` — CLI: run a benchmark model N steps and print
  the table

No jax / paddle_tpu imports at module level: the executor, readers,
and the native predictor all pull this in during package init.
"""
import json
import logging
import os

from . import registry as _registry
from . import spans as _spans
from . import memory as _memory
from . import compiles
from . import fleet
from .registry import (Counter, Gauge, Histogram, counter, gauge,
                       histogram, prometheus_text,
                       DEFAULT_TIME_BUCKETS)
from .spans import (span, iter_spans, chrome_trace, write_chrome_trace,
                    SpanRecord, append_span, now_us, instant_event)
from .compiles import compile_log, compiled_text, owned as compile_owner
from .memory import device_memory_supported, sample_device_memory

__all__ = ["enabled", "enable", "disable", "counter", "gauge",
           "histogram", "span", "snapshot", "prometheus_text",
           "chrome_trace", "write_chrome_trace", "compile_log",
           "compile_owner", "compiled_text", "compiles", "iter_spans", "sample_device_memory",
           "device_memory_supported", "reset", "flush", "fleet",
           "append_span", "now_us", "instant_event", "Counter",
           "Gauge", "Histogram", "SpanRecord", "DEFAULT_TIME_BUCKETS",
           "attribution", "slo", "reqtrace", "reqtrace_enabled",
           "reqtrace_enable", "reqtrace_disable", "memledger",
           "memledger_enabled", "memledger_enable",
           "memledger_disable"]


def __getattr__(name):
    # attribution/slo/reqtrace/memledger load lazily: the off-path
    # contract (bench pin) is that a disabled run never even imports
    # them
    if name in ("attribution", "slo", "reqtrace", "memledger"):
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute "
                         f"{name!r}")

_LOG = logging.getLogger("paddle_tpu.telemetry")


def _env_truthy(val):
    return (val or "").strip().lower() not in ("", "0", "false", "off",
                                               "no")


_ENABLED = _env_truthy(os.environ.get("PADDLE_TPU_TELEMETRY"))


def enabled():
    """One-flag gate every instrumented hot path checks first."""
    return _ENABLED


def enable():
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


# span()/fleet consult the same flag without importing this module back
_spans._span_enabled = enabled
fleet._enabled = enabled


_REQTRACE = _env_truthy(os.environ.get("PADDLE_TPU_REQTRACE"))


def reqtrace_enabled():
    """Gate every request-tracing seam checks before touching the
    reqtrace module: a plain bool, so `PADDLE_TPU_REQTRACE` unset costs
    one flag check and provably never imports
    paddle_tpu.telemetry.reqtrace (pinned by test_bench_contract)."""
    return _REQTRACE


def reqtrace_enable():
    global _REQTRACE
    _REQTRACE = True


def reqtrace_disable():
    global _REQTRACE
    _REQTRACE = False


_MEMLEDGER = _env_truthy(os.environ.get("PADDLE_TPU_MEMLEDGER"))


def memledger_enabled():
    """Gate every device-memory attribution seam checks before touching
    the ledger: a plain bool, so `PADDLE_TPU_MEMLEDGER` unset costs one
    flag check and provably never imports
    paddle_tpu.telemetry.memledger (pinned by test_bench_contract)."""
    return _MEMLEDGER


def memledger_enable():
    global _MEMLEDGER
    _MEMLEDGER = True


def memledger_disable():
    global _MEMLEDGER
    _MEMLEDGER = False


def snapshot():
    """{metric_name: value} — counters/gauges as numbers, histograms as
    {count, sum, min, max, mean, buckets}. Empty when nothing was ever
    recorded (the disabled-mode contract). Once a fleet rank is known
    (parallel.fleet.init / telemetry.fleet.configure), a non-empty
    snapshot also carries "process.index"/"process.count"."""
    snap = _registry.snapshot()
    if snap:
        snap.update(fleet.process_meta())
    return snap


def reset():
    """Drop all metrics and spans (not the enabled flag, and not the
    compile log: what was compiled stays compiled). Used by tpustat to scope metrics to the steady-state
    loop, and by tests."""
    _registry.reset_metrics()
    _spans.clear_spans()
    # restart the MFU/goodput accumulation window too — but only if
    # attribution was ever loaded (importing it here would defeat the
    # lazy off-path contract)
    import sys
    attr = sys.modules.get(__name__ + ".attribution")
    if attr is not None:
        attr.reset_window()


def flush(log=True):
    """Final export: log a one-line summary and, when
    PADDLE_TPU_TELEMETRY_DIR is set, write metrics.json, metrics.prom,
    and trace.json there. Returns the snapshot (None when disabled) —
    Executor.close() calls this so a run's metrics outlive it.

    Fleet mode (a rank configured): every rank also writes its spool
    envelope (fleet.write_rank_snapshot); the single-artifact files are
    written by rank 0 only, so N ranks sharing one directory don't
    clobber each other's metrics.json."""
    if not _ENABLED:
        return None
    snap = snapshot()
    n_spans = len(iter_spans())
    if log:
        _LOG.info("telemetry flush: %d metrics, %d spans", len(snap),
                  n_spans)
    r = fleet.rank()
    out_dir = os.environ.get("PADDLE_TPU_TELEMETRY_DIR")
    if out_dir and r in (None, 0):
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "metrics.json"), "w") as f:
            json.dump(snap, f, indent=2, default=str)
        with open(os.path.join(out_dir, "metrics.prom"), "w") as f:
            f.write(prometheus_text())
        write_chrome_trace(os.path.join(out_dir, "trace.json"))
        # request-trace exemplars ride the same artifact directory —
        # but only if reqtrace was ever loaded (a sys.modules probe,
        # like reset() uses for attribution, keeps the off-path pure)
        import sys
        rt = sys.modules.get(__name__ + ".reqtrace")
        if rt is not None:
            with open(os.path.join(out_dir, "traces.json"), "w") as f:
                json.dump(rt.dump(), f, indent=2, default=str)
        # the memory ledger rides along the same way — only if it was
        # ever loaded (sys.modules probe keeps the off-path pure)
        ml = sys.modules.get(__name__ + ".memledger")
        if ml is not None:
            payload = ml.snapshot_report()
            payload["timeline"] = ml.get().timeline()
            rep = ml.last_report()
            if rep is not None:
                payload["last_report"] = rep.to_dict()
            with open(os.path.join(out_dir, "memory.json"), "w") as f:
                json.dump(payload, f, indent=2, default=str)
    if r is not None and fleet.spool_dir() is not None:
        try:
            fleet.write_rank_snapshot()
        except OSError as e:
            _LOG.warning("fleet spool flush failed: %s", e)
    return snap
