"""The one span primitive, and the ring's Chrome trace-event export.

`span(name, **args)` always opens a `jax.profiler.TraceAnnotation`
named `pt/<name>`: while a profiler session runs (`profiler.
start_profiler`, a benchmark's traced run) the span lands in the
profiler's own xplane file, on the clock of the device's ops, with its
arguments as the event's stats; with no session the annotation records
nothing. With telemetry enabled the same span is also appended to a
bounded process-global ring (a thread-local depth counter tags each
record), which `chrome_trace()` renders as complete-duration ("X")
events for chrome://tracing or Perfetto.

Ring timestamps are perf_counter_ns relative to this module's import,
in microseconds (the trace-event format's native unit); the profiler's
file has its own clock, shared with the device.
"""
import collections
import json
import os
import threading
import time

__all__ = ["span", "iter_spans", "clear_spans", "chrome_trace",
           "write_chrome_trace", "SpanRecord", "TRACE_PREFIX",
           "now_us", "append_span", "instant_event", "counter_event"]

_EPOCH_NS = time.perf_counter_ns()
_MAX_SPANS = 200_000

SpanRecord = collections.namedtuple(
    "SpanRecord", ["name", "cat", "ts_us", "dur_us", "tid", "depth",
                   "args"])

# every span's name in the profiler's trace starts with this, so that a
# reader tells the program's spans from JAX's and the benchmark's own
TRACE_PREFIX = "pt/"

_spans = collections.deque(maxlen=_MAX_SPANS)
_lock = threading.Lock()
_tls = threading.local()


def _now_us():
    return (time.perf_counter_ns() - _EPOCH_NS) / 1e3


def now_us():
    """Current timestamp on THIS process's span timeline (µs since
    module import). Fleet clock markers are stamped with this so
    per-rank timelines can be offset-aligned when stitched."""
    return _now_us()


def append_span(name, cat="host", ts_us=None, dur_us=0.0, tid=None,
                depth=0, args=None):
    """Record a pre-built span (no timing context) — used for synthetic
    timeline tracks (fleet clock markers, pipeline schedule cells).
    No-op when telemetry is disabled."""
    if not _span_enabled():
        return None
    rec = SpanRecord(name, cat,
                     _now_us() if ts_us is None else float(ts_us),
                     float(dur_us),
                     threading.get_ident() if tid is None else tid,
                     depth, args or None)
    with _lock:
        _spans.append(rec)
    return rec


def instant_event(name, cat="instant", **args):
    """Zero-duration marker (recompile explained, decode admit/retire)
    rendered as a Chrome instant ("i") event — a vertical tick on the
    timeline rather than a bar. No-op when telemetry is disabled."""
    return append_span(name, cat=cat, dur_us=0.0, args=args or None)


def counter_event(name, values, ts_us=None, track="memory"):
    """Sampled counter values (per-step HBM bytes by ledger category)
    rendered as a Chrome counter ("C") event — a stacked area track in
    Perfetto. `values` is {series_name: number}. No-op when telemetry
    is disabled."""
    return append_span(name, cat="counter", ts_us=ts_us, dur_us=0.0,
                       tid=track, args=dict(values))


_annotation = None


def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported on first use: this module
    is pulled in during package init and by jax-free tools."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _Span:
    """A TraceAnnotation that also lands in the ring when telemetry is
    on. `set(**args)` adds counts known only once the span is open
    (whether this run compiled); they join the event's stats."""
    __slots__ = ("name", "cat", "args", "_ann", "_ring", "_t0")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = (_annotation or _trace_annotation())(
            TRACE_PREFIX + name, **args)
        self._ring = _span_enabled()

    def set(self, **args):
        self._ann.set_metadata(**args)
        if self._ring:
            self.args.update(args)

    def __enter__(self):
        self._ann.__enter__()
        if self._ring:
            _tls.depth = depth = getattr(_tls, "depth", 0)
            _tls.depth = depth + 1
            self._t0 = _now_us()
        return self

    def __exit__(self, *exc):
        if self._ring:
            t1 = _now_us()
            _tls.depth -= 1
            rec = SpanRecord(self.name, self.cat, self._t0,
                             t1 - self._t0, threading.get_ident(),
                             _tls.depth, self.args or None)
            with _lock:
                _spans.append(rec)
        self._ann.__exit__(*exc)
        return False


def _span_enabled():
    # rebound by telemetry/__init__ to the real flag accessor; the
    # default keeps this module importable standalone
    return True


def span(name, cat="host", **args):
    """Context manager around a host-side region: a `pt/<name>`
    TraceAnnotation with `args` as its stats (recorded only while a
    profiler session runs), and a ring record when telemetry is
    enabled. Pass counts that are already computed; nothing is
    formatted here."""
    return _Span(name, cat, args)


def iter_spans():
    with _lock:
        return list(_spans)


def clear_spans():
    with _lock:
        _spans.clear()


def chrome_trace():
    """The timeline as a Chrome trace-event dict:
    {"traceEvents": [...], "displayTimeUnit": "ms"} — json.dump it (or
    use write_chrome_trace) and load in chrome://tracing/Perfetto."""
    pid = os.getpid()
    with _lock:
        spans = list(_spans)
    events = []
    tids = set()
    for s in spans:
        tids.add(s.tid)
        if s.cat == "counter":
            # counter ("C") events: args ARE the series values — no
            # depth key, or Perfetto would chart it as a series
            events.append({"name": s.name, "cat": s.cat, "ph": "C",
                           "ts": s.ts_us, "pid": pid, "tid": s.tid,
                           "args": dict(s.args) if s.args else {}})
            continue
        if s.cat == "instant":
            ev = {"name": s.name, "cat": s.cat, "ph": "i",
                  "ts": s.ts_us, "s": "t", "pid": pid, "tid": s.tid}
        else:
            ev = {"name": s.name, "cat": s.cat, "ph": "X",
                  "ts": s.ts_us, "dur": s.dur_us, "pid": pid,
                  "tid": s.tid}
        args = dict(s.args) if s.args else {}
        args["depth"] = s.depth
        ev["args"] = args
        events.append(ev)
    # synthetic tracks (pipeline schedule cells, fleet markers) use
    # string tids alongside integer thread idents — sort by str so the
    # mix never TypeErrors, and keep their own names as track labels
    for tid in sorted(tids, key=str):
        name = f"host thread {tid}" if isinstance(tid, int) else str(tid)
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": name}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path):
    trace = chrome_trace()
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
