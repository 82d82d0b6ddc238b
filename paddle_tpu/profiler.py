"""Profiler.

Parity: python/paddle/fluid/profiler.py + platform/profiler.cc — here
backed by jax.profiler (XLA/TPU traces viewable in TensorBoard /
Perfetto) plus a host-side wall-clock summary table.
"""
import contextlib
import time
from collections import defaultdict

import jax

from . import telemetry as _tm

__all__ = ["cuda_profiler", "profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "record_event", "summary", "device_op_times", "profile_step_fn"]

_records = defaultdict(lambda: [0, 0.0])  # name -> [count, total_s]
_trace_dir = None


def start_profiler(state="All", tracer_option=None, log_dir="/tmp/ptpu_prof"):
    global _trace_dir
    _trace_dir = log_dir
    try:
        jax.profiler.start_trace(log_dir)
    except Exception:
        _trace_dir = None


def stop_profiler(sorted_key="total", profile_path=None):
    global _trace_dir
    if _trace_dir is not None:
        try:
            jax.profiler.stop_trace()
        finally:
            _trace_dir = None
    return summary(sorted_key)


def reset_profiler():
    _records.clear()


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             log_dir="/tmp/ptpu_prof"):
    start_profiler(state, log_dir=log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def record_event(name):
    """Host-side timing + device annotation (jax named scope). With
    telemetry enabled the same region is also a telemetry span, so
    profiler annotations land on the unified Chrome-trace timeline
    next to the executor's own spans instead of only in _records."""
    t0 = time.perf_counter()
    try:
        with _tm.span(name, cat="profiler"), \
                jax.profiler.TraceAnnotation(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        rec = _records[name]
        rec[0] += 1
        rec[1] += dt
        if _tm.enabled():
            _tm.histogram("profiler.event_seconds").observe(dt)


def summary(sorted_key="total"):
    rows = [(name, c, tot, tot / max(c, 1))
            for name, (c, tot) in _records.items()]
    rows.sort(key=lambda r: -r[2])
    lines = [f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}"]
    for name, c, tot, avg in rows:
        lines.append(f"{name:<40}{c:>8}{tot:>12.4f}{avg:>12.4f}")
    report = "\n".join(lines)
    return report


def device_op_times(trace_dir, family=True):
    """Parse the xplane.pb trace under `trace_dir` and return
    {op_name: total_device_seconds} aggregated over the device plane's
    'XLA Ops' lines: device-side event durations, free of host-side
    dispatch noise. `family=True` collapses fusion instances
    ('fusion.123' → 'fusion') for a readable breakdown.

    The xplane proto has moved between TF releases
    (tensorflow.core.profiler → tensorflow.tsl.profiler → standalone
    tsl); try every known home, then fall back to a dependency-free
    wire-format decoder of the few fields this summary needs."""
    import glob
    import os
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    xplane_pb2 = _find_xplane_pb2()

    out = defaultdict(float)
    for path in glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True):
        with open(path, "rb") as f:
            data = f.read()
        if xplane_pb2 is not None:
            space = xplane_pb2.XSpace()
            space.ParseFromString(data)
            # filter before materializing: host planes can carry 100k+
            # python-trace events this summary would only discard
            planes = [
                (plane.name,
                 {mid: m.name for mid, m in plane.event_metadata.items()},
                 [(line.name,
                   [(ev.metadata_id, ev.duration_ps)
                    for ev in line.events])
                  for line in plane.lines if "XLA Ops" in line.name])
                for plane in space.planes
                if "TPU" in plane.name or "/device:" in plane.name]
        else:
            planes = _decode_xspace_minimal(data)
        for pname, ev_meta, lines in planes:
            if "TPU" not in pname and "/device:" not in pname:
                continue
            for lname, events in lines:
                if "XLA Ops" not in lname:
                    continue
                for metadata_id, duration_ps in events:
                    nm = ev_meta.get(metadata_id, str(metadata_id))
                    if family:
                        nm = nm.split(".")[0].rstrip("0123456789")
                    out[nm] += duration_ps * 1e-12
    return dict(out)


# every home the TF xplane proto has had across releases; the unit
# test imports this so its cross-check can never drift from production
_XPLANE_PB2_CANDIDATES = (
    "tensorflow.core.profiler.protobuf.xplane_pb2",
    "tensorflow.tsl.profiler.protobuf.xplane_pb2",
    "tsl.profiler.protobuf.xplane_pb2",
)


def _find_xplane_pb2():
    import importlib
    for mod in _XPLANE_PB2_CANDIDATES:
        try:
            return importlib.import_module(mod)
        except Exception:
            continue
    return None


def _pb_fields(buf):
    """Yield (field_number, wire_type, value) over a protobuf message.
    Values: varint int for wire type 0, bytes for type 2; types 1/5
    (fixed64/32) are skipped with correct framing; groups unsupported
    (absent from the xplane schema). Truncated input raises (a partial
    decode would silently understate device time downstream)."""
    i, n = 0, len(buf)
    while i < n:
        tag = 0
        shift = 0
        while True:
            b = buf[i]
            i += 1
            tag |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        field, wtype = tag >> 3, tag & 7
        if wtype == 0:
            val = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            yield field, wtype, val
        elif wtype == 2:
            ln = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            if i + ln > n:
                raise ValueError(
                    f"truncated length-delimited field {field}: "
                    f"declared {ln} bytes, {n - i} remain")
            yield field, wtype, buf[i:i + ln]
            i += ln
        elif wtype == 1:
            if i + 8 > n:
                raise ValueError("truncated fixed64 field")
            i += 8
        elif wtype == 5:
            if i + 4 > n:
                raise ValueError("truncated fixed32 field")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wtype}")


def _decode_xspace_minimal(data):
    """Hand-rolled XSpace decode (tsl/profiler/protobuf/xplane.proto):
    XSpace.planes=1; XPlane{name=2, lines=3, event_metadata=4(map)};
    XLine{name=2, events=4}; XEvent{metadata_id=1, duration_ps=3};
    XEventMetadata{id=1, name=2}. Returns the same
    [(plane_name, {mid: name}, [(line_name, [(mid, dur_ps)])])] shape
    the protobuf path produces."""
    planes = []
    for f, w, v in _pb_fields(data):
        if f != 1 or w != 2:
            continue
        pname, ev_meta, lines = "", {}, []
        for pf, pw, pv in _pb_fields(v):
            if pf == 2 and pw == 2:
                pname = pv.decode("utf-8", "replace")
            elif pf == 3 and pw == 2:  # XLine
                lname, events = "", []
                for lf, lw, lv in _pb_fields(pv):
                    if lf == 2 and lw == 2:
                        lname = lv.decode("utf-8", "replace")
                    elif lf == 4 and lw == 2:  # XEvent
                        mid = dur = 0
                        for ef, ew, evv in _pb_fields(lv):
                            if ef == 1 and ew == 0:
                                mid = evv
                            elif ef == 3 and ew == 0:
                                dur = evv
                        events.append((mid, dur))
                lines.append((lname, events))
            elif pf == 4 and pw == 2:  # map<int64, XEventMetadata>
                mid, mname = 0, ""
                for mf, mw, mv in _pb_fields(pv):
                    if mf == 1 and mw == 0:
                        mid = mv
                    elif mf == 2 and mw == 2:
                        for ef, ew, evv in _pb_fields(mv):
                            if ef == 1 and ew == 0:
                                mid = evv
                            elif ef == 2 and ew == 2:
                                mname = evv.decode("utf-8", "replace")
                ev_meta[mid] = mname
        planes.append((pname, ev_meta, lines))
    return planes


def profile_step_fn(fn, steps=10, trace_dir=None):
    """Run `fn()` `steps` times under a device trace; return
    (per_step_device_seconds, {op_family: per_step_seconds}). The
    trace stops after `jax.block_until_ready` on fn's last result."""
    import shutil
    import tempfile
    if trace_dir is None:
        # per-call dir: a fixed path would let concurrent profilers
        # delete or cross-pollute each other's xplane files
        trace_dir = tempfile.mkdtemp(prefix="ptpu_devprof_")
    shutil.rmtree(trace_dir, ignore_errors=True)
    fn()  # warm the compile cache outside the trace
    jax.profiler.start_trace(trace_dir)
    try:
        with _tm.span("profiler.profile_step_fn", steps=steps):
            out = None
            for _ in range(steps):
                out = fn()
            jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    ops = device_op_times(trace_dir)
    total = sum(ops.values())
    if total <= 0.0:
        # a 0.0 "per-step device time" would masquerade as evidence —
        # an unrecognized plane/line layout must be loud
        raise RuntimeError(
            f"no device-plane 'XLA Ops' events found in {trace_dir}; "
            "trace layout unrecognized for this backend")
    if _tm.enabled():
        # device op times join the host spans on one timeline (per-step
        # durations, laid back-to-back on a synthetic device track)
        _tm.merge_device_ops(ops, scale=steps)
        _tm.gauge("profiler.device_step_seconds").set(total / steps)
    return total / steps, {k: v / steps for k, v in ops.items()}


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Compat alias (ref profiler.py:cuda_profiler): profiles the device
    whatever it is — on TPU this simply delegates to profiler()."""
    with profiler("All", "total", output_file):
        yield
