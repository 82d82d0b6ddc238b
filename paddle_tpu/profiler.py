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
    """Host-side timing of a region, as one telemetry span: under a
    running profiler session it is the `pt/<name>` event of the trace,
    beside the executor's own spans and on the clock of the device's
    ops; with telemetry enabled it also joins the span ring."""
    t0 = time.perf_counter()
    try:
        with _tm.span(name, cat="profiler"):
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
    """{op_name: total_device_seconds} over the "XLA Ops" lines of the
    device planes in the xplane files under `trace_dir`: device-side
    event durations, free of host-side dispatch noise. An op is named
    by its HLO instruction (the event's name is the whole HLO line);
    `family=True` collapses instances ('fusion.123' -> 'fusion') for a
    readable breakdown. Sums only: for busy time and gaps read the
    events' starts as well (chipbench/trace.py does)."""
    import glob
    import re
    from jax.profiler import ProfileData
    instr = re.compile(r"%?([\w.\-]+)")
    out = defaultdict(float)
    for path in glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if "TPU" not in plane.name and "/device:" not in plane.name:
                continue
            for line in plane.lines:
                if "XLA Ops" not in line.name:
                    continue
                for ev in line.events:
                    m = instr.match(ev.name)
                    nm = m.group(1) if m else ev.name
                    if family:
                        nm = nm.split(".")[0].rstrip("0123456789")
                    out[nm] += ev.duration_ns * 1e-9
    return dict(out)


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
        _tm.gauge("profiler.device_step_seconds").set(total / steps)
    return total / steps, {k: v / steps for k, v in ops.items()}


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Compat alias (ref profiler.py:cuda_profiler): profiles the device
    whatever it is — on TPU this simply delegates to profiler()."""
    with profiler("All", "total", output_file):
        yield
