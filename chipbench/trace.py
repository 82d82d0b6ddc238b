"""Reduction of a profiler trace to busy time, idle gaps and op times.

The arithmetic works on plain lists of (name, start, duration) in seconds,
so that it can be tested on a small synthetic list; `read_xplane` is the
one function that touches the profiler's file.
"""
import bisect
import glob
import os
import re
import shutil
from collections import defaultdict


def busy_union(intervals):
    """Seconds covered by the union of (start, duration) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def idle_gaps(intervals, t0=None, t1=None):
    """[(start, duration)] of the stretches inside [t0, t1] that no interval
    covers. t0/t1 default to the first start and the last end."""
    ivs = sorted(intervals)
    if not ivs:
        return []
    end = ivs[0][0] if t0 is None else t0
    gaps = []
    for s, d in ivs:
        if s > end:
            gaps.append((end, s - end))
        end = max(end, s + d)
    if t1 is not None and t1 > end:
        gaps.append((end, t1 - end))
    return gaps


def name_gaps(gaps, spans, top=10, outside="unattributed"):
    """Put every instant of each gap down to the innermost host span
    (name, start, duration) open at that instant -- of those open, the one
    that opened last -- and sum by name, longest first (the `top` longest;
    None: all). A gap that runs through several spans is split among them;
    what no span covers is `outside`."""
    spans = sorted((s, s + d, name) for name, s, d in spans)
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    by = defaultdict(float)
    for gs, gd in gaps:
        ge = gs + gd
        near = [x for x in spans[bisect.bisect_left(starts, gs - longest):
                                 bisect.bisect_left(starts, ge)]
                if x[1] > gs]
        cuts = sorted({gs, ge} | {t for s, e, _ in near for t in (s, e)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            inside = [x for x in near if x[0] <= a and x[1] >= b]
            name = max(inside, key=lambda x: (x[0], -x[1]))[2] \
                if inside else outside
            by[name] += b - a
    return sorted(([k, v] for k, v in by.items()), key=lambda x: -x[1])[:top]


_OP = re.compile(r"%?([\w.\-]+)")


def op_name(event_name):
    """The profiler names a device op by its whole HLO line
    (`%fusion.36 = bf16[...] fusion(...)`): keep the instruction's name,
    and mark a Pallas (Mosaic) kernel as `tpu_custom_call/<name>`."""
    short = _OP.match(event_name)
    short = short.group(1) if short else event_name
    if 'custom_call_target="tpu_custom_call"' in event_name:
        return "tpu_custom_call/" + short
    return short


def start(trace_dir):
    """Start the profiler with the Python tracer off (it slows the host and
    swells the file); TraceAnnotation spans are still recorded."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop():
    import jax
    jax.profiler.stop_trace()


SPAN_PREFIXES = ("cb/", "pt/")


def span_name(event_name):
    """A host event's name without its prefix where it is a span of the
    benchmark (`cb/`) or of the program (`pt/`), else None."""
    for prefix in SPAN_PREFIXES:
        if event_name.startswith(prefix):
            return event_name[len(prefix):]
    return None


def read_xplane(trace_dir):
    """{"chips": [{"ops": [(name, start, dur)], "modules": [...]}],
    "spans": [(name, start, dur)]} in seconds on the file's own clock.
    Device planes are those named /device:TPU:n; `ops` is their "XLA Ops"
    line, `modules` their "XLA Modules" line. `spans` are the host-side
    TraceAnnotations whose name starts with one of SPAN_PREFIXES, the
    prefix taken off: the benchmark's own `cb/` spans (`window`,
    `exe.run`) and the program's `pt/` spans (`executor.fetch_readback`),
    so that an idle gap is named by the innermost thing the host was in."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {"chips": [], "spans": []}
    data = ProfileData.from_file(sorted(files)[-1])
    chips, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    chip[key] = [(op_name(e.name), e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9) for e in line.events]
            chips.append(chip)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = span_name(e.name)
                    if name is not None:
                        spans.append((name, e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    chips.sort(key=lambda c: c["name"])
    return {"chips": chips, "spans": spans}


def reduce(raw, window_s):
    """What the readers and the result line need from one traced window."""
    chips = raw["chips"]
    win = [(s, d) for name, s, d in raw["spans"] if name == "window"]
    if win:
        # the benchmark's own cb/window annotation bounds the window on the
        # trace's clock: keep what starts inside it
        lo, hi = win[0][0], win[0][0] + win[0][1]
        window_s = win[0][1]
        chips = [dict(c, ops=[e for e in c["ops"] if lo <= e[1] < hi],
                      modules=[e for e in c["modules"] if lo <= e[1] < hi])
                 for c in chips]
        raw = dict(raw, chips=chips)
    if not chips or not any(c["ops"] for c in chips):
        return {"window_s": window_s, "busy_s": 0.0, "chips": 0,
                "op_seconds": {}, "module_seconds": {}, "module_counts": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}, "raw": raw}
    n = len(chips)
    busy = [busy_union([(s, d) for _, s, d in c["ops"]]) for c in chips]
    ops, mods, counts = defaultdict(float), defaultdict(float), defaultdict(int)
    for c in chips:
        for name, _, d in c["ops"]:
            ops[name] += d / n
        for name, _, d in c["modules"]:
            mods[name] += d / n
            counts[name] += 1
    fam = defaultdict(float)
    for name, sec in ops.items():
        fam[name.split(".")[0].rstrip("0123456789_") or name] += sec
    first = chips[0]
    gaps = idle_gaps([(s, d) for _, s, d in first["ops"]],
                     *((lo, hi) if win else ()))
    return {
        "window_s": window_s, "busy_s": sum(busy) / n, "chips": n,
        "op_seconds": dict(ops), "module_seconds": dict(mods),
        "module_counts": {k: v / n for k, v in counts.items()},
        "breakdown": {
            "device_ops": sorted(([k, v] for k, v in fam.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": name_gaps(
                gaps, [x for x in raw["spans"] if x[0] != "window"])},
        "raw": raw}
