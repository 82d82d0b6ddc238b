"""Profiler: where the chip's time went, by Fluid op and by host span.

Parity: python/paddle/fluid/profiler.py + platform/profiler.cc, whose
product is a table of time by op. Here a session is a `jax.profiler`
trace (the timeline stays the xplane file under `log_dir`, for
TensorBoard / Perfetto), and `stop_profiler` reads that file back through
`jax.profiler.ProfileData` and prints one report from the names the
program already writes into it:

- *device, by Fluid op*: `core/trace.py` runs every op under
  `jax.named_scope(op.type)` and, under it, the site the op was declared
  at (`op_namescope`: `fluid.name_scope`, a layer's `name=`), so an HLO
  instruction's `op_name` reads `jvp(mul)/lm_head/dot_general`. A device
  event is named by its instruction, so it is joined to that stack through
  the program's optimized HLO text (`telemetry.compiled_text`, found from
  the `program` stat of the `pt/executor.run` spans); Mosaic kernels are
  sub-rows by their `name=`, and what XLA emits under no scope is listed
  by instruction family;
- *host, by span*: the `pt/<name>` events of `telemetry.span`
  (`record_event` regions are spans) with their self time and the
  device-idle time under each, on the trace's one clock;
- *header*: session seconds, steps, busy share, peak bytes, and what was
  compiled inside the session, by owner.

`report(log_dir)` gives the same as rows. The arithmetic works on plain
lists and is tested on hand-made ones; `read_xplane` is the one function
that touches the profiler's file. (`chipbench/` keeps an independent copy
of the reductions it needs: a benchmark does not measure a program with
the program's own reader.)
"""
import bisect
import contextlib
import glob
import os
import re
import tempfile
import time
import warnings
from collections import defaultdict

import jax

from . import telemetry as _tm

__all__ = ["cuda_profiler", "profiler", "start_profiler", "stop_profiler",
           "reset_profiler", "record_event", "summary", "report",
           "last_report",
           "device_op_times", "profile_step_fn"]

PT = _tm.spans.TRACE_PREFIX
KERNEL = "tpu_custom_call/"        # a Mosaic kernel's mark in an op's name
SESSION = "profiler.session"       # the span a session holds open
RUNS = ("executor.run", "pexe.run")
NO_SPAN = "(no span)"
UNSCOPED = "unscoped"
# the reference's sorted_key -> the column of a row
SORT_KEYS = {"calls": "calls", "total": "total_ms", "max": "max_ms",
             "min": "min_ms", "ave": "mean_ms"}

_INSTR = re.compile(r"%?([\w.\-]+)")
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"^(transpose\()?(jvp\()?([\w.\-]+)\)*$")


# ------------------------------------------------------------- the file
def _xplanes(log_dir):
    return set(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True))


def find_xplane(log_dir, but=()):
    """The newest `*.xplane.pb` under `log_dir` that is none of `but`
    (or `log_dir` itself where it is such a file), or None."""
    if os.path.isfile(log_dir):
        return log_dir
    files = _xplanes(log_dir) - set(but)
    return max(files, key=os.path.getmtime) if files else None


def _op_name(event_name):
    """The profiler names a device op by its whole HLO line
    (`%fusion.36 = bf16[...] fusion(...)`): keep the instruction's name,
    a Mosaic kernel's (`pl.pallas_call(name=...)`) behind `KERNEL`."""
    m = _INSTR.match(event_name)
    short = m.group(1) if m else event_name
    if 'custom_call_target="tpu_custom_call"' in event_name:
        return KERNEL + short
    return short


def read_xplane(path):
    """{"chips": [[(op, start, dur)]], "spans": [(name, start, dur, thread,
    stats)]} in seconds on the file's one clock: the "XLA Ops" line of
    every device plane, and the `pt/` host events with the prefix taken
    off, a span's counts in `stats`."""
    from jax.profiler import ProfileData
    chips, spans, short = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                # this line alone: "Async XLA Ops" holds the copies' and
                # slices' start-to-done spans, which overlap the ops
                if line.name == "XLA Ops":
                    ops = []
                    for e in line.events:
                        # an instruction's line comes back every step
                        name = short.get(e.name)
                        if name is None:
                            name = short[e.name] = _op_name(e.name)
                        ops.append((name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9))
                    chips.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PT):
                        spans.append((e.name[len(PT):], e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, line.name,
                                      dict(e.stats)))
    chips.sort()
    return {"chips": [ops for _, ops in chips], "spans": spans}


# ------------------------------------------------------------ the spans
def parents_of(spans):
    """For each span the index of its parent: the innermost span of the
    same thread that contains it, or None."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][3], spans[i][1], -spans[i][2]))
    parent = [None] * len(spans)
    stack, thread = [], None
    for i in order:
        _, s, d, th = spans[i][:4]
        if th != thread:
            stack, thread = [], th
        while stack and spans[stack[-1]][1] + spans[stack[-1]][2] < s + d:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def self_seconds(spans, parent):
    """Each span's duration less what its children cover."""
    own = [s[2] for s in spans]
    for i, p in enumerate(parent):
        if p is not None:
            own[p] -= spans[i][2]
    return own


def step_of(spans, parent):
    """Each span's step: its own `step` stat, else its nearest ancestor's.
    Deferred work carries the step that dispatched it, so an async
    read-back inside a later `run` counts to its own step."""
    def find(i):
        while i is not None:
            if "step" in spans[i][4]:
                return spans[i][4]["step"]
            i = parent[i]
        return None
    return [find(i) for i in range(len(spans))]


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


def idle_gaps(intervals, lo, hi):
    """[(start, duration)] of the stretches of [lo, hi] that no
    (start, duration) interval covers."""
    gaps, end = [], lo
    for s, d in sorted(intervals):
        if s > end:
            gaps.append((end, min(s, hi) - end))
        end = max(end, s + d)
        if end >= hi:
            break
    if hi > end:
        gaps.append((end, hi - end))
    return [g for g in gaps if g[1] > 0]


def idle_by_span(gaps, spans):
    """{span name: seconds}: every instant of each gap put down to the
    innermost span open at that instant, of those open the one that
    opened last (on any thread: the trace has one clock). A gap that
    runs through several spans is split among them; what no span covers
    is under `NO_SPAN`."""
    # between two neighbouring edges of the spans one span is innermost
    edges = sorted({t for _, s, d, *_ in spans for t in (s, s + d)})
    ivs = sorted((s[1], s[1] + s[2], s[0]) for s in spans)
    inner, open_, k = [], [], 0
    for a in edges[:-1]:
        while k < len(ivs) and ivs[k][0] <= a:
            open_.append(ivs[k])
            k += 1
        open_ = [x for x in open_ if x[1] > a]
        inner.append(max(open_, key=lambda x: (x[0], -x[1]))[2]
                     if open_ else NO_SPAN)
    by = defaultdict(float)
    for t, gd in gaps:
        end = t + gd
        i = bisect.bisect_right(edges, t) - 1
        while t < end:
            nxt = edges[i + 1] if i + 1 < len(edges) else end
            name = inner[i] if 0 <= i < len(inner) else NO_SPAN
            by[name] += min(end, nxt) - t
            t, i = min(end, nxt), i + 1
    return dict(by)


def _stats_row(key, durations, **more):
    ms = [1e3 * d for d in durations]
    return dict(key, calls=len(ms), total_ms=sum(ms), min_ms=min(ms),
                max_ms=max(ms), mean_ms=sum(ms) / len(ms), **more)


def _sorted(rows, sorted_key):
    return sorted(rows, key=lambda r: -r[SORT_KEYS[sorted_key]])


def host_rows(spans, gaps, sorted_key="total"):
    """One row per span name: calls, total, self time, the device-idle
    time under it, min / max / mean of a call, in ms. The self times sum
    to the time the threads spent in any span, the idle times to the
    gaps' (a `NO_SPAN` row takes what lies under none)."""
    own = self_seconds(spans, parents_of(spans))
    idle = idle_by_span(gaps, spans)
    durs, selfs = defaultdict(list), defaultdict(float)
    for (name, _, d, *_), o in zip(spans, own):
        durs[name].append(d)
        selfs[name] += o
    rows = [_stats_row({"span": n}, d, self_ms=1e3 * selfs[n],
                       idle_ms=1e3 * idle.get(n, 0.0))
            for n, d in durs.items()]
    rows = _sorted(rows, sorted_key)
    if idle.get(NO_SPAN):
        rows.append({"span": NO_SPAN, "calls": 0, "total_ms": 0.0,
                     "min_ms": 0.0, "max_ms": 0.0, "mean_ms": 0.0,
                     "self_ms": 0.0, "idle_ms": 1e3 * idle[NO_SPAN]})
    return rows


def step_rows(spans):
    """One row per step: {"step", "compile_run", "self_ms": {span name:
    ms}, "deferred_ms"}, a span counted to the step it carries
    (`step_of`); `deferred_ms` is the part of it that ran inside the
    `run` of another step."""
    parent = parents_of(spans)
    own, steps = self_seconds(spans, parent), step_of(spans, parent)
    rows = {}
    for i, ((name, _, _, _, stats), o, st) in enumerate(
            zip(spans, own, steps)):
        if st is None:
            continue
        row = rows.setdefault(st, {"step": st, "compile_run": False,
                                   "self_ms": defaultdict(float),
                                   "deferred_ms": 0.0})
        row["self_ms"][name] += 1e3 * o
        if name in RUNS and stats.get("compile_run"):
            row["compile_run"] = True
        run = parent[i]
        while run is not None and spans[run][0] not in RUNS:
            run = parent[run]
        if run is not None and spans[run][4].get("step", st) != st:
            row["deferred_ms"] += 1e3 * o
    return [dict(r, self_ms=dict(r["self_ms"]))
            for _, r in sorted(rows.items())]


# ----------------------------------------------------------- the scopes
def scopes_of(hlo_text):
    """{instruction name: op_name} for every instruction of the module
    that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            o = _OP_NAME.search(line)
            if o:
                out[m.group(1)] = o.group(1)
    return out


def classify(op_name, sites=()):
    """(phase, op type, name scope) of a name stack, or None where it has
    no Fluid op's scope. The first element after the `jit(...)` ones is
    the op's when something follows it: `jvp(mul)/..` forward,
    `transpose(jvp(mul))/..` backward, a bare `adam/..` "optimizer" (what
    runs after the gradient). The name scope is the longest run of
    elements after it that is one of `sites`, the program's own
    `op_namescope`s (a primitive's or a nested transform's name is
    none)."""
    if not op_name:
        return None
    parts = [p for p in op_name.split("/") if not p.startswith("jit(")]
    m = _SCOPE.match(parts[0]) if len(parts) >= 2 else None
    if not m:
        return None
    phase = "backward" if m.group(1) else "forward" if m.group(2) \
        else "optimizer"
    inner = parts[1:-1]
    for k in range(len(inner), 0, -1):
        if "/".join(inner[:k]) in sites:
            return phase, m.group(3), "/".join(inner[:k])
    return phase, m.group(3), ""


def _family(name):
    return name.split(".")[0].rstrip("0123456789_") or name


def device_rows(chips, scopes, sites=(), steps=1, sorted_key="total"):
    """One row per (phase, op type, name scope) of the device ops'
    time, averaged over the chips: calls, total ms, ms a step, min / max
    / mean of a call and the share of the chips' busy time; under a row, its
    Mosaic kernels by name (`kernel`), and under the one `UNSCOPED` row
    XLA's own instructions by family. Rows with `kernel` empty add up to
    all the device time."""
    n = max(len(chips), 1)
    main, sub = defaultdict(list), defaultdict(list)
    keys = {instr: classify(op_name, sites)
            for instr, op_name in scopes.items()}
    for ops in chips:
        for name, _, dur in ops:
            instr = name.rpartition("/")[2]
            key = keys.get(instr)
            if name.startswith(KERNEL):
                kernel = re.sub(r"\.\d+$", "", instr)
            else:
                kernel = None if key else _family(instr)
            key = key or ("-", UNSCOPED, "")
            main[key].append(dur)
            if kernel:
                sub[key + (kernel,)].append(dur)
    busy = sum(busy_union([(s, d) for _, s, d in ops])
               for ops in chips) or 1.0

    def row(key, durs):
        r = _stats_row(dict(zip(("phase", "op", "scope", "kernel"),
                                key + ("",))), durs)
        # every chip runs the program: calls and totals are one chip's
        r.update(calls=r["calls"] / n, total_ms=r["total_ms"] / n,
                 share=sum(durs) / busy)
        r["ms_per_step"] = r["total_ms"] / max(steps, 1)
        return r

    out = []
    for r in _sorted([row(k, d) for k, d in main.items()], sorted_key):
        key = (r["phase"], r["op"], r["scope"])
        out.append(r)
        out += _sorted([row(k, d) for k, d in sub.items()
                        if k[:3] == key], sorted_key)
    return out


def rollup(rows, by):
    """{value of column `by`: ms a step} over the rows that are no
    sub-row: by "op" what `train_op_ms_per_step.*` reads, by "phase" what
    `train_phase_ms_per_step.*` reads."""
    out = defaultdict(float)
    for r in rows:
        if not r["kernel"]:
            out[r[by]] += r["ms_per_step"]
    return dict(out)


# ----------------------------------------------------------- the report
def _session_bounds(raw):
    held = [s for s in raw["spans"] if s[0] == SESSION]
    if held:
        return held[-1][1], held[-1][1] + held[-1][2]
    ivs = [(s, s + d) for _, s, d, *_ in raw["spans"]] \
        + [(s, s + d) for ops in raw["chips"] for _, s, d in ops]
    if not ivs:
        return 0.0, 0.0
    return min(a for a, _ in ivs), max(b for _, b in ivs)


def _compiled(session):
    """[{"owner", "programs", "seconds"}] of the compile log's records
    that ended inside the session."""
    by = {}
    for r in _tm.compile_log():
        if session["t0"] <= r.t_end <= session["t1"]:
            row = by.setdefault(r.owner, {"owner": str(r.owner),
                                          "programs": 0, "seconds": 0.0})
            row["seconds"] += r.seconds
            row["programs"] += r.event == _tm.compiles.BACKEND
    return sorted(by.values(), key=lambda r: -r["seconds"])


def report(log_dir, sorted_key="total", session=None):
    """The report as rows: {"header": {...}, "device": [...] | None,
    "host": [...], "steps": [...]}. `log_dir` holds a session's xplane
    file (any: the host part needs nothing else). The device part needs
    the HLO text of the program the session ran, which only the process
    that compiled it has (`telemetry.compiled_text`); without a text
    every device op is listed by instruction family, and without a
    device plane (a CPU trace) the part is None and the header says so.
    A step is a `pt/executor.run` span of the session's most frequent
    program, compile runs left out; the device time of a compile run
    inside the session is in the totals all the same, so keep the first
    run outside. `session`: what `stop_profiler` knows beyond the file."""
    if sorted_key is None or sorted_key == "default":
        sorted_key = "total"
    if sorted_key not in SORT_KEYS:
        raise ValueError(f"sorted_key {sorted_key!r}: one of "
                         f"{sorted(SORT_KEYS)}")
    t_build = time.perf_counter()
    path = find_xplane(log_dir)
    if path is None:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir!r}")
    raw = read_xplane(path)
    lo, hi = _session_bounds(raw)
    spans = [s for s in raw["spans"] if lo <= s[1] <= hi]
    chips = [[e for e in ops if lo <= e[1] < hi] for ops in raw["chips"]]
    runs = [s for s in spans if s[0] in RUNS]
    programs = defaultdict(int)
    for s in runs:
        if "program" in s[4] and not s[4].get("compile_run"):
            programs[s[4]["program"]] += 1
    program = max(programs, key=programs.get) if programs else None
    steps = programs.get(program, 0)
    header = {"xplane": path, "sorted_key": sorted_key,
              "session_s": hi - lo, "steps": steps,
              "program": None if program is None else f"executor:{program}",
              "compile_steps": sorted(s[4].get("step") for s in runs
                                      if s[4].get("compile_run")),
              "busy_share": None, "scoped": False, "peak_bytes": None,
              "compiled": None}
    if session is not None:
        header["peak_bytes"] = session["peak_bytes"]
        header["compiled"] = _compiled(session)
    device, gaps = None, []
    if any(chips):
        busy = [busy_union([(s, d) for _, s, d in ops]) for ops in chips]
        header["busy_share"] = sum(busy) / len(busy) / max(hi - lo, 1e-12)
        text = _tm.compiled_text(header["program"]) \
            if header["program"] else None
        header["scoped"] = text is not None
        device = device_rows(
            chips, scopes_of(text) if text else {},
            _tm.compiles.program_sites(header["program"]), steps,
            sorted_key)
        # the first chip's gaps, as one host's spans explain them
        gaps = idle_gaps([(s, d) for _, s, d in chips[0]], lo, hi)
    rep = {"header": header, "device": device,
           "host": host_rows(spans, gaps, sorted_key),
           "steps": step_rows(spans)}
    header["report_s"] = time.perf_counter() - t_build
    return rep


def _table(rows, cols):
    """Fixed-width text of `rows`, `cols` = [(title, key, format)]."""
    cells = [[t for t, _, _ in cols]] + [
        [format(r[k], f) if f else str(r[k]) for _, k, f in cols]
        for r in rows]
    width = [max(len(c[i]) for c in cells) for i in range(len(cols))]
    left = [not f for _, _, f in cols]
    return "\n".join("  ".join(
        c.ljust(w) if lft else c.rjust(w)
        for c, w, lft in zip(line, width, left)).rstrip() for line in cells)


def render(rep):
    """The report as the text `stop_profiler` prints."""
    h = rep["header"]
    out = ["------------------------->     Profiling Report     "
           "<-------------------------", ""]
    busy = "no device plane" if h["busy_share"] is None \
        else f"device busy {100 * h['busy_share']:.1f}%"
    peak = "" if h["peak_bytes"] is None \
        else f", peak {h['peak_bytes']:.4g} B"
    out.append(f"Session {h['session_s']:.3f} s, {h['steps']} steps of "
               f"{h['program']}, {busy}{peak}")
    if h["compile_steps"]:
        out.append(f"Compile runs inside the session (left out of the "
                   f"steps): step {h['compile_steps']}")
    for c in h["compiled"] or ():
        out.append(f"Compiled inside the session: {c['owner']}, "
                   f"{c['programs']} program(s), {c['seconds']:.2f} s")
    out += [f"Time unit: ms. Sorted by {h['sorted_key']}, descending. "
            f"Timeline: {h['xplane']}", ""]
    if rep["device"] is None:
        out += ["Device, by Fluid op: ABSENT. The trace has no device "
                "plane (no 'XLA Ops' line of a /device: plane): this "
                "backend's profile holds host lines only, and a device "
                "time read from them would be no evidence.", ""]
    else:
        out.append("Device, by Fluid op (phase, op type, name scope; "
                   "indented: its Mosaic kernels, or XLA's own "
                   "instructions by family)"
                   + ("" if h["scoped"] else
                      ": NO HLO TEXT for the program of this session in "
                      "this process, so nothing is joined to a scope"))
        rows = [dict(r, op="  " + r["kernel"] if r["kernel"] else r["op"],
                     phase="" if r["kernel"] else r["phase"],
                     scope="" if r["kernel"] else r["scope"],
                     pct=100 * r["share"]) for r in rep["device"]]
        out += [_table(rows, [
            ("Phase", "phase", ""), ("Op", "op", ""), ("Scope", "scope", ""),
            ("Calls", "calls", ".0f"), ("Total", "total_ms", ".3f"),
            ("/step", "ms_per_step", ".3f"), ("Min", "min_ms", ".3f"),
            ("Max", "max_ms", ".3f"), ("Ave", "mean_ms", ".3f"),
            ("Busy%", "pct", ".2f")]), ""]
    out.append("Host, by span (self: less its children; idle under: "
               "device-idle time while it was the innermost span open)")
    out.append(_table(rep["host"], [
        ("Span", "span", ""), ("Calls", "calls", "d"),
        ("Total", "total_ms", ".3f"), ("Self", "self_ms", ".3f"),
        ("Idle under", "idle_ms", ".3f"), ("Min", "min_ms", ".3f"),
        ("Max", "max_ms", ".3f"), ("Ave", "mean_ms", ".3f")]))
    late = [r for r in rep["steps"] if r["deferred_ms"]]
    if late:
        out += ["", f"Deferred work (async_steps): "
                f"{sum(r['deferred_ms'] for r in late):.3f} ms of steps "
                f"{late[0]['step']}..{late[-1]['step']} ran inside a later "
                f"step's run; report()['steps'] counts it to the step that "
                f"dispatched it"]
    return "\n".join(out)


# ---------------------------------------------------------- the session
_session = None     # the running session: log_dir, its span, its start
_last = None        # the last report, for summary()


def _start_trace(log_dir):
    """Python tracer off (it slows the host and swells the file; the
    `pt/` TraceAnnotations are recorded all the same), host tracer at
    level 2: what keeps a 5 s trace of training steps readable."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def start_profiler(state="All", tracer_option=None, log_dir=None):
    """Start a session: a `jax.profiler` trace into `log_dir`, by default
    a fresh directory of this session's own under the temporary one (a
    fixed path would let two processes read each other's file), named in
    the report. `state` and `tracer_option` are the reference's and select
    nothing here: the one trace holds host and device."""
    global _session
    if _session is not None and _session["error"] is None:
        return                    # already running (the reference's rule)
    if log_dir is None:
        log_dir = tempfile.mkdtemp(prefix="ptpu_prof_")
    # what a given `log_dir` held before is no file of this session
    _session = {"log_dir": log_dir, "had": _xplanes(log_dir),
                "t0": time.perf_counter(), "span": None, "error": None}
    try:
        _start_trace(log_dir)
    except RuntimeError as e:
        # JAX runs one trace at a time: inside another session (a
        # benchmark's traced run) this one records nothing of its own
        _session["error"] = f"{type(e).__name__}: {e}"
        warnings.warn(f"profiler session not started: {e}", RuntimeWarning,
                      stacklevel=2)
        return
    _session["span"] = _tm.span(SESSION).__enter__()


def stop_profiler(sorted_key="total", profile_path=None):
    """Stop the session, read its xplane file and print the report
    (`report` has the rows); returns the text. `profile_path` is the
    reference's and is not written: the timeline is the xplane file."""
    global _session, _last
    session, _session = _session, None
    if session is None:
        text = "profiler: stop_profiler without a session"
    elif session["error"]:
        text = (f"profiler: the session never started "
                f"({session['error']}); nothing was recorded")
    else:
        try:
            session["span"].__exit__(None, None, None)
        finally:
            jax.profiler.stop_trace()
        session["t1"] = time.perf_counter()
        stats = jax.local_devices()[0].memory_stats() or {}
        session["peak_bytes"] = stats.get("peak_bytes_in_use")
        path = find_xplane(session["log_dir"], but=session["had"])
        if path is None:
            raise FileNotFoundError(
                f"the session wrote no *.xplane.pb under "
                f"{session['log_dir']!r}")
        _last = report(path, sorted_key, session)
        text = render(_last)
    print(text)
    return text


def reset_profiler():
    """Forget the last report."""
    global _last
    _last = None


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             log_dir=None):
    start_profiler(state, log_dir=log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def record_event(name):
    """Host-side region, as one telemetry span: under a running session
    it is the `pt/<name>` event of the trace, beside the executor's own
    spans and on the clock of the device's ops, and a row of the
    report's host part; with telemetry enabled it also joins the span
    ring."""
    return _tm.span(name, cat="profiler")


def last_report():
    """The last session's report as rows (`report`), or None."""
    return _last


def summary(sorted_key="total"):
    """The host part of the last session's report, as rows."""
    return [] if _last is None else _sorted(_last["host"], sorted_key)


def device_op_times(trace_dir, family=True):
    """{op_name: total_device_seconds} over the "XLA Ops" lines of the
    device planes of the newest xplane file under `trace_dir`, summed
    over the planes: device-side event durations, free of host-side
    dispatch noise. An op is named by its HLO instruction;
    `family=True` collapses instances ('fusion.123' -> 'fusion') for a
    readable breakdown. Sums only: `report` has the rest."""
    path = find_xplane(trace_dir)
    out = defaultdict(float)
    for ops in read_xplane(path)["chips"] if path else ():
        for name, _, dur in ops:
            name = name.rpartition("/")[2]
            out[_family(name) if family else name] += dur
    return dict(out)


def profile_step_fn(fn, steps=10, trace_dir=None):
    """Run `fn()` `steps` times under a device trace; return
    (per_step_device_seconds, {op_family: per_step_seconds}). The
    trace stops after `jax.block_until_ready` on fn's last result."""
    import shutil
    if trace_dir is None:
        # per-call dir: a fixed path would let concurrent profilers
        # delete or cross-pollute each other's xplane files
        trace_dir = tempfile.mkdtemp(prefix="ptpu_devprof_")
    shutil.rmtree(trace_dir, ignore_errors=True)
    fn()  # warm the compile cache outside the trace
    _start_trace(trace_dir)
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
    return total / steps, {k: v / steps for k, v in ops.items()}


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """Compat alias (ref profiler.py:cuda_profiler): profiles the device
    whatever it is — on TPU this simply delegates to profiler()."""
    with profiler("All", "total", output_file):
        yield
