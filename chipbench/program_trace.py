"""The program's own spans and scopes, read out of the profiler's trace.

`chipbench/trace.py` reduces a trace to what the benchmark sees from
outside: busy time, and gaps named by the benchmark's `cb/` spans. This
module reads the same file for what the program says of itself (PR 27):

- `pt/<name>` host spans (`paddle_tpu.telemetry.span`) with their start,
  duration, stats (the span's counts) and parent (by containment on one
  thread);
- each device op with its scope path. This runtime's reader
  (`jax.profiler.ProfileData`) gives an "XLA Ops" event its HLO line as
  the name and no `op_name` (the `tf_op` stat sits on the event's
  metadata, which ProfileData does not give out), so ops are joined to
  their `jax.named_scope` through the program's optimized HLO text
  (`paddle_tpu.telemetry.compiled_text`): instruction name ->
  `metadata={op_name="jit(stepped)/transpose(jvp(mul))/dot_general"}`.

A reader calls `load(__file__)`: the run's xplane file is found from the
reader's own place (up to the directory that holds BENCHMARK.json, the
newest `*.xplane.pb` under `.chipbench_trace/`), so nothing of the
harness has to hand it over. Everything is clipped to the benchmark's
`cb/window` span. The arithmetic below works on plain lists and is
tested on small synthetic ones; with a program that has no such span,
scope or text (the parent of PR 27) every function returns nothing.
"""
import glob
import os
import re
from collections import defaultdict

PT = "pt/"
ENTRY = ("executor.", "pexe.")     # spans of the entry layer
KERNEL = "tpu_custom_call/"        # chipbench/trace.py::op_name's mark
_HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"^(transpose\()?(jvp\()?([\w.\-]+)\)*$")


def part(name):
    """What a reader takes from its metric's name: the part after the first
    dot (`train_op_ms_per_step.mul` -> `mul`). A further dot starts a tag
    the reader does not read; it tells apart two entries of the same thing
    for different cells (`train_op_ms_per_step.mul.second_model`)."""
    return name.split(".")[1]


# ------------------------------------------------------------ the file
def find_root(start):
    d = os.path.dirname(os.path.abspath(start))
    while True:
        if os.path.isfile(os.path.join(d, "BENCHMARK.json")):
            return d
        up = os.path.dirname(d)
        if up == d:
            return None
        d = up


def find_xplane(start):
    """Newest *.xplane.pb under <root>/.chipbench_trace/, or None."""
    root = find_root(start)
    if root is None:
        return None
    files = glob.glob(os.path.join(root, ".chipbench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def read_xplane(path):
    """{"window": (lo, hi) | None, "chips": [[(op, start, dur)]],
    "spans": [(name, start, dur, thread, stats)]} in seconds on the file's
    clock. `chips` holds the "XLA Ops" line of every /device:TPU:n plane,
    an op named by its HLO instruction as chipbench/trace.py names it
    (`fusion.36`, `tpu_custom_call/layer_norm_fwd.1`); `spans` the `pt/`
    host events, prefix taken off."""
    from jax.profiler import ProfileData
    from chipbench.trace import op_name
    data = ProfileData.from_file(path)
    chips, spans, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips.append((plane.name, [
                        (op_name(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PT):
                        spans.append((e.name[len(PT):], e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9, line.name,
                                      dict(e.stats)))
                    elif e.name == "cb/window" and window is None:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    chips.sort()
    return {"window": window, "chips": [ops for _, ops in chips],
            "spans": spans}


def clip(raw):
    """Keep what starts inside the window (as chipbench/trace.py does)."""
    if raw["window"] is None:
        return raw
    lo, hi = raw["window"]
    return {"window": raw["window"],
            "chips": [[e for e in ops if lo <= e[1] < hi]
                      for ops in raw["chips"]],
            "spans": [s for s in raw["spans"] if lo <= s[1] < hi]}


_LOADED = {}


def load(start):
    """The clipped trace of this run, read once per file; None where there
    is no file."""
    path = find_xplane(start)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = clip(read_xplane(path))
    return _LOADED[key]


# ------------------------------------------------------------ the spans
def with_parents(spans):
    """[(name, start, dur, thread, stats, parent_index)]: the parent is the
    innermost span of the same thread that contains this one."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][3], spans[i][1], -spans[i][2]))
    parent = [None] * len(spans)
    stack, thread = [], None
    for i in order:
        _, s, d, th, _ = spans[i]
        if th != thread:
            stack, thread = [], th
        while stack and spans[stack[-1]][1] + spans[stack[-1]][2] < s + d:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return [spans[i] + (parent[i],) for i in range(len(spans))]


def idle_by_span(ops, spans, lo, hi):
    """{span suffix: idle seconds}: every instant of device-idle time
    inside [lo, hi] put down to the innermost entry-layer span open at
    that instant, named by what follows its first dot ("caller": none
    open; "run": inside the parent but in none of its children). One gap
    that runs through several spans is split among them
    (chipbench/trace.py::name_gaps). None where the program has no
    entry-layer span."""
    from chipbench.trace import idle_gaps, name_gaps
    entry = [(n.split(".", 1)[1], s, d) for n, s, d, *_ in spans
             if n.startswith(ENTRY)]
    if not entry:
        return None
    gaps = idle_gaps([(s, d) for _, s, d in ops], lo, hi)
    return dict(name_gaps(gaps, entry, top=None, outside="caller"))


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


def classify(op_name):
    """(phase, op type) of a name stack: the first element after the
    `jit(...)` ones is the Fluid op's scope when something follows it.
    `jvp(mul)/dot_general` -> ("forward", "mul"); `transpose(jvp(mul))/..`
    -> ("backward", "mul"); `adam/mul` -> ("optimizer", "adam"): the bare
    op type is what runs after the gradient. Anything else -- no name,
    or a primitive under no scope -- is (None, None)."""
    if not op_name:
        return None, None
    parts = [p for p in op_name.split("/") if not p.startswith("jit(")]
    if len(parts) < 2:
        return None, None
    m = _SCOPE.match(parts[0])
    if not m:
        return None, None
    phase = "backward" if m.group(1) else "forward" if m.group(2) \
        else "optimizer"
    return phase, m.group(3)


def op_seconds(chips, scopes):
    """({phase: s}, {op type: s}) of device-op time, averaged over the
    chips; what has no scope is under "unscoped" in both."""
    by_phase, by_op = defaultdict(float), defaultdict(float)
    n = len(chips)
    for ops in chips:
        for name, _, dur in ops:
            phase, op = classify(scopes.get(name.rpartition("/")[2]))
            by_phase[phase or "unscoped"] += dur / n
            by_op[op or "unscoped"] += dur / n
    return dict(by_phase), dict(by_op)


def kernel_seconds(chips):
    """{kernel name: s} of the Mosaic kernels (`pl.pallas_call(name=...)`
    names the HLO instruction; `.N` is XLA's counter), averaged over the
    chips."""
    out, n = defaultdict(float), len(chips)
    for ops in chips:
        for name, _, dur in ops:
            if name.startswith(KERNEL):
                out[re.sub(r"\.\d+$", "", name[len(KERNEL):])] += dur / n
    return dict(out)


def window_owner(spans):
    """The compile log's owner of the program the window ran: the
    `program` count of its `*.run` spans, the most frequent one."""
    seen = defaultdict(int)
    for name, _, _, _, stats in spans:
        if name.startswith(ENTRY) and name.endswith(".run") \
                and "program" in stats:
            seen[stats["program"]] += 1
    if not seen:
        return None
    return f"executor:{max(seen, key=seen.get)}"


_SCOPED = {}


def scoped_seconds(start):
    """(by_phase, by_op) for this run, or None where the trace has no
    device op, the window no `*.run` span, or the program gives no text
    (`paddle_tpu.telemetry.compiled_text`, PR 27)."""
    tr = load(start)
    if not tr or not any(tr["chips"]):
        return None
    owner = window_owner(tr["spans"])
    if owner is None:
        return None
    key = (id(tr), owner)
    if key not in _SCOPED:
        from paddle_tpu import telemetry
        text_of = getattr(telemetry, "compiled_text", None)
        text = text_of(owner) if text_of else None
        _SCOPED.clear()
        _SCOPED[key] = op_seconds(tr["chips"], scopes_of(text)) \
            if text else None
    return _SCOPED[key]


# ------------------------------------------------------ the compile log
def union_seconds(intervals):
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def compile_phases(records, owner_prefix="executor:"):
    """{"trace", "lower", "backend", "cache_load": seconds, "programs": n}
    of the compile-log records (event, seconds, owner, fun_name, t_end)
    whose owner starts with `owner_prefix`. Traces nest (a jitted
    function called while another is traced reports its own), so `trace`
    is the union of their intervals; JAX's backend-compile event wraps the
    persistent cache's lookup, so a load's time is taken out of `backend`
    and stands alone. None where there is no such record."""
    mine = [r for r in records
            if r[2] is not None and str(r[2]).startswith(owner_prefix)]
    if not mine:
        return None
    ev = defaultdict(list)
    for event, seconds, _, _, t_end in mine:
        ev[event.rsplit("/", 1)[-1]].append((t_end - seconds, t_end))
    dur = {k: sum(b - a for a, b in v) for k, v in ev.items()}
    load_s = dur.get("cache_retrieval_time_sec", 0.0)
    return {"trace": union_seconds(ev.get("jaxpr_trace_duration", [])),
            "lower": dur.get("jaxpr_to_mlir_module_duration", 0.0),
            "backend": max(0.0, dur.get("backend_compile_duration", 0.0)
                           - load_s),
            "cache_load": load_s,
            "programs": len(ev.get("backend_compile_duration", []))}


def executor_compiles():
    """compile_phases of this process's log, or None where the program has
    no compile log (before PR 27)."""
    from paddle_tpu import telemetry
    log = getattr(telemetry, "compile_log", None)
    return compile_phases(log()) if log else None
