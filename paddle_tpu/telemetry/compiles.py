"""Who asked XLA for which program, and what each phase of it cost.

One listener on `jax.monitoring`, registered once (`install()`, called
where the package first has jax: paddle_tpu/__init__), appends one
record per event to a bounded process-global list (a trace that nests
inside a later one is folded into it): the three phases JAX
reports for every program it builds (trace to jaxpr, jaxpr to MLIR,
backend compile -- which on a persistent-cache hit is the load) and the
persistent cache's hits, misses and retrieval times. It fires only when
something compiles, so the steady path pays nothing; it is always on,
and gates nothing (`executor.compile_count`, `explain_recompile` stay
behind telemetry's flag).

`owner` is a plain module attribute: the entry layers set it around the
calls that may compile for them (`owned(...)`: "executor:<program
version>" around a first run, "decode.prefill:<bucket>", "decode.step",
"decode.write_slots"); whatever compiles with no owner set -- the
caller's own jits -- is logged under None. One owner at a time: two
threads compiling at once are put down to whichever set it last.

`register_program` keeps, per owner, the way to the optimized HLO text
of the executable that owner runs (`compiled_text(owner)`). A device
trace names an op by its HLO instruction and this runtime's profile
reader (`jax.profiler.ProfileData`) does not give out the instruction's
`op_name`, so a reader joins the two through this text: instruction
name -> `metadata={op_name=...}`, which carries `jax.named_scope`: the
Fluid op's type and, under it, the site it was declared at. A site is
told from a primitive's name in that stack by the program's own list of
them (`program_sites(owner)`).
"""
import collections
import contextlib
import time

__all__ = ["compile_log", "owned", "install", "EVENTS", "CompileRecord",
           "NO_OWNER", "register_program", "compiled_text",
           "program_sites"]

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
EVENTS = frozenset((TRACE, LOWER, BACKEND, CACHE_HIT, CACHE_MISS,
                    CACHE_LOAD))

CompileRecord = collections.namedtuple(
    "CompileRecord", ["event", "seconds", "owner", "fun_name", "t_end"])

owner = None
_records = collections.deque(maxlen=50_000)
_installed = False


def _on_duration(event, duration, **kw):
    if event not in EVENTS:
        return
    now = time.perf_counter()
    if event == TRACE:
        # a function traced while another is being traced reports first
        # and lies inside the outer one's interval: the outer record
        # stands for both (hundreds of inner ones per program otherwise)
        start = now - duration
        while _records and _records[-1].event == TRACE \
                and _records[-1].owner == owner \
                and _records[-1].t_end - _records[-1].seconds >= start:
            _records.pop()
    _records.append(CompileRecord(event, float(duration), owner,
                                  kw.get("fun_name"), now))


def _on_event(event, **kw):
    if event in EVENTS:
        _records.append(CompileRecord(event, 0.0, owner, None,
                                      time.perf_counter()))


def install():
    """Register the listener with jax.monitoring; later calls do nothing."""
    global _installed
    if _installed:
        return
    _installed = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


class owned:
    """Context manager: whatever compiles inside is put down to `name`.
    One object can be entered again and again (not nested in itself)."""
    __slots__ = ("name", "_before")

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        global owner
        self._before, owner = owner, self.name
        return self

    def __exit__(self, *exc):
        global owner
        owner = self._before
        return False


# for a stretch that is known to compile nothing (a cached key): no
# object made, no owner set
NO_OWNER = contextlib.nullcontext()


_programs = collections.OrderedDict()    # owner -> (HLO text's maker, sites)
_MAX_PROGRAMS = 16


def register_program(owner_name, jitted, args, sites=()):
    """Remember how to get the HLO text of what `jitted(*args)` runs, and
    the `op_namescope`s its ops carry (`Program.name_scopes()`).
    Nothing is lowered or rendered here: only the arguments' shapes,
    dtypes and shardings are kept (no array), and `jitted` itself. The
    newest program of an owner replaces the one before; the oldest
    owner goes when there are more than 16."""
    import jax

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=getattr(a, "sharding", None))

    avals = jax.tree_util.tree_map(aval, args)
    _programs.pop(owner_name, None)
    _programs[owner_name] = (
        lambda: jitted.lower(*avals).compile().as_text(), frozenset(sites))
    while len(_programs) > _MAX_PROGRAMS:
        _programs.popitem(last=False)


def compiled_text(owner_name):
    """The optimized HLO module of `owner_name`'s newest program, as
    text, or None. While JAX still holds the executable this costs the
    rendering only; after `jax.clear_caches()` it lowers again and
    loads the executable from the persistent cache."""
    entry = _programs.get(owner_name)
    return entry[0]() if entry is not None else None


def program_sites(owner_name):
    """The name scopes (`op_namescope`) of `owner_name`'s newest
    program's ops: the elements of a name stack that are sites."""
    entry = _programs.get(owner_name)
    return entry[1] if entry is not None else frozenset()


def compile_log():
    """The records so far, oldest first: (event, seconds, owner,
    fun_name, t_end). `seconds` is 0.0 for the cache's hit and miss
    events; `t_end` is time.perf_counter() when JAX reported the event,
    so a phase ran from `t_end - seconds` to `t_end`. Traces nest (a
    jitted function called while another is traced reports its own);
    the inner ones are folded into the outer as it arrives, and what
    the clocks' jitter leaves is covered by taking the union of a
    phase's intervals, not the sum of `seconds`. The oldest records go
    when there are more than 50,000."""
    return list(_records)
