"""Built-in analysis passes for the static program verifier.

Each pass is a function `pass_fn(ctx) -> list[Diagnostic]` registered
with @analysis_pass(name); the pipeline (pipeline.py) runs them in
registration order. Passes are pure readers — they never mutate the
Program — so they are safe to run at any point, including the
Executor's pre-trace gate.
"""
import numpy as np

from .defuse import (CONTROL_FLOW_TYPES, MACRO_TYPES,
                     control_flow_free_vars, sub_block_bound_names,
                     sub_block_indices)
from .diagnostics import Diagnostic, ERROR, WARNING, INFO

__all__ = ["analysis_pass", "PASSES", "pass_names"]

PASSES = []  # [(name, fn)] in registration order


def analysis_pass(name):
    def deco(fn):
        fn._pass_name = name
        PASSES.append((name, fn))
        return fn
    return deco


def pass_names():
    return [n for n, _ in PASSES]


def _sparse_delta_names(program):
    """SparseDelta taps are seeded by the tracer before any op runs
    (core/trace.py:_collect_sparse_deltas) — implicitly defined."""
    names = set()
    for b in program.blocks:
        for op in b.ops:
            if op.attrs.get("is_sparse") and op.inputs.get("SparseDelta"):
                names.add(op.inputs["SparseDelta"][0])
    return names


def _initially_defined(ctx):
    """Names materialized before the first op executes: feeds, is_data
    vars, persistable scope state, and tracer-seeded sparse deltas."""
    defined = set(ctx.feed_names)
    for v in ctx.program.list_vars():
        if v.is_data or v.persistable:
            defined.add(v.name)
    defined |= _sparse_delta_names(ctx.program)
    return defined


# ---------------------------------------------------------------------------
# use-before-def
# ---------------------------------------------------------------------------
@analysis_pass("use-before-def")
def check_use_before_def(ctx):
    """A var consumed before any op defines it (and not fed / is_data /
    persistable) would surface as a KeyError mid-trace; report it at the
    IR level with the op that first trips it."""
    program = ctx.program
    diags = []
    reported = set()

    def walk(block, defined):
        for i, op in enumerate(block.ops):
            reads = set(op.input_names())
            if op.type == "backward_macro":
                reads.add(op.attrs.get("loss_name"))
                reads.discard(None)
            elif op.type in CONTROL_FLOW_TYPES:
                reads |= control_flow_free_vars(program, op)
            for name in sorted(reads - defined):
                if name in reported:
                    continue
                reported.add(name)
                defined.add(name)  # suppress downstream cascades
                diags.append(Diagnostic(
                    ERROR, "use-before-def",
                    f"var {name!r} is consumed by {op.type!r} before any "
                    f"op defines it",
                    block_idx=block.idx, op_idx=i, op_type=op.type,
                    var_names=[name],
                    hint="feed it, mark it persistable (and run the "
                         "startup program), or append a producing op "
                         "before this one"))
            if op.type in CONTROL_FLOW_TYPES:
                bound = sub_block_bound_names(op)
                for bidx in sub_block_indices(op):
                    if bidx < len(program.blocks):
                        walk(program.blocks[bidx], defined | bound)
            defined |= set(op.output_names())

    walk(program.global_block(), _initially_defined(ctx))
    return diags


# ---------------------------------------------------------------------------
# unknown-op
# ---------------------------------------------------------------------------
@analysis_pass("unknown-op")
def check_unknown_ops(ctx):
    """Op types with no registered kernel fail at trace time with
    NotImplementedError; flag them up front with a did-you-mean."""
    from ..ops.registry import has_kernel, closest_kernels
    diags = []
    for block in ctx.program.blocks:
        for i, op in enumerate(block.ops):
            if op.type in MACRO_TYPES or has_kernel(op.type):
                continue
            suggestions = closest_kernels(op.type)
            hint = (f"did you mean {', '.join(map(repr, suggestions))}?"
                    if suggestions else
                    "register a kernel in ops/ or fix the op type")
            diags.append(Diagnostic(
                ERROR, "unknown-op",
                f"op type {op.type!r} has no registered kernel",
                block_idx=block.idx, op_idx=i, op_type=op.type,
                hint=hint))
    return diags


# ---------------------------------------------------------------------------
# dead-code
# ---------------------------------------------------------------------------
@analysis_pass("dead-code")
def check_dead_code(ctx):
    """Ops unreachable from the fetch set that also write no persistable
    state are dropped by the tracer (core/trace.py:_prune_ops) — dead
    weight in the program, and usually a wiring mistake. Runs only when
    the caller names a fetch set (without one, reachability is
    undefined: every leaf output is a potential fetch)."""
    if not ctx.fetch_names:
        return []
    program = ctx.program
    persistable = {v.name for v in program.persistable_vars()}
    needed = set(ctx.fetch_names)
    live = set()
    block = program.global_block()
    for i in range(len(block.ops) - 1, -1, -1):
        op = block.ops[i]
        outs = set(op.output_names())
        if (needed & outs) or (outs & persistable):
            live.add(i)
            needed |= set(op.input_names())
            if op.type == "backward_macro":
                needed.add(op.attrs.get("loss_name"))
                needed.discard(None)
            if op.type in CONTROL_FLOW_TYPES:
                needed |= control_flow_free_vars(program, op)
    diags = []
    for i, op in enumerate(block.ops):
        if i in live:
            continue
        outs = op.output_names()
        diags.append(Diagnostic(
            WARNING, "dead-code",
            f"op {op.type!r} is unreachable from the fetch set "
            f"{sorted(ctx.fetch_names)} and writes no persistable state "
            f"(outputs: {outs})",
            block_idx=block.idx, op_idx=i, op_type=op.type,
            var_names=outs,
            hint="fetch one of its outputs, or remove the op"))
    return diags


# ---------------------------------------------------------------------------
# dtype/shape propagation
# ---------------------------------------------------------------------------
_BATCH_PLACEHOLDER = 4  # stand-in for -1 dims during abstract interp


def _declared_struct(var):
    """ShapeDtypeStruct from a declared Variable, or None if the var has
    no usable declaration (shape () means "unknown" for temps)."""
    import jax
    from ..core.dtypes import as_jnp_dtype
    shape = tuple(_BATCH_PLACEHOLDER if s == -1 else s for s in var.shape)
    return jax.ShapeDtypeStruct(shape, as_jnp_dtype(var.dtype))


def _shapes_compatible(declared, traced):
    """Declared shape vs traced shape; -1 declared dims are wildcards
    and the placeholder batch substitutes for them on the traced side.
    Fluid's scalar convention makes (1,) and () interchangeable."""
    d, t = tuple(declared), tuple(traced)
    if d in ((), (1,)) and t in ((), (1,)):
        return True
    if len(d) != len(t):
        return False
    return all(dd == -1 or dd == tt for dd, tt in zip(d, t))


@analysis_pass("shape-dtype")
def check_shape_dtype(ctx):
    """Abstract interpretation of the whole Program: each kernel runs
    under jax.eval_shape on ShapeDtypeStructs seeded from feeds and
    persistables, and traced output shapes/dtypes are checked against
    the declared Variable.shape/dtype. Control-flow ops recurse into
    their sub-blocks with the env threaded exactly the way
    core/trace.py:_exec_control_flow binds names (sub_env = copy of the
    parent env, carries/slices bound by the op), so a shape bug inside
    a cond/while/scan/static_rnn body is caught here instead of dying
    mid-trace — plus loop-specific checks trace time cannot phrase:
    branch outputs that disagree and loop carries whose struct changes
    across iterations. Ops whose kernels need concrete values (or
    whose inputs are already unknown) degrade to the declared signature
    instead of poisoning downstream checks."""
    import jax
    import jax.numpy as jnp
    from ..core.dtypes import as_jnp_dtype
    from ..core.trace import kernel_attrs
    from ..ops.registry import get_kernel, has_kernel, KernelCtx

    program = ctx.program
    block = program.global_block()
    diags = []
    env = {}       # name -> ShapeDtypeStruct
    unknown = set()

    for v in program.list_vars():
        if v.is_data or v.persistable:
            env[v.name] = _declared_struct(v)
    for name in ctx.feed_names:
        v = block.vars.get(name)
        if v is not None:
            env[name] = _declared_struct(v)
    for b in program.blocks:
        for op in b.ops:
            if op.attrs.get("is_sparse") and op.inputs.get("SparseDelta"):
                wname = op.inputs.get("W", [None])[0]
                wdt = (env[wname].dtype if wname in env else jnp.float32)
                env[op.inputs["SparseDelta"][0]] = \
                    jax.ShapeDtypeStruct((), wdt)

    ctx_k = KernelCtx(key=jax.random.PRNGKey(0),
                      is_test=getattr(program, "_is_test", False))

    def struct_eq(a, b):
        return (tuple(a.shape) == tuple(b.shape)
                and np.dtype(a.dtype) == np.dtype(b.dtype))

    def struct_str(s):
        return f"{np.dtype(s.dtype).name}{tuple(s.shape)}"

    def known(e, unk, names):
        return all(n in e and n not in unk for n in names)

    def walk_sub(bidx, e, unk, binds, check_declared=True):
        """Walk sub-block `bidx` against a COPY of (env, unknown) with
        `binds` name->struct overlaid — trace.py's sub_env=dict(env)
        semantics. Returns the sub scope for reading results out.
        check_declared=False for scan bodies: scan_layer builds the
        body against the FULL xs, so declared shapes there carry a
        spurious leading T the trace-time slice binding removes."""
        sub_e, sub_unk = dict(e), set(unk)
        sub_e.update(binds)
        for n in binds:
            sub_unk.discard(n)
        walk_block(program.blocks[bidx], sub_e, sub_unk,
                   check_declared=check_declared)
        return sub_e, sub_unk

    def carry_stability(blk, i, op, what, name, init, new):
        if not struct_eq(init, new):
            diags.append(Diagnostic(
                ERROR, "shape-dtype",
                f"op {op.type!r}: {what} {name!r} enters as "
                f"{struct_str(init)} but the body produces "
                f"{struct_str(new)} — the carry must keep one "
                f"shape/dtype across iterations",
                block_idx=blk.idx, op_idx=i, op_type=op.type,
                var_names=[name],
                hint="make the body's carry output match the init "
                     "struct (reshape/cast inside the body)"))

    def walk_control_flow(blk, i, op, env, unknown):
        a = op.attrs

        def bind_out(name, struct):
            if struct is None:
                unknown.add(name)
            else:
                env[name] = struct
                unknown.discard(name)

        if op.type == "cond":
            t_e, t_unk = walk_sub(a["true_block"], env, unknown, {})
            f_e, f_unk = walk_sub(a["false_block"], env, unknown, {})
            for name, tn, fn in zip(op.outputs.get("Out", ()),
                                    a["true_outs"], a["false_outs"]):
                ts = t_e.get(tn) if tn not in t_unk else None
                fs = f_e.get(fn) if fn not in f_unk else None
                if ts is not None and fs is not None \
                        and not struct_eq(ts, fs):
                    diags.append(Diagnostic(
                        ERROR, "shape-dtype",
                        f"op 'cond': branches disagree on output "
                        f"{name!r}: true branch {tn!r} is "
                        f"{struct_str(ts)}, false branch {fn!r} is "
                        f"{struct_str(fs)} — lax.cond requires "
                        f"identical output structs",
                        block_idx=blk.idx, op_idx=i, op_type=op.type,
                        var_names=[name],
                        hint="make both branches produce the same "
                             "shape and dtype"))
                bind_out(name, ts if ts is not None else fs)
            return
        if op.type == "while_loop":
            carries = a["carry_names"]
            if not known(env, unknown, carries):
                fallback_outputs(blk, env, unknown, op)
                return
            binds = {n: env[n] for n in carries}
            walk_sub(a["cond_block"], env, unknown, binds)
            b_e, b_unk = walk_sub(a["body_block"], env, unknown, binds)
            for cname, bout in zip(carries, a["body_outs"]):
                if bout in b_e and bout not in b_unk:
                    carry_stability(blk, i, op, "loop carry", cname,
                                    env[cname], b_e[bout])
            for name, cname in zip(op.outputs.get("Out", ()), carries):
                bind_out(name, env[cname])
            return
        if op.type == "scan":
            init_n = op.inputs["Init"][0]
            xs_n = op.inputs["Xs"][0]
            if not known(env, unknown, (init_n, xs_n)) \
                    or not env[xs_n].shape:
                fallback_outputs(blk, env, unknown, op)
                return
            xs = env[xs_n]
            x = jax.ShapeDtypeStruct(tuple(xs.shape[1:]), xs.dtype)
            b_e, b_unk = walk_sub(a["body_block"], env, unknown,
                                  {a["init_name"]: env[init_n],
                                   a["x_name"]: x},
                                  check_declared=False)
            co = a["carry_out"]
            if co in b_e and co not in b_unk:
                carry_stability(blk, i, op, "scan carry", co,
                                env[init_n], b_e[co])
            bind_out(op.outputs["CarryOut"][0], env[init_n])
            y = b_e.get(a["y_out"]) if a["y_out"] not in b_unk else None
            bind_out(op.outputs["Ys"][0],
                     None if y is None else jax.ShapeDtypeStruct(
                         (xs.shape[0],) + tuple(y.shape), y.dtype))
            return
        if op.type == "static_rnn":
            outers = [o for o, _ in a["x_map"]]
            inits = [init for init, _, _ in a["mem_map"]]
            if not known(env, unknown, outers + inits) \
                    or any(not env[o].shape for o in outers):
                fallback_outputs(blk, env, unknown, op)
                return
            T = env[outers[0]].shape[0]
            binds = {}
            for outer, step in a["x_map"]:
                xs = env[outer]
                binds[step] = jax.ShapeDtypeStruct(tuple(xs.shape[1:]),
                                                   xs.dtype)
            for init, prev, _ in a["mem_map"]:
                binds[prev] = env[init]
            b_e, b_unk = walk_sub(a["step_block"], env, unknown, binds)
            for init, _, new in a["mem_map"]:
                if new in b_e and new not in b_unk:
                    carry_stability(blk, i, op, "rnn memory", new,
                                    env[init], b_e[new])
            for step_y, out in a["y_map"]:
                y = b_e.get(step_y) if step_y not in b_unk else None
                bind_out(out,
                         None if y is None else jax.ShapeDtypeStruct(
                             (T,) + tuple(y.shape), y.dtype))
            for name, (init, _, _) in zip(a.get("final_mem_outs", []),
                                          a["mem_map"]):
                bind_out(name, env[init])
            return
        fallback_outputs(blk, env, unknown, op)

    def fallback_outputs(blk, env, unknown, op):
        for name in op.output_names():
            var = blk.vars.get(name)
            if var is not None and var.shape != ():
                env[name] = _declared_struct(var)
            else:
                unknown.add(name)

    def walk_block(blk, env, unknown, check_declared=True):
        for i, op in enumerate(blk.ops):
            if op.type in CONTROL_FLOW_TYPES:
                try:
                    walk_control_flow(blk, i, op, env, unknown)
                except (KeyError, IndexError, TypeError):
                    # malformed control-flow attrs: other passes report
                    fallback_outputs(blk, env, unknown, op)
                continue
            if op.type in MACRO_TYPES or not has_kernel(op.type):
                fallback_outputs(blk, env, unknown, op)
                continue
            in_names = op.input_names()
            if any(n in unknown or n not in env for n in in_names):
                fallback_outputs(blk, env, unknown, op)
                continue
            ins = {slot: [env[n] for n in names]
                   for slot, names in op.inputs.items() if names}
            attrs = kernel_attrs(op)[0]
            attrs.setdefault("_op_type", op.type)
            kern = get_kernel(op.type)
            try:
                out = jax.eval_shape(lambda xs: kern(ctx_k, xs, attrs),
                                     ins)
            except Exception as e:
                # Concretization/tracer errors mean the kernel needs
                # concrete VALUES — not checkable abstractly, degrade.
                # A plain TypeError/ValueError with fully-known input
                # structs means the op cannot execute at trace time
                # either (incompatible shapes/dtypes): a real bug.
                if isinstance(e, (TypeError, ValueError)) \
                        and not isinstance(e, jax.errors.JAXTypeError):
                    diags.append(Diagnostic(
                        ERROR, "shape-dtype",
                        f"op {op.type!r} rejects its input "
                        f"shapes/dtypes "
                        f"({', '.join(f'{n}={struct_str(env[n])}' for n in in_names)}): "
                        f"{e}",
                        block_idx=blk.idx, op_idx=i, op_type=op.type,
                        var_names=in_names,
                        hint="the same error would abort the trace; "
                             "fix the operand shapes"))
                else:
                    diags.append(Diagnostic(
                        INFO, "shape-dtype",
                        f"op {op.type!r} not abstractly traceable "
                        f"({type(e).__name__}); downstream shapes "
                        f"unchecked",
                        block_idx=blk.idx, op_idx=i, op_type=op.type))
                fallback_outputs(blk, env, unknown, op)
                continue
            for slot, names in op.outputs.items():
                vals = out.get(slot)
                if vals is None:
                    for n in names:
                        unknown.add(n)
                    continue
                for name, val in zip(names, vals):
                    env[name] = jax.ShapeDtypeStruct(tuple(val.shape),
                                                     val.dtype)
                    unknown.discard(name)
                    var = blk.vars.get(name)
                    if var is None or not check_declared:
                        continue
                    decl_dt = np.dtype(as_jnp_dtype(var.dtype))
                    if np.dtype(val.dtype) != decl_dt:
                        diags.append(Diagnostic(
                            ERROR, "shape-dtype",
                            f"op {op.type!r} produces {name!r} as "
                            f"{np.dtype(val.dtype).name} but the var is "
                            f"declared {var.dtype}",
                            block_idx=blk.idx, op_idx=i,
                            op_type=op.type, var_names=[name],
                            hint="fix the var's declared dtype or "
                                 "insert a cast op"))
                    if var.shape != () and not _shapes_compatible(
                            var.shape, val.shape):
                        diags.append(Diagnostic(
                            ERROR, "shape-dtype",
                            f"op {op.type!r} produces {name!r} with "
                            f"shape {tuple(val.shape)} but the var is "
                            f"declared {tuple(var.shape)} (with -1 as "
                            f"the batch placeholder "
                            f"{_BATCH_PLACEHOLDER})",
                            block_idx=blk.idx, op_idx=i,
                            op_type=op.type, var_names=[name],
                            hint="fix the declared shape or the op "
                                 "wiring"))

    walk_block(block, env, unknown)
    return diags


# ---------------------------------------------------------------------------
# write-after-write / aliasing hazards
# ---------------------------------------------------------------------------
@analysis_pass("waw-hazard")
def check_waw_hazards(ctx):
    """Two ops writing one var name with no read in between: the first
    value is dead, and the final value depends on op ORDER — exactly
    what parallel/ executors (which partition/reorder op lists) must
    not depend on. In-place updates (output name also an input of the
    same op) are the sanctioned aliasing pattern and pass."""
    program = ctx.program
    diags = []
    for block in program.blocks:
        last_write = {}   # name -> op idx
        read_since = {}   # name -> bool
        for i, op in enumerate(block.ops):
            reads = set(op.input_names())
            if op.type in CONTROL_FLOW_TYPES:
                reads |= control_flow_free_vars(program, op)
            if op.type == "backward_macro":
                reads.add(op.attrs.get("loss_name"))
                reads |= set(op.attrs.get("param_names", ()))
                reads.discard(None)
            for n in reads:
                read_since[n] = True
            for n in op.output_names():
                prev = last_write.get(n)
                if prev is not None and prev != i \
                        and not read_since.get(n, False):
                    diags.append(Diagnostic(
                        WARNING, "waw-hazard",
                        f"var {n!r} written by op {prev} is overwritten "
                        f"by op {i} ({op.type!r}) with no read in "
                        f"between — dead store, and order-dependent "
                        f"under parallel execution",
                        block_idx=block.idx, op_idx=i, op_type=op.type,
                        var_names=[n],
                        hint="give the second write its own var, or "
                             "drop the first"))
                last_write[n] = i
                read_since[n] = False
    return diags


# ---------------------------------------------------------------------------
# recompilation-hazard lint
# ---------------------------------------------------------------------------
_ATTR_ARRAY_WARN_ELEMS = 64


def _is_array_like(v):
    try:
        import jax
        if isinstance(v, jax.Array):
            return True
    except Exception:
        pass
    return isinstance(v, np.ndarray)


@analysis_pass("recompile-hazard")
def check_recompile_hazards(ctx):
    """The executor caches one compiled module per (program version,
    feed signature, ...) — core/trace.py closes over op attrs as
    compile-time constants. Attrs or feed declarations that vary per
    step silently turn every step into a fresh XLA compile."""
    diags = []
    for block in ctx.program.blocks:
        for i, op in enumerate(block.ops):
            for k, v in op.attrs.items():
                if callable(v) and not isinstance(v, type):
                    diags.append(Diagnostic(
                        WARNING, "recompile-hazard",
                        f"attr {k!r} of op {op.type!r} is a callable — "
                        f"unserializable and unhashable, so it can "
                        f"never participate in a compile-cache key",
                        block_idx=block.idx, op_idx=i, op_type=op.type,
                        hint="pass data, not functions, through op "
                             "attrs"))
                elif _is_array_like(v) and np.size(v) > _ATTR_ARRAY_WARN_ELEMS:
                    diags.append(Diagnostic(
                        WARNING, "recompile-hazard",
                        f"attr {k!r} of op {op.type!r} is a "
                        f"{np.size(v)}-element array baked into the "
                        f"program — it compiles to an XLA constant, and "
                        f"a per-step value here recompiles every step",
                        block_idx=block.idx, op_idx=i, op_type=op.type,
                        hint="feed it as a data var instead of an attr"))
                elif isinstance(v, (set, frozenset)):
                    diags.append(Diagnostic(
                        WARNING, "recompile-hazard",
                        f"attr {k!r} of op {op.type!r} is a set — "
                        f"iteration order is unstable across processes, "
                        f"so serialized programs and cache keys drift",
                        block_idx=block.idx, op_idx=i, op_type=op.type,
                        hint="use a sorted list"))
    # feed-signature hazards: the executor compiles per distinct feed
    # shape; unknown dims beyond the leading batch axis multiply the
    # number of distinct signatures (padding keeps them static)
    for v in ctx.program.global_block().vars.values():
        if not v.is_data:
            continue
        wild = [ax for ax, s in enumerate(v.shape) if s == -1]
        if any(ax > 0 for ax in wild):
            diags.append(Diagnostic(
                WARNING, "recompile-hazard",
                f"data var {v.name!r} declares unknown dim(s) at "
                f"non-leading axes {[ax for ax in wild if ax > 0]} "
                f"(shape {tuple(v.shape)}) — every distinct feed shape "
                f"compiles a fresh executable",
                block_idx=0, var_names=[v.name],
                hint="pad to a static length and carry a seq_len var "
                     "(see lod.py)"))
    return diags
