"""Program → pure JAX function tracer.

This is the TPU-native replacement for the reference's op-by-op executor
(paddle/fluid/framework/executor.cc): instead of dispatching one kernel
per op per step, the whole op list is traced into ONE pure function

    step(persist: dict, feed: dict, key) -> (fetches: list, new_persist: dict)

which the Executor jits — XLA sees the entire step (forward, backward,
optimizer update) as a single module and can fuse/layout/overlap freely.

The `backward_macro` op (appended by core/backward.py:append_backward) is
handled here: the forward segment is replayed inside jax.value_and_grad
(has_aux carries the full env so intermediate vars stay fetchable and
batch-norm stat updates survive), replacing the reference's symbolic
per-op grad ops (python/paddle/fluid/backward.py).
"""
import contextlib

import jax
import jax.numpy as jnp

from ..ops.registry import get_kernel, KernelCtx, accel, lowering_for
from .framework import grad_var_name
from .dtypes import is_float

__all__ = ["build_step_fn", "exec_op", "op_scope"]

# Fuse the per-param optimizer tail (SURVEY §5 headroom note): maximal
# consecutive runs of adam ops with identical hyperparams+LR are
# grouped by (shape, dtype) and updated as ONE stacked elementwise
# kernel instead of one fused kernel per param — transformer-base has
# ~100 small bias/LayerNorm params whose individual updates are pure
# per-kernel overhead. Only small params are stacked (the stack/unstack
# copies a group; for large matmul weights the copy would cost more
# than the launch it saves). Arithmetic is identical to the per-param
# kernel (XLA's fusion choices may differ by ~1 ULP). Module-level
# toggles so benchmarks can A/B.
FUSE_OPTIMIZER_TAIL = True
FUSE_MAX_ELEMS = 1 << 18


def op_scope(op_type, site=None):
    """The scope of everything one Fluid op emits: the op type names it,
    so a device trace can say which Fluid op a fusion belongs to, and
    under it the site the op was declared at (`op_namescope`), so the
    trace can say which `mul`. Inside value_and_grad JAX's name stack
    adds the phase by itself: `jvp(<op>)/<site>` forward,
    `transpose(jvp(<op>))/<site>` backward, the bare op type after the
    gradient. Metadata only: the module is the same."""
    stack = contextlib.ExitStack()
    stack.enter_context(jax.named_scope(op_type))
    if site:
        stack.enter_context(jax.named_scope(site))
    return stack


def kernel_attrs(op):
    """(the attributes as a kernel gets them, the op's site):
    `op_namescope` is the trace's, no kernel's."""
    attrs = dict(op.attrs)
    return attrs, attrs.pop("op_namescope", None)


def _adam_sig(op):
    a = op.attrs
    return (a.get("beta1", 0.9), a.get("beta2", 0.999),
            a.get("epsilon", 1e-8), op.inputs["LearningRate"][0])


def _plan_update_tail(tail_ops):
    """Split the update-op tail into plan entries: ("op", op, idx) run
    one-by-one, ("adam_run", [(op, idx), ...]) eligible for stacked
    execution. Only CONSECUTIVE same-signature adam ops group — other
    ops between them keep their program order."""
    plan = []
    i = 0
    while i < len(tail_ops):
        op, idx = tail_ops[i]
        if op.type != "adam":
            plan.append(("op", op, idx))
            i += 1
            continue
        sig = _adam_sig(op)
        run = [(op, idx)]
        j = i + 1
        while j < len(tail_ops) and tail_ops[j][0].type == "adam" \
                and _adam_sig(tail_ops[j][0]) == sig:
            run.append(tail_ops[j])
            j += 1
        plan.append(("adam_run", run))
        i = j
    return plan


def _exec_adam_group(env, ops_, is_test, place):
    """Stacked adam update for params of one (shape, dtype) group: the
    REGISTERED 'adam' kernel runs once on [N, ...]-stacked inputs (no
    second copy of the update math to drift), with the per-param [1]
    beta-pow scalars stacked and reshaped so they broadcast as [N,1..]
    leading-axis rows."""
    n = len(ops_)

    def stack(slot):
        return jnp.stack([env[op.inputs[slot][0]] for op in ops_])

    p = stack("Param")
    bshape = (n,) + (1,) * (p.ndim - 1)
    ins = {
        "Param": [p],
        "Grad": [stack("Grad")],
        "Moment1": [stack("Moment1")],
        "Moment2": [stack("Moment2")],
        "Beta1Pow": [stack("Beta1Pow").reshape(bshape)],
        "Beta2Pow": [stack("Beta2Pow").reshape(bshape)],
        "LearningRate": [env[ops_[0].inputs["LearningRate"][0]]],
    }
    ctx = KernelCtx(is_test=is_test, place=place)
    out = get_kernel("adam")(ctx, ins, kernel_attrs(ops_[0])[0])
    for i, op in enumerate(ops_):
        env[op.outputs["ParamOut"][0]] = out["ParamOut"][0][i]
        env[op.outputs["Moment1Out"][0]] = out["Moment1Out"][0][i]
        env[op.outputs["Moment2Out"][0]] = out["Moment2Out"][0][i]
        env[op.outputs["Beta1PowOut"][0]] = \
            out["Beta1PowOut"][0][i].reshape(
                env[op.inputs["Beta1Pow"][0]].shape)
        env[op.outputs["Beta2PowOut"][0]] = \
            out["Beta2PowOut"][0][i].reshape(
                env[op.inputs["Beta2Pow"][0]].shape)


def _exec_adam_run(env, run, key, is_test, place, block):
    """Execute one consecutive adam run: same-(shape, dtype) params of
    tail size stack into one kernel; the rest go through exec_op."""
    groups = {}
    order = []
    for op, idx in run:
        pv = env[op.inputs["Param"][0]]
        gkey = (tuple(pv.shape), str(pv.dtype))
        if gkey not in groups:
            groups[gkey] = []
            order.append(gkey)
        groups[gkey].append((op, idx))
    for gkey in order:
        members = groups[gkey]
        n_elems = 1
        for s in gkey[0]:
            n_elems *= s
        if len(members) >= 2 and n_elems <= FUSE_MAX_ELEMS:
            with op_scope("adam"):
                _exec_adam_group(env, [op for op, _ in members], is_test,
                                 place)
        else:
            for op, idx in members:
                exec_op(env, op, idx, key, is_test, place, block)


def _replay_block(program, blk, env, base_key, is_test, place):
    """Execute a sub-block's ops against env (used by control-flow ops)."""
    for j, op in enumerate(blk.ops):
        exec_op(env, op, blk.idx * 100000 + j, base_key, is_test, place, blk,
                program=program)


def _exec_control_flow(env, op, base_key, is_test, place, program):
    import jax as _jax
    attrs = op.attrs
    if op.type == "cond":
        pred = env[op.inputs["Cond"][0]]
        tb = program.blocks[attrs["true_block"]]
        fb = program.blocks[attrs["false_block"]]

        def branch(blk, out_names):
            def f(_):
                e = dict(env)
                _replay_block(program, blk, e, base_key, is_test, place)
                return tuple(e[n] for n in out_names)
            return f

        pred_scalar = jnp.reshape(pred, ()).astype(bool)
        res = _jax.lax.cond(pred_scalar,
                            branch(tb, attrs["true_outs"]),
                            branch(fb, attrs["false_outs"]), None)
        for n, v in zip(op.outputs["Out"], res):
            env[n] = v
        return
    if op.type == "while_loop":
        carry_names = attrs["carry_names"]
        cb = program.blocks[attrs["cond_block"]]
        bb = program.blocks[attrs["body_block"]]

        def cond_f(carry):
            e = dict(env)
            e.update(dict(zip(carry_names, carry)))
            _replay_block(program, cb, e, base_key, is_test, place)
            return jnp.reshape(e[attrs["cond_out"]], ()).astype(bool)

        def body_f(carry):
            e = dict(env)
            e.update(dict(zip(carry_names, carry)))
            _replay_block(program, bb, e, base_key, is_test, place)
            return tuple(e[n] for n in attrs["body_outs"])

        init = tuple(env[n] for n in carry_names)
        res = _jax.lax.while_loop(cond_f, body_f, init)
        for n, v in zip(op.outputs["Out"], res):
            env[n] = v
        return
    if op.type == "static_rnn":
        bb = program.blocks[attrs["step_block"]]
        x_map = attrs["x_map"]        # [(outer_name, step_name)]
        mem_map = attrs["mem_map"]    # [(init_name, prev_step_name, new_name)]
        y_map = attrs["y_map"]        # [(step_y_name, out_name)]

        def body_f(carry, xt):
            e = dict(env)
            for (_, sname), v in zip(x_map, xt):
                e[sname] = v
            for (_, pname, _), c in zip(mem_map, carry):
                e[pname] = c
            _replay_block(program, bb, e, base_key, is_test, place)
            new_c = tuple(e[n] for _, _, n in mem_map)
            ys = tuple(e[y] for y, _ in y_map)
            return new_c, ys

        init = tuple(env[i] for i, _, _ in mem_map)
        xs = tuple(env[o] for o, _ in x_map)
        carry, ys = _jax.lax.scan(body_f, init, xs)
        for (_, outn), v in zip(y_map, ys):
            env[outn] = v
        for name, v in zip(attrs.get("final_mem_outs", []), carry):
            env[name] = v
        return
    if op.type == "scan":
        bb = program.blocks[attrs["body_block"]]

        def body_f(carry, x):
            e = dict(env)
            e[attrs["init_name"]] = carry
            e[attrs["x_name"]] = x
            _replay_block(program, bb, e, base_key, is_test, place)
            return e[attrs["carry_out"]], e[attrs["y_out"]]

        carry, ys = _jax.lax.scan(body_f, env[op.inputs["Init"][0]],
                                  env[op.inputs["Xs"][0]])
        env[op.outputs["CarryOut"][0]] = carry
        env[op.outputs["Ys"][0]] = ys
        return
    raise NotImplementedError(op.type)


def exec_op(env, op, op_idx, base_key, is_test, place, block, program=None):
    """Execute one op against env (name → array)."""
    if op.type in ("cond", "while_loop", "scan", "static_rnn"):
        prog = program if program is not None else block.program
        _exec_control_flow(env, op, base_key, is_test, place, prog)
        return
    kern = get_kernel(op.type)
    ins = {}
    for slot, names in op.inputs.items():
        if not names:
            continue
        vals = []
        for n in names:
            if n not in env:
                raise KeyError(
                    f"op {op.type!r} input {slot}:{n!r} not materialized; "
                    f"did you run the startup program / feed it?")
            vals.append(env[n])
        ins[slot] = vals
    attrs, site = kernel_attrs(op)
    attrs.setdefault("_op_type", op.type)
    with op_scope(op.type, site):
        key = jax.random.fold_in(base_key, op_idx) \
            if base_key is not None else None
        # trace-time lowering consults the kern registry through the one
        # accel seam (ops.registry.accel) — op kernels never import pallas
        ctx = KernelCtx(key=key, is_test=is_test, place=place, accel=accel)
        outs = kern(ctx, ins, attrs)
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for n, v in zip(names, vals):
            var = block.vars.get(n)
            if var is not None and var.stop_gradient and is_float(str(v.dtype)) \
                    and not var.persistable:
                v = jax.lax.stop_gradient(v)
            env[n] = v


def _find_backward(ops):
    idxs = [i for i, op in enumerate(ops) if op.type == "backward_macro"]
    if not idxs:
        return None
    if len(idxs) > 1:
        raise NotImplementedError("multiple backward sections in one program")
    return idxs[0]


def _sub_block_free_vars(program, op, _seen=None):
    """Names a control-flow op's sub-blocks read but don't produce,
    recursing through nested control flow (a Switch chain nests cond ops
    in wrapper blocks — their free vars are still this op's inputs)."""
    free = set()
    seen = _seen if _seen is not None else set()
    for key in ("true_block", "false_block", "cond_block", "body_block",
                "step_block"):
        bidx = op.attrs.get(key)
        if bidx is None or bidx in seen:
            continue
        seen.add(bidx)
        sub = program.blocks[bidx]
        produced = {n for o in sub.ops for n in o.output_names()}
        for o in sub.ops:
            sub_free = set(o.input_names())
            if o.type in ("cond", "while_loop", "scan", "static_rnn"):
                sub_free |= _sub_block_free_vars(program, o, seen)
            free |= sub_free - produced
    return free


def _prune_ops(program, ops, fetch_names):
    """Keep only ops needed for the fetches or writing persistable state
    (param updates, bn stats, counters) — the reference Executor prunes
    the ProgramDesc to the fetch targets the same way."""
    block = program.global_block()
    persistable = {v.name for v in program.persistable_vars()}
    needed = set(fetch_names)
    kept = []
    for op in reversed(ops):
        outs = op.output_names()
        if (needed & set(outs)) or any(o in persistable for o in outs):
            kept.append(op)
            needed |= set(op.input_names())
            if op.type == "backward_macro":
                needed.add(op.attrs["loss_name"])
            if op.type in ("cond", "while_loop", "scan", "static_rnn"):
                needed |= _sub_block_free_vars(program, op)
    return list(reversed(kept))


def _collect_sparse_deltas(program, ops):
    """(delta_name, param_name) for every is_sparse lookup in ops,
    recursing into control-flow sub-blocks (deltas must be seeded in
    env before any replay touches the op)."""
    out = []
    seen_blocks = set()

    def scan(op_list):
        for op in op_list:
            if op.attrs.get("is_sparse") and op.inputs.get("SparseDelta"):
                out.append((op.inputs["SparseDelta"][0],
                            op.inputs["W"][0]))
            for key in ("true_block", "false_block", "cond_block",
                        "body_block", "step_block"):
                bidx = op.attrs.get(key)
                if bidx is not None and bidx not in seen_blocks:
                    seen_blocks.add(bidx)
                    scan(program.blocks[bidx].ops)

    scan(ops)
    return out


def build_step_fn(program, fetch_names, is_test, place,
                  grad_transform=None, sparse_engine=None):
    """Returns step(persist, feed, key) -> (fetches, new_persist).

    Pure and jittable; the op list/attrs are closed over (static).

    grad_transform: optional hook applied at the point where data-
    parallel gradients are summed — called as
    `grad_transform(grads, env) -> (synced_grads, extra_persist)`
    right after jax.value_and_grad, before the optimizer tail, with ALL
    grads (dense param grads keyed by param name AND is_sparse row
    grads keyed by their delta-tap name) and the full env; the returned
    dict overrides matching entries. `extra_persist` entries (e.g.
    gradsync error-feedback residuals) join new_persist even though
    they are not program vars. The parallel gradsync policy layer
    threads through here; None keeps the step bit-identical to before
    the hook existed.

    sparse_engine: optional parallel/sparse.py SparseEngine — THE
    dispatch hook for mesh-sharded embedding tables. Ops the engine
    owns (lookup_table on a distributed table, its sparse_sgd /
    sparse_adam tail updates) execute through the engine instead of
    their registered kernels, and the engine's non-program state
    (stats accumulators, stale-update rings) joins new_persist. None
    (every path but the explicit ParallelExecutor sparse one) leaves
    dispatch byte-for-byte untouched."""
    block = program.global_block()
    ops = _prune_ops(program, list(block.ops), fetch_names)
    persist_names = [v.name for v in program.persistable_vars()]
    bi = _find_backward(ops)
    loss_scope = contextlib.nullcontext
    if bi is not None:
        # the float32 sum the gradient is taken of belongs to the op that
        # computes the loss
        loss_op = next((op for op in reversed(ops[:bi])
                        if ops[bi].attrs["loss_name"]
                        in op.output_names()), None)
        if loss_op is not None:
            def loss_scope():
                return op_scope(loss_op.type,
                                loss_op.attrs.get("op_namescope"))
    sparse_deltas = _collect_sparse_deltas(program, ops)
    eng = sparse_engine

    def run_op(e, op, i, key):
        if eng is not None and eng.owns(op):
            eng.exec(e, op)
        else:
            exec_op(e, op, i, key, is_test, place, block)

    def step(persist, feed, key):
        # Pallas dispatch (trace-time) follows the Place this step is
        # compiled for; place=None callers scope it themselves or take
        # the process default
        with lowering_for(getattr(place, "platform", None)):
            return _step(persist, feed, key)

    def _step(persist, feed, key):
        env = {}
        env.update(feed)
        env.update(persist)
        extra_persist = {}
        # is_sparse lookup taps: scalar zero by default (broadcasts in
        # the lookup add); the training path below overrides the ones
        # in its diff set with full-shape zeros so grads are ROW grads
        for dname, wname in sparse_deltas:
            if wname in env:
                env[dname] = jnp.zeros((), env[wname].dtype)
        if bi is None:
            for i, op in enumerate(ops):
                run_op(env, op, i, key)
        else:
            bop = ops[bi]
            pnames = bop.attrs["param_names"]
            loss_name = bop.attrs["loss_name"]
            base_env = dict(env)

            def fwd(pvals):
                e = dict(base_env)
                e.update(pvals)
                for i, op in enumerate(ops[:bi]):
                    run_op(e, op, i, key)
                loss = e[loss_name]
                with loss_scope():
                    return jnp.sum(loss.astype(jnp.float32)), e

            if getattr(program, "_remat", False):
                # transpiler.memory_optimize: recompute forward activations
                # in the backward pass instead of keeping them in HBM
                fwd = jax.checkpoint(fwd)

            pvals = {n: env[n] for n in pnames}
            # row-sparse embedding taps: the delta joins the diff set
            # with the GATHERED shape (ids + [D]) — its gradient is the
            # row gradient; the [V, D] table never densifies (the
            # SelectedRows-grad analog, ref lookup_table_op.cc)
            sparse_specs = bop.attrs.get("sparse_params", [])
            tap_grads = {}  # delta name -> row-grad var name
            ids_shapes = {}
            missing = [t["ids"] for s in sparse_specs for t in s["taps"]
                       if t["ids"] not in env]
            if missing:
                # ids produced INSIDE the forward (e.g. a cast/reshape
                # of a feed): shapes are static, so one abstract replay
                # of the forward segment (scalar-zero deltas already in
                # base_env) yields them without running anything
                def _probe(_):
                    e = dict(base_env)
                    for i, op in enumerate(ops[:bi]):
                        run_op(e, op, i, key)
                    return {n: e[n] for n in missing}

                ids_shapes = {n: v.shape for n, v in
                              jax.eval_shape(_probe, 0).items()}
            for spec in sparse_specs:
                wv = env[spec["param"]]
                for tap in spec["taps"]:
                    ishape = tuple(env[tap["ids"]].shape
                                   if tap["ids"] in env
                                   else ids_shapes[tap["ids"]])
                    if ishape and ishape[-1] == 1:
                        ishape = ishape[:-1]
                    pvals[tap["delta"]] = jnp.zeros(
                        ishape + (wv.shape[-1],), wv.dtype)
                    tap_grads[tap["delta"]] = tap["grad"]
            (_, env), grads = jax.value_and_grad(fwd, has_aux=True)(pvals)
            # what the step emits here belongs to no kernel: the Fluid op
            # it stands for names it
            with op_scope("backward_macro"):
                if grad_transform is not None:
                    synced, extra_persist = grad_transform(dict(grads), env)
                    grads = dict(grads, **synced)
                for n in pnames:
                    env[grad_var_name(n)] = grads[n].astype(env[n].dtype) \
                        if hasattr(grads[n], "astype") else grads[n]
            for dname, gname in tap_grads.items():
                env[gname] = grads[dname]
            tail = [(op, i) for i, op in
                    enumerate(ops[bi + 1:], start=bi + 1)]
            if FUSE_OPTIMIZER_TAIL:
                for entry in _plan_update_tail(tail):
                    if entry[0] == "op":
                        run_op(env, entry[1], entry[2], key)
                    else:
                        _exec_adam_run(env, entry[1], key, is_test,
                                       place, block)
            else:
                for op, i in tail:
                    run_op(env, op, i, key)
        new_persist = {n: env[n] for n in persist_names if n in env}
        new_persist.update(extra_persist)
        if eng is not None:
            new_persist.update(eng.collect(env))
        fetches = [env[n] for n in fetch_names]
        return fetches, new_persist

    return step
