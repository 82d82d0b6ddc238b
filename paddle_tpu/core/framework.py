"""Core IR: Program / Block / Operator / Variable.

Parity: python/paddle/fluid/framework.py (reference) — the Python graph
builder that the reference lowers to a C++ ProgramDesc protobuf and walks
op-by-op. Here the Program is a lightweight op list that the Executor
traces into ONE pure JAX function and compiles with XLA (see
core/trace.py) — whole-program compilation instead of per-op kernel
dispatch, which is the TPU-native execution model.
"""
import contextlib
import re

import numpy as np

from .. import unique_name
from .dtypes import convert_dtype

__all__ = [
    "Variable", "Parameter", "Operator", "Block", "Program",
    "default_main_program", "default_startup_program", "program_guard",
    "name_scope", "grad_var_name", "default_seed",
]

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name):
    return name + GRAD_SUFFIX


class Variable:
    """Symbolic tensor in a Block.

    Shapes may contain -1 (unknown/batch dims, resolved at feed time —
    XLA still sees static shapes because compilation is per feed-shape).
    """

    def __init__(self, block, name=None, shape=None, dtype="float32",
                 persistable=False, stop_gradient=False, is_data=False,
                 lod_level=0, trainable=False, initializer=None, **kwargs):
        self.block = block
        self.name = name if name is not None else unique_name.generate("tmp")
        self.shape = tuple(int(s) for s in shape) if shape is not None else ()
        self.dtype = convert_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.lod_level = lod_level
        self.trainable = trainable
        self.initializer = initializer
        # sequence-length companion variable name for LoD-style data (mask-based
        # replacement for the reference's LoDTensor levels)
        self.seq_len_var = kwargs.get("seq_len_var", None)

    # ---- numpy-ish sugar -------------------------------------------------
    @property
    def ndim(self):
        return len(self.shape)

    def astype(self, dtype):
        from ..layers import tensor as _t
        return _t.cast(self, dtype)

    def __str__(self):
        return (f"Variable(name={self.name}, shape={self.shape}, "
                f"dtype={self.dtype}, persistable={self.persistable})")

    __repr__ = __str__

    # arithmetic operator overloads are patched in by layers/math_op_patch.py


class Parameter(Variable):
    """Trainable persistable variable (ref framework.py:Parameter)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        kwargs.setdefault("trainable", True)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)
        self.regularizer = kwargs.get("regularizer", None)
        self.gradient_clip_attr = kwargs.get("gradient_clip_attr", None)
        self.do_model_average = kwargs.get("do_model_average", None)
        self.optimize_attr = kwargs.get("optimize_attr", {"learning_rate": 1.0})


class Operator:
    """One op node: type + named input/output slots + attrs.

    The kernel implementing `type` lives in ops/registry.py — programs stay
    serializable because ops carry no callables.
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        # store names, not Variable objects, for serialization; None
        # entries (optional slots, e.g. bias_attr=False) are dropped so
        # slot lists are clean for the analysis def-use builder
        self.inputs = self._normalize_slots(inputs)
        self.outputs = self._normalize_slots(outputs)
        self.attrs = dict(attrs or {})
        # the site this op was declared at (ref framework.py: the same
        # attribute): the tracer writes it under the op type in the
        # device trace's name stack; a kernel's attrs leave it out
        # (core/trace.py::kernel_attrs, the shape pass too)
        if _name_scope_stack:
            self.attrs.setdefault("op_namescope",
                                  "/".join(_name_scope_stack))

    @staticmethod
    def _normalize_slots(slots):
        out = {}
        for k, vs in (slots or {}).items():
            if not isinstance(vs, (list, tuple)):
                vs = (vs,)
            out[k] = [v.name if isinstance(v, Variable) else v
                      for v in vs if v is not None]
        return out

    def input_names(self):
        return [n for vs in self.inputs.values() for n in vs]

    def output_names(self):
        return [n for vs in self.outputs.values() for n in vs]

    def __str__(self):
        return f"Op(type={self.type}, in={self.inputs}, out={self.outputs})"

    __repr__ = __str__


class Block:
    """Ordered op list + var table (ref framework.py:Block).

    Only block 0 is used for straight-line programs; control-flow layers use
    functional lax primitives inside a single op instead of sub-blocks, so
    nested blocks exist mainly for API parity.
    """

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    def var(self, name):
        v = self.vars.get(name)
        if v is None:
            raise ValueError(f"variable {name!r} not found in block {self.idx}")
        return v

    def has_var(self, name):
        return name in self.vars

    def create_var(self, **kwargs):
        name = kwargs.get("name") or unique_name.generate("tmp")
        kwargs["name"] = name
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[name] = v
        return v

    def create_parameter(self, **kwargs):
        name = kwargs.get("name") or unique_name.generate("param")
        kwargs["name"] = name
        p = Parameter(self, kwargs.pop("shape"), kwargs.pop("dtype"), **kwargs)
        self.vars[name] = p
        # parameters are global — mirror into block 0 like the reference does
        g = self.program.global_block()
        if g is not self:
            g.vars[name] = p
        return p

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type=type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.append(op)
        self.program._bump_version()
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type=type, inputs=inputs, outputs=outputs, attrs=attrs)
        self.ops.insert(0, op)
        self.program._bump_version()
        return op

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]


class Program:
    """A whole computation graph; traced+compiled as one XLA module.

    Parity: ref framework.py:Program / ProgramDesc. random_seed controls all
    in-graph RNG ops (dropout, random init); the Executor folds per-op
    indices into one key so every op draws independent, reproducible bits.
    """

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self._version = 0
        self.random_seed = 0
        self._backward_sections = []   # filled by core/backward.py
        self._lr_schedulers = []
        self._is_test = False
        self._device_counters = {}     # see mark_counter

    # -- structure ---------------------------------------------------------
    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    def all_parameters(self):
        return self.global_block().all_parameters()

    def mark_counter(self, var, name, kind="counter"):
        """Mark a scalar the step computes on the device (an expert
        layer's load, say) as telemetry metric `name`: every
        `Executor.run` of this program brings it back with the fetches,
        inside `executor.fetch_readback`, and adds it to
        `telemetry.counter(name)` (`kind="gauge"`: sets
        `telemetry.gauge(name)`), counting the runs in
        `telemetry.counter("<prefix>.steps")`, the prefix being the
        name up to its first dot. A program with no mark pays nothing."""
        if kind not in ("counter", "gauge"):
            raise ValueError(f"kind {kind!r}: 'counter' or 'gauge'")
        self._device_counters[name] = (var.name, kind)
        self._bump_version()

    def name_scopes(self):
        """The sites its ops were declared at (`op_namescope`)."""
        return {op.attrs["op_namescope"] for b in self.blocks
                for op in b.ops if op.attrs.get("op_namescope")}

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def persistable_vars(self):
        seen = {}
        for v in self.list_vars():
            if v.persistable:
                seen[v.name] = v
        return list(seen.values())

    # -- static analysis (paddle_tpu/analysis — proglint) ------------------
    def verify(self, fetch_list=None, feed_names=None, passes=None,
               raise_on_error=False):
        """Run the static verifier/lint pipeline over this program and
        return a list of analysis.Diagnostic (most severe first).

        fetch_list enables dead-code reachability; feed_names are names
        guaranteed materialized at step start (is_data/persistable vars
        are always assumed). With raise_on_error=True, error-severity
        findings raise analysis.ProgramVerificationError."""
        from ..analysis import verify_program
        return verify_program(self, fetch_list=fetch_list,
                              feed_names=feed_names, passes=passes,
                              raise_on_error=raise_on_error)

    # -- cloning (ref Program.clone(for_test=True)) ------------------------
    def clone(self, for_test=False):
        import copy
        p = Program.__new__(Program)
        p.blocks = []
        p.current_block_idx = 0
        p._version = self._version
        p.random_seed = self.random_seed
        p._lr_schedulers = list(self._lr_schedulers)
        p._is_test = for_test or self._is_test
        p._device_counters = dict(self._device_counters)
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            for name, v in b.vars.items():
                nv = copy.copy(v)
                nv.block = nb
                nb.vars[name] = nv
            for op in b.ops:
                attrs = dict(op.attrs)
                if for_test and op.type in ("dropout", "batch_norm"):
                    attrs["is_test"] = True
                nop = Operator(nb, op.type)
                nop.attrs = attrs   # op_namescope rides along as any other
                nop.inputs = {k: list(v) for k, v in op.inputs.items()}
                nop.outputs = {k: list(v) for k, v in op.outputs.items()}
                # fluid interop: proto-declared attr types (INT vs
                # LONG) ride clones, or a loaded-then-re-exported
                # model would lose the distinction (fluid_proto)
                at = getattr(op, "attr_types", None)
                if at:
                    nop.attr_types = dict(at)
                nb.ops.append(nop)
            p.blocks.append(nb)
        if for_test:
            p._backward_sections = []
            p._prune_backward_for_test()
        else:
            p._backward_sections = list(self._backward_sections)
        return p

    def _prune_backward_for_test(self):
        """Drop grad/update/train-only ops when cloning for inference
        (is_train_only marks e.g. the LR-counter increment and EMA
        updates, which must not mutate state during eval)."""
        b = self.global_block()
        b.ops = [op for op in b.ops
                 if not op.attrs.get("is_optimizer_op", False)
                 and not op.attrs.get("is_backward_op", False)
                 and not op.attrs.get("is_train_only", False)]
        self._bump_version()

    # -- serialization (ref ProgramDesc protobuf → JSON here) --------------
    def to_desc(self):
        return {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [{
                "idx": b.idx,
                "parent_idx": b.parent_idx,
                "vars": [{
                    "name": v.name, "shape": list(v.shape), "dtype": v.dtype,
                    "persistable": v.persistable, "trainable": v.trainable,
                    "is_data": v.is_data, "lod_level": v.lod_level,
                    "stop_gradient": v.stop_gradient,
                    "is_parameter": isinstance(v, Parameter),
                } for v in b.vars.values()],
                "ops": [{
                    "type": op.type, "inputs": op.inputs,
                    "outputs": op.outputs,
                    "attrs": _jsonable_attrs(op.attrs),
                } for op in b.ops],
            } for b in self.blocks],
        }

    @staticmethod
    def from_desc(desc):
        p = Program()
        p.random_seed = desc.get("random_seed", 0)
        p.blocks = []
        for bd in desc["blocks"]:
            b = Block(p, bd["idx"], bd["parent_idx"])
            for vd in bd["vars"]:
                if vd.get("is_parameter"):
                    par = Parameter(b, vd["shape"], vd["dtype"], name=vd["name"],
                                    trainable=vd.get("trainable", True))
                    b.vars[vd["name"]] = par
                else:
                    b.vars[vd["name"]] = Variable(
                        b, name=vd["name"], shape=vd["shape"], dtype=vd["dtype"],
                        persistable=vd["persistable"], is_data=vd.get("is_data", False),
                        lod_level=vd.get("lod_level", 0),
                        stop_gradient=vd.get("stop_gradient", False))
            for od in bd["ops"]:
                op = Operator(b, od["type"])
                op.inputs = {k: list(v) for k, v in od["inputs"].items()}
                op.outputs = {k: list(v) for k, v in od["outputs"].items()}
                op.attrs = od["attrs"]
                b.ops.append(op)
            p.blocks.append(b)
        p._bump_version()
        return p


def _jsonable_attrs(attrs):
    out = {}
    for k, v in attrs.items():
        if isinstance(v, np.ndarray):
            out[k] = {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
        elif isinstance(v, (np.integer,)):
            out[k] = int(v)
        elif isinstance(v, (np.floating,)):
            out[k] = float(v)
        elif callable(v):
            out[k] = f"<callable:{getattr(v, '__name__', 'fn')}>"
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# default programs & guards (ref framework.py bottom half)
# ---------------------------------------------------------------------------
_main_program = Program()
_startup_program = Program()
default_seed = 0


def default_main_program():
    return _main_program


def default_startup_program():
    return _startup_program


def switch_main_program(program):
    global _main_program
    prev, _main_program = _main_program, program
    return prev


def switch_startup_program(program):
    global _startup_program
    prev, _startup_program = _startup_program, program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_start = None
    if startup_program is not None:
        prev_start = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_start is not None:
            switch_startup_program(prev_start)


_name_scope_stack = []
_SCOPE_ELEMENT = re.compile(r"[\w.\-]+")


def valid_name_scope(prefix):
    """Whether every `/`-separated element of `prefix` can stand in a
    device trace's name stack."""
    return isinstance(prefix, str) and all(
        _SCOPE_ELEMENT.fullmatch(e) for e in prefix.split("/"))


@contextlib.contextmanager
def name_scope(prefix):
    """Every op declared inside carries the stack of open scopes, joined
    by `/`, as its `op_namescope` attribute (ref framework.py:name_scope).
    Under a profiler session the device time of the op's kernels is
    reported by that site (`fluid.profiler`): which `mul` was slow."""
    if not valid_name_scope(prefix):
        raise ValueError(
            f"name_scope({prefix!r}): each '/'-separated element must "
            f"be made of letters, digits, '_', '.' and '-'")
    _name_scope_stack.append(prefix)
    try:
        yield
    finally:
        _name_scope_stack.pop()
