"""Mixed precision (bfloat16) utilities.

Parity: paddle/contrib/float16/float16_transpiler.py — the reference
rewrites a fp32 inference ProgramDesc to fp16. On TPU the native fast
dtype is bfloat16 (MXU-preferred, no loss-scaling needed thanks to fp32
exponent range), so the transpiler casts params + feeds to bf16 and
keeps normalization/softmax/losses in fp32 (the kernels in ops/kernels_nn
already upcast internally).
"""
import numpy as np

from .core.scope import global_scope

__all__ = ["bf16_guard", "cast_program_to_bf16", "cast_params_to_bf16"]

# dtype-sensitive ops that must keep fp32 params (norm stats/scales)
_KEEP_FP32_PARAM_SUFFIX = ("batch_norm", "layer_norm", "group_norm")

# ... and, whatever a variable is called, what it is used for: one that
# an op reads or writes through one of these slots keeps float32 (the
# norms' scales and shifts: their kernels compute the statistics in
# float32; a router's weight and the routing weights it gives: a router
# that rounds its scores picks other experts; a linear-attention layer's
# log-decay and what it is made from: the scan sums it over a chunk). A
# name can be anything a ParamAttr says.
_KEEP_FP32_SLOTS = {
    "batch_norm": ("Scale", "Bias"), "layer_norm": ("Scale", "Bias"),
    "group_norm": ("Scale", "Bias"), "rms_norm": ("Scale",),
    "moe_route": ("Weight", "TopkW"),
    "kda_gate": ("ALog", "DtBias", "Out"),
    "selective_scan": ("ALog", "D", "Dt"),
}

# ... and what a kept variable is made of through a step or a bias: a
# selective scan's dt = softplus(x W + b) keeps the sum and the bias b
# float32 (the product x W is the program's)
_KEEP_FP32_THROUGH = {"softplus": "X", "elementwise_add": "Y"}


def _fp32_by_use(program):
    """Names of the variables some op reads or writes through a slot of
    _KEEP_FP32_SLOTS, and what they are made of through
    _KEEP_FP32_THROUGH."""
    keep = set()
    for block in program.blocks:
        for op in block.ops:
            for slot in _KEEP_FP32_SLOTS.get(op.type, ()):
                keep.update(op.inputs.get(slot, ()))
                keep.update(op.outputs.get(slot, ()))
        for op in reversed(block.ops):
            slot = _KEEP_FP32_THROUGH.get(op.type)
            if slot and keep.intersection(op.output_names()):
                keep.update(op.inputs.get(slot, ()))
    return keep


def cast_program_to_bf16(program, keep_io_fp32=True):
    """Rewrite var dtypes float32→bfloat16 for Parameters and activations.

    Never touched: data IO vars, norm scales/biases, and ALL persistable
    non-Parameter state (optimizer moments, beta-pow scalars, LR vars,
    counters, bn moving stats) — bf16 cannot represent e.g. beta2=0.999
    (rounds to 1.0, zeroing Adam's bias-corrected LR), so optimizer state
    must stay fp32 (master-weight style; the update kernels already
    compute in fp32). Returns the modified program (in place, like the
    ref float16 transpiler)."""
    from .core.framework import Parameter
    by_use = _fp32_by_use(program)
    for block in program.blocks:
        for var in block.vars.values():
            if var.dtype != "float32":
                continue
            if keep_io_fp32 and var.is_data:
                continue
            if var.name in by_use:
                continue
            if isinstance(var, Parameter):
                # norm scales stay fp32 (kernels compute stats in fp32)
                if any(s in var.name for s in _KEEP_FP32_PARAM_SUFFIX):
                    continue
            elif var.persistable:
                continue
            var.dtype = "bfloat16"
    program._bump_version()
    return program


def cast_params_to_bf16(program, scope=None):
    """Cast already-initialized scope params to match program dtypes."""
    import jax.numpy as jnp
    scope = scope or global_scope()
    for var in program.persistable_vars():
        val = scope.get(var.name)
        if val is None:
            continue
        want = var.dtype
        have = str(np.asarray(val).dtype) if not hasattr(val, "dtype") else str(val.dtype)
        if want == "bfloat16" and have == "float32":
            scope.set(var.name, jnp.asarray(val, dtype=jnp.bfloat16))


import contextlib


@contextlib.contextmanager
def bf16_guard(program=None):
    """Build-time scoped bf16 region (ref contrib amp bf16_guard): ops
    appended to `program` (default main) INSIDE this context get their
    float32 Parameters and intermediate vars rewritten to bfloat16 on
    exit — the scoped version of cast_program_to_bf16, with the same
    keep-fp32 rules (data IO, norm scales, optimizer/persistable state).
    """
    from .core.framework import default_main_program, Parameter
    program = program or default_main_program()
    block = program.global_block()
    start = len(block.ops)
    yield
    new_ops = list(block.ops[start:])
    # include ops nested in control-flow sub-blocks created in the region
    def expand(ops):
        out = []
        for op in ops:
            out.append(op)
            for key in ("true_block", "false_block", "cond_block",
                        "body_block", "step_block"):
                bidx = op.attrs.get(key)
                if bidx is not None:
                    out.extend(expand(program.blocks[bidx].ops))
        return out
    new_ops = expand(new_ops)
    # outputs created inside the region, plus the Parameters its ops
    # consume — NOT inputs produced outside (those keep their dtype;
    # the kernels' autocast handles the boundary)
    all_vars = {}
    for blk in program.blocks:
        all_vars.update(blk.vars)
    touched = set()
    for op in new_ops:
        touched.update(op.output_names())
        for n in op.input_names():
            if isinstance(all_vars.get(n), Parameter):
                touched.add(n)
    by_use = _fp32_by_use(program)
    for blk in program.blocks:
        for var in blk.vars.values():
            if var.name not in touched or var.dtype != "float32":
                continue
            if var.is_data:
                continue
            if var.name in by_use:
                continue
            if isinstance(var, Parameter):
                if any(s in var.name for s in _KEEP_FP32_PARAM_SUFFIX):
                    continue
            elif var.persistable:
                continue
            var.dtype = "bfloat16"
    program._bump_version()
