"""kern-capability pass: will the sharded program actually get its
registered Pallas kernels?

The kern registry (ops/kern) dispatches per op at trace time through a
STATIC capability probe — shapes and dtypes only, runnable on
jax.ShapeDtypeStructs without data. This pass runs those same probes
at lint time over the Program's declared shapes, so a sharded config
learns BEFORE anything traces which ops will silently lower their jnp
fallback (functional, just unaccelerated).

Mesh awareness: the program body traces INSIDE shard_map, so each
device sees the per-shard batch — when the config declares a data
axis, the probe runs on the leading dim divided by that axis's size.
A kernel that accepts the global batch but rejects the per-device
slice is exactly the surprise this pass exists to catch.

Import discipline (bench-contract pin): ops.kern is imported lazily
INSIDE the pass body — a validate-off process never pulls the
registry through this module.
"""
from ..diagnostics import Diagnostic, WARNING
from .context import mesh_pass

__all__ = ["check_kern_capability", "probe_program_kernels"]


def _static_shape(shape):
    return all(isinstance(d, int) and d > 0 for d in shape)


def _struct_of(block, gblock, op, slot):
    """Declared ShapeDtypeStruct for the first var in `slot`, or None
    when the slot is absent / the var is undeclared / any dim is
    dynamic (-1 batch: no static verdict possible, stay quiet)."""
    names = op.inputs.get(slot) or []
    if not names:
        return None
    var = block.vars.get(names[0]) or gblock.vars.get(names[0])
    if var is None:
        return None
    shape = tuple(var.shape)
    if not shape or not _static_shape(shape):
        return None
    import jax
    from ...core.dtypes import as_jnp_dtype
    return jax.ShapeDtypeStruct(shape, as_jnp_dtype(var.dtype))


def _shard_leading(struct, dp):
    """The per-device view of a batch-leading value: shard_map slices
    the leading dim over the data axis before the body traces."""
    if struct is None or dp <= 1 or not struct.shape:
        return struct
    lead = struct.shape[0]
    if lead % dp:
        return struct  # indivisible: mesh-spec owns that finding
    import jax
    return jax.ShapeDtypeStruct((lead // dp,) + tuple(struct.shape[1:]),
                                struct.dtype)


def _ln_probe_args(block, gblock, op, dp):
    x = _struct_of(block, gblock, op, "X")
    if x is None:
        return None
    x = _shard_leading(x, dp)
    scale = _struct_of(block, gblock, op, "Scale")
    bias = _struct_of(block, gblock, op, "Bias")
    eps = op.attrs.get("epsilon", 1e-5)
    begin = op.attrs.get("begin_norm_axis", 1)
    return (x, scale, bias, eps, begin), {}


def _emb_probe_args(block, gblock, op, dp):
    table = _struct_of(block, gblock, op, "W")
    ids = _struct_of(block, gblock, op, "Ids")
    if table is None or ids is None:
        return None
    pool = op.attrs.get("pooltype", op.attrs.get("combiner",
                                                 "sum")).lower()
    pool = "mean" if pool in ("mean", "average") else pool
    if pool not in ("sum", "mean"):
        return None  # the op kernel raises; not a kern finding
    # mirror the op kernel's id normalization: squeeze a trailing 1,
    # lift 1-d ids to [R, 1]
    shape = list(ids.shape)
    if len(shape) >= 2 and shape[-1] == 1:
        shape = shape[:-1]
    if len(shape) == 1:
        shape = shape + [1]
    import jax
    import jax.numpy as jnp
    inv = _shard_leading(jax.ShapeDtypeStruct(tuple(shape), jnp.int32),
                         dp)
    weights = _struct_of(block, gblock, op, "Weight")
    return (table, inv, weights, pool), {}


# op type -> probe-arg extractor; only op types the kern registry
# serves from Program IR (the library-call adapters — decode_attend,
# int8_quant, ... — never appear as program ops)
_EXTRACTORS = {
    "layer_norm": _ln_probe_args,
    "fused_embedding_seq_pool": _emb_probe_args,
}


def probe_program_kernels(program, mesh=None, data_axis=None):
    """[(block_idx, op_idx, op_type, kernel_name, shape_str, ok)] for
    every program op a registered kernel serves and whose declared
    shapes give the probe a static verdict. Imports ops.kern."""
    from ...ops.kern import registry as kreg
    dp = 1
    if mesh is not None and data_axis and data_axis in mesh.axes:
        dp = mesh.axis_size(data_axis)
    gblock = program.global_block()
    out = []
    for block in program.blocks:
        for i, op in enumerate(block.ops):
            extract = _EXTRACTORS.get(op.type)
            if extract is None or op.type not in kreg.ADAPTERS:
                continue
            spec = kreg.get(kreg.ADAPTERS[op.type])
            built = extract(block, gblock, op, dp)
            if built is None:
                continue
            args, kwargs = built
            try:
                ok = bool(spec.probe(*args, **kwargs))
            except Exception:
                continue  # a probe that cannot judge stays silent
            shapes = ", ".join(
                f"{a.dtype}{tuple(a.shape)}" for a in args
                if hasattr(a, "shape") and hasattr(a, "dtype"))
            out.append((block.idx, i, op.type, spec.name, shapes, ok))
    return out


@mesh_pass("kern-capability")
def check_kern_capability(mctx):
    if mctx.program is None:
        return []
    diags = []
    dp = 1
    if mctx.data_axis and mctx.data_axis in mctx.mesh.axes:
        dp = mctx.mesh.axis_size(mctx.data_axis)
    for bidx, i, op_type, kernel, shapes, ok in probe_program_kernels(
            mctx.program, mesh=mctx.mesh, data_axis=mctx.data_axis):
        if ok:
            continue
        sharded = (f" (per-device view: leading dim / "
                   f"{mctx.data_axis}={dp})" if dp > 1 else "")
        diags.append(Diagnostic(
            WARNING, "kern-capability",
            f"op {op_type!r} has a registered Pallas kernel "
            f"({kernel!r}) but its capability probe rejects the "
            f"declared shapes [{shapes}]{sharded} — this op lowers the "
            f"jnp fallback: correct, just not accelerated",
            block_idx=bidx, op_idx=i, op_type=op_type,
            hint="see `tpukern probe` for the kernel's shape/dtype "
                 "gate; pad or retile the offending dims (or accept "
                 "the fallback)"))
    return diags
