"""mesh-spec pass: PartitionSpecs vs the declared mesh.

The structural rules mirror what jax.shard_map enforces at trace time
(pinned against the real API by tests/test_meshlint_property.py over
300+ random configs):

    unknown axis    any spec axis not on the mesh       -> reject
    axis reuse      one axis in several entries         -> reject
    rank            len(spec) > value rank              -> reject
    divisibility    dim size % prod(axis sizes) != 0    -> reject
"""
from ..diagnostics import Diagnostic, ERROR
from .context import entry_axes, mesh_pass, normalize_spec, spec_str

__all__ = ["static_spec_verdict", "check_mesh_specs"]


def static_spec_verdict(mesh, spec, shape=None, kind="in"):
    """(ok, reasons) — does jax.shard_map accept `spec` for a value of
    `shape` on `mesh`? Pure structural model, no jax import.
    `shape=None` skips the shape-dependent rules (rank/divisibility).
    `kind` only flavors the reason strings ("in" | "out")."""
    spec = normalize_spec(spec)
    reasons = []
    seen = set()
    for entry in spec:
        for ax in entry_axes(entry):
            if ax not in mesh.axes:
                reasons.append(
                    f"{kind}_spec {spec_str(spec)} names axis {ax!r} "
                    f"not on the {mesh}")
            elif ax in seen:
                reasons.append(
                    f"{kind}_spec {spec_str(spec)} uses axis {ax!r} "
                    f"more than once: jax binds a mesh axis to at "
                    f"most one dimension of one value")
            seen.add(ax)
    if shape is not None:
        if len(spec) > len(shape):
            reasons.append(
                f"{kind}_spec {spec_str(spec)} is longer (rank "
                f"{len(spec)}) than the value (shape {tuple(shape)})")
        else:
            for d, entry in enumerate(spec):
                axes = [a for a in entry_axes(entry) if a in mesh.axes]
                if not axes:
                    continue
                factor = 1
                for a in axes:
                    factor *= mesh.axis_size(a)
                if shape[d] % factor:
                    reasons.append(
                        f"{kind}_spec {spec_str(spec)} shards dim {d} "
                        f"(size {shape[d]}) over {'*'.join(axes)}="
                        f"{factor}, which does not divide it")
    return (not reasons), reasons


@mesh_pass("mesh-spec")
def check_mesh_specs(mctx):
    diags = []
    for use in mctx.uses:
        specs = [("in", n, s, sh) for n, s, sh in
                 zip(use.arg_names, use.in_specs, use.arg_shapes)]
        specs += [("out", f"out{i}", s, None)
                  for i, s in enumerate(use.out_specs)]
        for kind, name, spec, shape in specs:
            ok, reasons = static_spec_verdict(mctx.mesh, spec,
                                              shape, kind=kind)
            for r in reasons:
                diags.append(Diagnostic(
                    ERROR, "mesh-spec",
                    f"shard_map {use.name!r}, {kind}put {name!r}: {r}",
                    var_names=[name],
                    hint="fix the PartitionSpec or the mesh axis "
                         "sizes; this exact config fails at trace "
                         "time"))
    return diags
