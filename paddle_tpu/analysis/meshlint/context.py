"""meshlint data model + pass registry.

Mirrors the proglint shape exactly (analysis/pipeline.py): passes are
`fn(mctx) -> [Diagnostic]` registered with @mesh_pass, run in
registration order, crash-isolated to INFO diagnostics, and report
through the same Diagnostic records — so the CLI and the executor
gates consume one format.

Everything here is import-light: no jax at module level, and a
MeshLintContext can describe a sharded execution WITHOUT live devices
(MeshSpec is axis names + sizes, not a jax.sharding.Mesh) — that is
what lets the multichip tests' configs be linted on any host.
"""
from ..diagnostics import Diagnostic, ProgramVerificationError, INFO

__all__ = ["MeshSpec", "ShardMapUse", "MeshLintContext", "MESH_PASSES",
           "mesh_pass", "mesh_pass_names", "run_mesh_passes",
           "verify_mesh", "normalize_spec", "spec_str"]

MESH_PASSES = []  # [(name, fn)] in registration order


def mesh_pass(name):
    def deco(fn):
        fn._pass_name = name
        MESH_PASSES.append((name, fn))
        return fn
    return deco


def mesh_pass_names():
    return [n for n, _ in MESH_PASSES]


def normalize_spec(spec):
    """A PartitionSpec (or plain tuple) -> canonical tuple of entries,
    each entry None | axis-name | tuple of axis names."""
    entries = []
    for e in tuple(spec):
        if e is None or isinstance(e, str):
            entries.append(e)
        else:
            entries.append(tuple(e))
    return tuple(entries)


def entry_axes(entry):
    """Axis names bound by one spec entry."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def spec_str(spec):
    """P(...)-style rendering of a normalized spec, for messages."""
    parts = []
    for e in normalize_spec(spec):
        if e is None:
            parts.append("None")
        elif isinstance(e, str):
            parts.append(repr(e))
        else:
            parts.append("(" + ", ".join(repr(a) for a in e) + ")")
    return "P(" + ", ".join(parts) + ")"


class MeshSpec:
    """Declared mesh: ordered axis name -> size. Deliberately NOT a
    jax.sharding.Mesh — no devices needed to lint a config."""

    def __init__(self, axes):
        self.axes = dict(axes)
        for name, size in self.axes.items():
            if not isinstance(name, str) or int(size) < 1:
                raise ValueError(f"bad mesh axis {name!r}={size!r}")

    @classmethod
    def from_mesh(cls, mesh):
        """From a live jax Mesh (mesh.shape is an ordered mapping)."""
        return cls({a: int(mesh.shape[a]) for a in mesh.axis_names})

    def axis_size(self, name):
        return int(self.axes[name])

    def size(self):
        n = 1
        for s in self.axes.values():
            n *= int(s)
        return n

    def __str__(self):
        inner = ", ".join(f"{a}={s}" for a, s in self.axes.items())
        return f"mesh({inner})"

    __repr__ = __str__


class ShardMapUse:
    """One shard_map call site, described statically.

    name          call-site label for diagnostics ("gradsync.step",
                  "pipeline.gpipe", ...)
    in_specs      sequence of PartitionSpecs (normalized), one per arg
    out_specs     same for outputs (may be empty when unknown)
    arg_shapes    per-arg global shape tuple, or None when unknown
    arg_names     per-arg label for messages (optional)
    """

    def __init__(self, name, in_specs, out_specs=(), arg_shapes=None,
                 arg_names=None):
        self.name = name
        self.in_specs = tuple(normalize_spec(s) for s in in_specs)
        self.out_specs = tuple(normalize_spec(s) for s in out_specs)
        n = len(self.in_specs)
        self.arg_shapes = (tuple(arg_shapes) if arg_shapes is not None
                           else (None,) * n)
        self.arg_names = (tuple(arg_names) if arg_names is not None
                          else tuple(f"arg{i}" for i in range(n)))


class MeshLintContext:
    """Read-only description of one sharded execution, handed to every
    mesh pass. All fields optional except the mesh — passes check what
    is present and stay quiet about the rest.

    mesh            MeshSpec (or live jax Mesh — converted)
    uses            [ShardMapUse]
    program         the Program (enables IR-level walks)
    fetch_names / feed_names   like AnalysisContext
    donate_state    persistable state is donated to the step fn
    async_steps     async in-flight window (0/None = synchronous)
    grad_sync       gradsync policy grammar string or policy object
    sparse          sparse-engine grammar string or policy object
    pipeline_schedule  "gpipe" | "1f1b" | None
    data_axis       pipeline data axis name (PipelineTrainer data_axis)
    member_policies per-member policy strings when members may diverge
    param_specs     {param name -> PartitionSpec} for footprint
    extra_state_bytes  flat extra per-member bytes (e.g. KV cache)
    memory_cap_bytes   per-device byte budget (None = skip the check)
    label           config label for reports
    """

    def __init__(self, mesh, uses=(), program=None, fetch_names=(),
                 feed_names=(), donate_state=True, async_steps=None,
                 grad_sync=None, sparse=None, pipeline_schedule=None,
                 data_axis=None, member_policies=None,
                 param_specs=None, extra_state_bytes=0,
                 memory_cap_bytes=None, label=""):
        if not isinstance(mesh, MeshSpec):
            mesh = MeshSpec.from_mesh(mesh)
        self.mesh = mesh
        self.uses = tuple(uses)
        self.program = program
        self.fetch_names = tuple(fetch_names or ())
        self.feed_names = tuple(feed_names or ())
        self.donate_state = bool(donate_state)
        self.async_steps = async_steps
        self.grad_sync = grad_sync
        self.sparse = sparse
        self.pipeline_schedule = pipeline_schedule
        self.data_axis = data_axis
        self.member_policies = (None if member_policies is None
                                else tuple(member_policies))
        self.param_specs = dict(param_specs or {})
        self.extra_state_bytes = int(extra_state_bytes)
        self.memory_cap_bytes = memory_cap_bytes
        self.label = label


def run_mesh_passes(mctx, passes=None):
    """Run the meshlint pipeline; same contract as analysis.run_passes:
    sorted diagnostics, subset selection by name, a crashing pass
    degrades to an info diagnostic instead of killing verification."""
    selected = list(MESH_PASSES)
    if passes is not None:
        wanted = set(passes)
        unknown = wanted - {n for n, _ in selected}
        if unknown:
            raise ValueError(
                f"unknown meshlint pass(es): {sorted(unknown)} "
                f"(available: {mesh_pass_names()})")
        selected = [(n, f) for n, f in selected if n in wanted]
    diags = []
    for name, fn in selected:
        try:
            diags.extend(fn(mctx) or [])
        except Exception as e:
            diags.append(Diagnostic(
                INFO, name,
                f"meshlint pass crashed: {type(e).__name__}: {e}",
                hint="report this — a verifier pass should handle any "
                     "well-formed config"))
    diags.sort(key=Diagnostic.sort_key)
    return diags


def verify_mesh(mctx, passes=None, raise_on_error=False):
    diags = run_mesh_passes(mctx, passes=passes)
    if raise_on_error and any(d.severity == "error" for d in diags):
        raise ProgramVerificationError(diags)
    return diags
