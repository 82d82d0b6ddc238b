"""paddle_tpu.analysis — static program verifier + lint (proglint).

The reference validates programs op-by-op in C++ (`InferShape`,
`OpDesc::Check`) as they are built; this stack defers the whole Program
to one JAX trace (core/trace.py), so without a verifier a malformed
program dies mid-trace with an XLA stack trace. This package checks the
IR *before* tracing:

    use-before-def     var consumed before defined (not fed/persistable)
    unknown-op         op type with no kernel, with did-you-mean
    dead-code          ops unreachable from the fetch set
    shape-dtype        abstract interpretation via jax.eval_shape vs
                       declared Variable.shape/dtype
    waw-hazard         write-after-write / aliasing (parallel/ safety)
    recompile-hazard   attrs/feed signatures that bust the compile cache

The `meshlint` subpackage extends the same pipeline to SHARDED
executions (PartitionSpecs vs the mesh, collective consistency,
donation aliasing, per-device footprint, static recompile hazards) —
see analysis/meshlint/__init__.py. It is imported lazily (ParallelExecutor.verify(), FarmConfig.verify(),
tools/tpulint.py), never from here: the validate-off path must not pay
for it.

Entry points: Program.verify(), Executor.run(..., validate=True) /
PADDLE_TPU_VALIDATE=1, tools/proglint.py, and tools/tpulint.py.
"""
from .diagnostics import (Diagnostic, ProgramVerificationError,
                          SEVERITIES, ERROR, WARNING, INFO,
                          format_diagnostics, max_severity, has_errors)
from .defuse import (DefUseGraph, OpNode, build_defuse,
                     CONTROL_FLOW_TYPES, MACRO_TYPES)
from .passes import analysis_pass, PASSES, pass_names
from .pipeline import AnalysisContext, run_passes, verify_program

__all__ = [
    "Diagnostic", "ProgramVerificationError", "SEVERITIES",
    "ERROR", "WARNING", "INFO",
    "format_diagnostics", "max_severity", "has_errors",
    "DefUseGraph", "OpNode", "build_defuse",
    "CONTROL_FLOW_TYPES", "MACRO_TYPES",
    "analysis_pass", "PASSES", "pass_names",
    "AnalysisContext", "run_passes", "verify_program",
]
