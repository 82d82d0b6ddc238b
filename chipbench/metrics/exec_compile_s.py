"""Layer: entry, tracer. Seconds, from process start, of each phase of the
programs the executor built (`paddle_tpu.telemetry.compile_log()`, owner
`executor:<program>`): `.trace` Program to jaxpr, `.lower` jaxpr to MLIR,
`.backend` XLA's compile, `.cache_load` the persistent cache's loads
(taken out of `.backend`, which JAX's event wraps around them)."""
from chipbench import program_trace


def read(facts, name):
    if not facts.get("on_chip"):
        return None
    phases = program_trace.executor_compiles()
    return phases[program_trace.part(name)] if phases else None
