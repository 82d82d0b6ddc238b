"""Layer: entry, tracer. Programs the executor asked XLA for since the
process started, loads from the persistent cache included (a count: the
CPU rehearsal reports it too)."""
from chipbench import program_trace


def read(facts, name):
    phases = program_trace.executor_compiles()
    return float(phases["programs"]) if phases else None
