"""Layer: model step. Device-op time per step under one Fluid op type's
scope (`jax.named_scope(op.type)` in core/trace.py), forward and backward
together; `.unscoped` is what lies under no scope (XLA's own copies, and
what the tracer emits between ops)."""
from chipbench import program_trace


def read(facts, name):
    if not facts.get("on_chip"):
        return None
    got = program_trace.scoped_seconds(__file__)
    if got is None:
        return None
    return 1e3 * got[1].get(program_trace.part(name), 0.0) / facts["steps"]
