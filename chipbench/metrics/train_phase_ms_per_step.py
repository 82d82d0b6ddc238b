"""Layer: model step. Device-op time per step by phase of the name stack:
`.forward` is `jvp(<op>)`, `.backward` `transpose(jvp(<op>))`, `.optimizer`
the bare op type after the gradient. A fusion counts whole under the scope
XLA kept on it. With what lies under no scope (`train_op_ms_per_step.
unscoped`) they add up to `train_device_step_ms`."""
from chipbench import program_trace


def read(facts, name):
    if not facts.get("on_chip"):
        return None
    got = program_trace.scoped_seconds(__file__)
    if got is None:
        return None
    return 1e3 * got[0].get(program_trace.part(name), 0.0) / facts["steps"]
