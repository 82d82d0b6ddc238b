"""Layer: entry. Device-idle time inside the window, per step, each instant
of it put down to the innermost `pt/executor.*` span of the program open at
that instant: `.feed_put`, `.prepare`, `.step`, `.fetch_readback`,
`.release`; `.caller` is idle time under no `pt/executor.run` (the
trainer's own loop). Their sum is the window's wall time per step less
`train_device_step_ms`, but for what lies in `executor.run` outside those
children."""
from chipbench import program_trace


def read(facts, name):
    tr = program_trace.load(__file__)
    if not facts.get("on_chip") or not tr or not tr["chips"] \
            or not tr["chips"][0] or tr["window"] is None:
        return None
    idle = program_trace.idle_by_span(tr["chips"][0], tr["spans"],
                                      *tr["window"])
    if idle is None:
        return None
    return 1e3 * idle.get(program_trace.part(name), 0.0) / facts["steps"]
