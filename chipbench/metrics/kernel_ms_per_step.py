"""Layer: kernels. Device time per step of the Mosaic kernel of that name
(`pl.pallas_call(name=...)`): `.layer_norm_fwd`, `.layer_norm_bwd`,
`.flash_attention_short_fwd`, `.flash_attention_short_bwd`. Found by name,
so another kernel in the step does not disturb it."""
from chipbench import program_trace


def read(facts, name):
    tr = program_trace.load(__file__)
    if not facts.get("on_chip") or not tr or not any(tr["chips"]):
        return None
    secs = program_trace.kernel_seconds(tr["chips"]).get(
        program_trace.part(name))
    return 1e3 * secs / facts["steps"] if secs else None
