"""Layer: kernels. Device time per step of the Pallas (Mosaic) kernels in
the train step: the fused LayerNorm, forward and backward, 30 of each.

A time, not a share of a roofline: XLA keeps these kernels' operands in
on-chip memory (the S(1) layouts in the trace), so the kernel moves its
bytes faster than HBM could (PERF.md section 6, PR 26) and an HBM roofline
reads over 100%. The table of peaks has no on-chip bandwidth to set it
against."""


def read(facts, name):
    tr = facts.get("trace")
    if facts["kind"] != "train" or not tr:
        return None
    secs = sum(v for k, v in tr["op_seconds"].items()
               if k.startswith("tpu_custom_call/"))
    if not secs:
        return None
    return 1e3 * secs / facts["steps"]
