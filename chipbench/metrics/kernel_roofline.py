"""Layer: kernels. `<kernel>_roofline`: the least time the chip could take
over the kernel's calls of one step (chipbench/counts.py::floor_seconds of
the model's `kernel_work`: needed FLOPs over the peak or needed bytes over
the peak bandwidth, whichever is larger) as a share of the time the Mosaic
kernel of that name took in the trace. Every `<kernel>_roofline` entry
without a reader of its own is read here. Nothing where the kernel did not
run or the model counts no work for it; never clamped: a share over 100
means the count is wrong."""
from chipbench import counts, program_trace
from chipbench.manifest import ROOFLINE


def read(facts, name):
    kernel = name.split(".")[0][:-len(ROOFLINE)]
    calls = (facts.get("kernel_work") or {}).get(kernel)
    tr = program_trace.load(__file__)
    if not facts.get("on_chip") or not calls or not tr \
            or not any(tr["chips"]):
        return None
    secs = program_trace.kernel_seconds(tr["chips"]).get(kernel)
    if not secs:
        return None
    floor = counts.floor_seconds(calls, facts["device_kind"])
    return 100.0 * floor * facts["steps"] / secs
