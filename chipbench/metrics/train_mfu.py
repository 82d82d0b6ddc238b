"""Layer: model step. Model FLOPs per step x steps / (window x chips x
peak): the whole step's share of the chip's peak."""
from chipbench import counts


def read(facts, name):
    if facts["kind"] != "train" or not facts["on_chip"]:
        return None
    peak = counts.peak(facts["device_kind"])["flops"]
    return 100.0 * facts["step_flops"] * facts["steps"] / (
        facts["window_s"] * facts["chips"] * peak)
