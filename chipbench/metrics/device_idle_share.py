"""Layer: device. 1 - union of device-op intervals / traced window."""


def read(facts, name):
    tr = facts.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
