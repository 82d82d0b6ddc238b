"""Layer: model step. Device busy per step, from the trace."""


def read(facts, name):
    tr = facts.get("trace")
    if facts["kind"] != "train" or not tr or not tr["busy_s"]:
        return None
    return 1e3 * tr["busy_s"] / facts["steps"]
