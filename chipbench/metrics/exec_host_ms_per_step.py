"""Layer: entry. Window wall per step minus traced device busy per step."""


def read(facts, name):
    tr = facts.get("trace")
    if facts["kind"] != "train" or not tr or not tr["busy_s"]:
        return None
    return 1e3 * (tr["window_s"] - tr["busy_s"]) / facts["steps"]
