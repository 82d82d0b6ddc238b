"""The comparison that decides `correct`: numbers, each beside its limit.

Every number is a gap between what the timed path produced and what the
plain reference gives for the same inputs. The limits are data: each
configuration's file holds them under `limits`, and PERF.md gives the
readings each was set from.
"""
import math
import statistics


def train_numbers(prog, ref):
    """prog/ref: {"loss": [l1, l2, l3], "grad_norm": {leaf: n},
    "delta_norm": {leaf: n}} -> {"loss_gap", "grad_gap", "delta_gap"}.

    A leaf's gap is |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    under Adam by round-off alone and are left out of `delta_gap`."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    g = ref["grad_norm"]
    med_g = statistics.median(g.values())
    grad_gap = max(abs(prog["grad_norm"][k] - g[k]) / max(g[k], med_g)
                   for k in g)
    moved = [k for k in g if g[k] >= 1e-3 * med_g]
    d = ref["delta_norm"]
    med_d = statistics.median(d[k] for k in moved)
    delta_gap = max(abs(prog["delta_norm"][k] - d[k]) / max(d[k], med_d)
                    for k in moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap}


def judge(numbers, limits):
    """[(name, value, limit, ok)] and the verdict. A number with no limit
    in the configuration's file fails: a limit is never guessed here."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        rows.append((name, value, limit, good))
        ok = ok and good
    return rows, ok
