"""Derives every end-to-end bound from the recorded runs, by the contract's rule.

The runs are the lines of chipbench/runs/*.jsonl: chip runs of the final
code, one result line each, with cell, seed and set ("1" and "2" are the
two full sets of the same seeds; lines with another `set` are traced or
earlier runs and are not read here). For each cell and metric the spread of
a set is the distance between the first and third quartile
(statistics.quantiles(values, n=4)) as a share of the median, and W is the
wider of the two sets' spreads in the widest cell. The rule:

    bound = 5 W, rounded to a thousandth, never under 1%, never over 10%

`setup_s` takes 0.1 as the contract says, the first (compiling) run of a
set left out of what is reported. Beside each bound this prints what the
check that admits it will read: it refuses a bound under 2 T as too tight
(T: the mean of the two sets' spreads, each set's run farthest from its
median left out) and one over 8 W, and over 1%, as too loose. Run it as

    python -m chipbench.bounds            # print the bounds and the spreads
    python -m chipbench.bounds --write    # and write them into BENCHMARK.json
"""
import glob
import json
import os
import statistics
import sys

FLOOR, CEILING, SETUP = 0.01, 0.1, 0.1


def load_runs(runs_dir):
    runs = []
    for path in sorted(glob.glob(os.path.join(runs_dir, "*.jsonl"))):
        with open(path) as f:
            runs += [json.loads(line) for line in f if line.strip()]
    return runs


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values):
    """The spread with the run farthest from the median left out: what the
    check reads for tightness (one far-off run does no harm, two do). A
    set of under five runs is read whole."""
    if len(values) < 5:
        return spread(values)
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])


def table(runs):
    """{metric: {cell: {set: [values]}}} of the untraced runs of the full
    sets, first run of each set included (only setup_s drops it)."""
    out = {}
    for r in runs:
        if r["trace"] or r["set"] not in ("1", "2") or not r["result"]:
            continue
        for name, m in r["result"]["metrics"].items():
            out.setdefault(name, {}).setdefault(r["cell"], {}).setdefault(
                r["set"], []).append(m["value"])
    return out


def derive(runs):
    """{metric: {"bound", "widest_spread", "trimmed_spread",
    "cells": {cell: {...}}}}"""
    out = {}
    for name, cells in table(runs).items():
        info, widest, trimmed = {}, 0.0, 0.0
        for cell, sets in cells.items():
            vals = {s: (v[1:] if name == "setup_s" else v)
                    for s, v in sets.items()}
            vals = {s: v for s, v in vals.items() if len(v) >= 3}
            spreads = {s: spread(v) for s, v in vals.items()}
            trims = [trimmed_spread(v) for v in vals.values()]
            info[cell] = {
                "spread": spreads,
                "median": {s: statistics.median(v) for s, v in vals.items()},
                "trimmed_spread": statistics.mean(trims) if trims else None}
            if spreads:
                widest = max(widest, max(spreads.values()))
                trimmed = max(trimmed, statistics.mean(trims))
        if name == "setup_s":
            bound = SETUP
        else:
            bound = min(CEILING, max(FLOOR, round(5 * widest, 3)))
        out[name] = {"bound": bound, "widest_spread": widest,
                     "trimmed_spread": trimmed, "cells": info}
    return out


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    derived = derive(load_runs(os.path.join(root, "chipbench", "runs")))
    for name, d in derived.items():
        print(f"{name}: bound {d['bound']} (W {d['widest_spread']:.5f}, "
              f"T {d['trimmed_spread']:.5f}: admitted from "
              f"{2 * d['trimmed_spread']:.5f} to "
              f"{max(FLOOR, 8 * d['widest_spread']):.5f})")
        for cell, c in d["cells"].items():
            print(f"    {cell}: spread {c['spread']} median {c['median']} "
                  f"trimmed spread, mean of the sets {c['trimmed_spread']}")
            meds = list(c["median"].values())
            if len(meds) == 2 and name != "setup_s":
                diff = abs(meds[1] - meds[0]) / meds[0]
                flag = "" if diff <= d["bound"] else "  OVER THE BOUND"
                print(f"        second set's median differs by {diff:.5f}"
                      f"{flag}")
    if "--write" in argv:
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            doc = json.load(f)
        for m in doc["end_to_end"]:
            if m["name"] in derived:
                m["bound"] = derived[m["name"]]["bound"]
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
