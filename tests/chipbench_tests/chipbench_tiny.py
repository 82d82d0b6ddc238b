"""A copy of the benchmark at tiny sizes in a temporary root.

It adds files only -- tiny configurations, tiny traffic mixes, cells for
them -- beside copies of the benchmark's readers, drivers and models, and
so shows that a configuration, a traffic mix, a cell, a per-layer metric
and a second model can each be added without editing a file that is there.

What is shrunk is data too: `tiny/configs/<configuration>.json` holds the
keys that replace the real file's (sizes, limits) and
`tiny/traffic/<traffic>.json` the tiny traffic file. A cell of
BENCHMARK.json whose configuration or traffic has no such file is left out
of the tiny root, so a later PR's cell breaks no test here; with the two
files added it is run by every test that is parametrised over `cells()`.
"""
import copy
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SECOND = os.path.join(HERE, "second_model")


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _dump(obj, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(obj, f)


TINY = _json(HERE, "tiny", "configs", "nmt_base_train_nodrop.json")


def _tiny(kind, name):
    path = os.path.join(HERE, "tiny", kind, name + ".json")
    return _json(path) if os.path.isfile(path) else None


def cells():
    """The cells of BENCHMARK.json that have a tiny configuration and a
    tiny traffic file, in its order."""
    real = _json(REPO, "BENCHMARK.json")
    return [w["name"] for w in real["workloads"]
            if _tiny("configs", w["config"]) is not None
            and _tiny("traffic", w["traffic"]) is not None]


def _by_name(entries, name):
    return next(x for x in entries if x["name"] == name)


def make_root(tmp, extra_metric=False, second_model=False):
    """Write the tiny benchmark under `tmp`; returns the root path."""
    tmp = str(tmp)
    real = _json(REPO, "BENCHMARK.json")
    for sub in ("metrics", "entries", "models"):
        shutil.copytree(os.path.join(REPO, "chipbench", sub),
                        os.path.join(tmp, "chipbench", sub))
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    doc = copy.deepcopy(real)
    doc["paths"] = ["chipbench"]
    kept = set(cells())
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] in kept]
    used = {w["config"] for w in doc["workloads"]}
    doc["configs"] = [c for c in doc["configs"] if c["name"] in used]
    for group in ("end_to_end", "per_layer"):
        for m in doc[group]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"] if w in kept]
        doc[group] = [m for m in doc[group] if m.get("workloads", True)]
    for c in doc["configs"]:
        cfg = _json(REPO, c["file"])
        cfg.update(_tiny("configs", c["name"]))
        c["file"] = "chipbench/configs/" + os.path.basename(c["file"])
        _dump(cfg, tmp, c["file"])
    for w in doc["workloads"]:
        _dump(_tiny("traffic", w["traffic"]), tmp, "chipbench", "traffic",
              w["traffic"] + ".json")
    rate = _by_name(doc["end_to_end"], "train_tokens_per_s")
    if extra_metric:
        # one more cell on one more traffic file, and one more metric with a
        # reader of its own: files and entries added, none edited
        first = _by_name(doc["configs"], "nmt_base_train_nodrop")
        _dump(dict(_tiny("traffic", "train_b128_t256"), batch_rows=4),
              tmp, "chipbench", "traffic", "train_b4.json")
        _dump(_json(tmp, first["file"]),
              tmp, "chipbench", "configs", "nmt_tiny_extra.json")
        doc["configs"].append({
            "name": "nmt_tiny_extra", "source": first["source"],
            "file": "chipbench/configs/nmt_tiny_extra.json",
            "reduced": ["dropout"], "why": "added by the test"})
        doc["workloads"].append({
            "name": "extra_cell", "config": "nmt_tiny_extra",
            "traffic": "train_b4", "chips": 1, "why": "added by the test"})
        rate["workloads"].append("extra_cell")
        with open(os.path.join(tmp, "chipbench", "metrics",
                               "steps_in_window.py"), "w") as f:
            f.write("def read(facts, name):\n"
                    "    return float(facts['steps'])\n")
        doc["per_layer"].append({
            "name": "steps_in_window.extra", "unit": "count",
            "better": "higher", "source": "program_counter",
            "layer": "entry, tracer", "moves": "train_tokens_per_s",
            "workloads": ["extra_cell"]})
    if second_model:
        # the rehearsal of a model_config PR: a model the train driver was
        # not written for, as three files and appended entries
        add = _json(SECOND, "entries.json")
        for sub, name in (("models", "nextid.py"),
                          ("configs", "nextid_small.json"),
                          ("traffic", "ids_b8_t16.json")):
            shutil.copy(os.path.join(SECOND, sub, name),
                        os.path.join(tmp, "chipbench", sub, name))
        doc["configs"].append(add["config"])
        doc["workloads"].append(add["workload"])
        rate["workloads"].append(add["workload"]["name"])
        doc["per_layer"] += add["per_layer"]
    _dump(doc, tmp, "BENCHMARK.json")
    return tmp
