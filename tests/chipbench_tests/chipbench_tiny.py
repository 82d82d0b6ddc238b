"""A copy of the benchmark at TransformerConfig.tiny() in a temporary root.

It adds files only -- tiny configurations, tiny traffic mixes, cells for
them -- beside copies of the benchmark's readers and drivers, and so shows
that a configuration, a traffic mix, a cell and a per-layer metric can each
be added without editing a file that is there.
"""
import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {"src_vocab": 128, "trg_vocab": 128, "max_len": 32, "d_model": 64,
        "d_inner": 128, "n_head": 4, "n_layer": 2}

LIMITS_TRAIN = {"loss_gap": 0.02, "grad_gap": 0.2, "delta_gap": 0.2}

def make_root(tmp, extra_metric=False):
    """Write the tiny benchmark under `tmp`; returns the root path."""
    tmp = str(tmp)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for sub in ("metrics", "entries"):
        shutil.copytree(os.path.join(REPO, "chipbench", sub),
                        os.path.join(tmp, "chipbench", sub))
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    doc = copy.deepcopy(real)
    doc["paths"] = ["chipbench"]
    for c in doc["configs"]:
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        cfg.update(TINY)
        cfg["limits"] = LIMITS_TRAIN
        json.dump(cfg, open(os.path.join(tmp, c["file"]), "w"))
    traffic = {
        "train_b128_t256": {"kind": "train_batches", "batch_rows": 8,
                            "src_len": 32, "trg_len": 32, "pool": 4,
                            "warm_steps": 1, "trace_s": 1,
                            "reference_block_rows": 4},
    }
    for name, t in traffic.items():
        json.dump(t, open(os.path.join(tmp, "chipbench", "traffic",
                                       name + ".json"), "w"))
    if extra_metric:
        # one more cell on one more traffic file, and one more metric with a
        # reader of its own: files and entries added, none edited
        t = dict(traffic["train_b128_t256"], batch_rows=4)
        json.dump(t, open(os.path.join(tmp, "chipbench", "traffic",
                                       "train_b4.json"), "w"))
        cfg = json.load(open(os.path.join(tmp, doc["configs"][0]["file"])))
        json.dump(cfg, open(os.path.join(tmp, "chipbench", "configs",
                                         "nmt_tiny_extra.json"), "w"))
        doc["configs"].append({
            "name": "nmt_tiny_extra", "source": doc["configs"][0]["source"],
            "file": "chipbench/configs/nmt_tiny_extra.json",
            "reduced": ["dropout"], "why": "added by the test"})
        doc["workloads"].append({
            "name": "extra_cell", "config": "nmt_tiny_extra",
            "traffic": "train_b4", "chips": 1, "why": "added by the test"})
        doc["end_to_end"][0]["workloads"].append("extra_cell")
        with open(os.path.join(tmp, "chipbench", "metrics",
                               "steps_in_window.py"), "w") as f:
            f.write("def read(facts, name):\n"
                    "    return float(facts['steps'])\n")
        doc["per_layer"].append({
            "name": "steps_in_window.extra", "unit": "count",
            "better": "higher", "source": "program_counter",
            "layer": "entry, tracer", "moves": "train_tokens_per_s",
            "workloads": ["extra_cell"]})
    json.dump(doc, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
