"""Loads BENCHMARK.json and the data files it names, by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under one of `paths`:

    <path>/configs/<config>.json     named by the configuration's `file`
    <path>/traffic/<traffic>.json    found by the cell's `traffic`
    <path>/metrics/<base>.py         found by the metric's name up to its
                                     first dot (`x.sat` and `x.steady`
                                     share the reader `x.py`); a name
                                     `<kernel>_roofline` with no file of
                                     its own shares `kernel_roofline.py`
    <path>/entries/<driver>.py       found by the configuration's `driver`
    <path>/models/<model>.py         found by the configuration's `model`:
                                     what a driver needs to know of the
                                     model it trains (entries/train.py)

A later PR adds files and entries to BENCHMARK.json; it edits none.
"""
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
ROOFLINE = "_roofline"      # `<kernel>_roofline`: a kernel's share of its own


class ManifestError(ValueError):
    pass


def default_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _find(root, paths, sub, filename):
    for p in paths:
        cand = os.path.join(root, p, sub, filename)
        if os.path.isfile(cand):
            return cand
    raise ManifestError(f"no {sub}/{filename} under any of {paths}")


def _json(path):
    with open(path) as f:
        return json.load(f)


_MODULES = {}


def load_module(path):
    """The reader or driver at `path`, loaded once per process."""
    path = os.path.abspath(path)
    if path not in _MODULES:
        name = "chipbench_dyn_" + re.sub(r"\W", "_", path)
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def four_chip_quota(n_cells):
    """How many cells may ask for four chips: a quarter of the cells,
    rounded down, and one always may (the contract's rule)."""
    return max(1, n_cells // 4)


class Manifest:
    def __init__(self, root=None):
        self.root = root or default_root()
        self.doc = _json(os.path.join(self.root, "BENCHMARK.json"))
        self.paths = self.doc["paths"]
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.doc["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.doc["per_layer"]}

    # ---- what one cell reports
    def _reports(self, metric, cell):
        wl = metric.get("workloads")
        return wl is None or cell in wl

    def cell_end_to_end(self, cell):
        return [m for m in self.doc["end_to_end"] if self._reports(m, cell)]

    def cell_per_layer(self, cell):
        e2e = {m["name"] for m in self.cell_end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if self._reports(m, cell) and m["moves"] in e2e]

    # ---- the files behind the names
    def config(self, name):
        entry = self.configs[name]
        return _json(os.path.join(self.root, entry["file"]))

    def traffic(self, name):
        return _json(_find(self.root, self.paths, "traffic", name + ".json"))

    def reader(self, metric_name):
        base = metric_name.split(".")[0]
        try:
            path = _find(self.root, self.paths, "metrics", base + ".py")
        except ManifestError:
            if not base.endswith(ROOFLINE):
                raise
            # every kernel's share of its roofline is read the same way
            path = _find(self.root, self.paths, "metrics",
                         "kernel_roofline.py")
        return load_module(path)

    def driver(self, name):
        return load_module(
            _find(self.root, self.paths, "entries", name + ".py"))

    def model(self, name):
        return load_module(
            _find(self.root, self.paths, "models", name + ".py"))

    # ---- cross-references, as the tests and every run check them
    def validate(self):
        doc = self.doc
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            names = [x["name"] for x in doc[group]]
            if len(set(names)) != len(names):
                raise ManifestError(f"duplicate name in {group}")
            for n in names:
                if not NAME.match(n):
                    raise ManifestError(f"bad name {n!r} in {group}")
        if "setup_s" not in self.end_to_end:
            raise ManifestError("no setup_s among end_to_end")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if not UNIT.match(m["unit"]):
                raise ManifestError(f"bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better")
            if m["source"] not in SOURCES:
                raise ManifestError(f"{m['name']}: source")
            for w in m.get("workloads", ()):
                if w not in self.cells:
                    raise ManifestError(f"{m['name']}: no cell {w!r}")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: end-to-end source")
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError(f"{m['name']}: bound {m['bound']}")
        for m in doc["per_layer"]:
            if m["moves"] not in self.end_to_end:
                raise ManifestError(f"{m['name']} moves {m['moves']!r}")
            for cell in m.get("workloads", ()):
                if m["moves"] not in {x["name"]
                                      for x in self.cell_end_to_end(cell)}:
                    raise ManifestError(
                        f"{m['name']}: cell {cell} does not report "
                        f"{m['moves']}")
            self.reader(m["name"])
        used = set()
        for w in doc["workloads"]:
            if w["config"] not in self.configs:
                raise ManifestError(f"{w['name']}: config {w['config']!r}")
            used.add(w["config"])
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips")
            self.traffic(w["traffic"])
            if len(self.cell_end_to_end(w["name"])) < 2:
                raise ManifestError(f"{w['name']}: needs setup_s and one "
                                    "more end-to-end metric")
            if not self.cell_per_layer(w["name"]):
                raise ManifestError(f"{w['name']}: no per-layer metric")
        four = [w["name"] for w in doc["workloads"] if w["chips"] == 4]
        if len(four) > four_chip_quota(len(doc["workloads"])):
            raise ManifestError(f"too many four-chip cells: {four}")
        if used != set(self.configs):
            raise ManifestError("a configuration is used by no cell")
        for c in doc["configs"]:
            cfg = self.config(c["name"])
            if not any(c["file"].startswith(p + "/") for p in self.paths):
                raise ManifestError(f"{c['name']}: file outside paths")
            self.driver(cfg["driver"])
            if "model" in cfg:
                self.model(cfg["model"])
        return self
