"""Tier-1 tests of the chip benchmark (chipbench/), all on the CPU.

Nothing here describes a TPU topology, at import or later. The cells' code
paths run at TransformerConfig.tiny() in a temporary root (chipbench_tiny.py); a line
printed there says `"platform": "cpu"` and claims no device metric.

No test here holds BENCHMARK.json to a list compared whole, to a position
in a list or to a count of cells: a later PR appends entries and adds
files, and what is checked is that what was there is still there.
"""
import io
import json
import os
import re
import statistics
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import bounds, correct, counts, loadgen, manifest  # noqa: E402
from chipbench import run as cb_run  # noqa: E402
from chipbench import trace as cb_trace  # noqa: E402

REPO = tiny.REPO
CELLS = tiny.cells() + ["extra_cell"]         # the last one the test adds
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(list(argv), root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tinybench"),
                          extra_metric=True)


# ------------------------------------------------------------ the manifest
def test_benchmark_json_has_exactly_the_contract_keys():
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["command"] == ["python", "-m", "chipbench.run"]
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert all(w["chips"] in (1, 4) for w in doc["workloads"])
    assert 1 <= doc["run_seconds"] <= 51


@pytest.mark.parametrize("n_cells,allowed", [
    (1, 1), (3, 1), (4, 1), (7, 1), (8, 2), (11, 2), (24, 6)])
def test_four_chip_cells_keep_to_their_quota(tmp_path, n_cells, allowed):
    """A quarter of the cells, rounded down, and one always may: the
    manifest admits `allowed` four-chip cells among `n_cells` and no more."""
    assert manifest.four_chip_quota(n_cells) == allowed
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    doc = json.load(open(path))
    first = doc["workloads"][0]
    doc["workloads"] = [dict(first, name=f"cell_{i}",
                             chips=4 if i < allowed else 1)
                        for i in range(n_cells)]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w["name"] for w in doc["workloads"]]
    json.dump(doc, open(path, "w"))
    manifest.Manifest(root).validate()
    if allowed < n_cells:
        doc["workloads"][allowed]["chips"] = 4
        json.dump(doc, open(path, "w"))
        with pytest.raises(manifest.ManifestError, match="four-chip"):
            manifest.Manifest(root).validate()


def test_manifest_cross_references_by_name():
    man = manifest.Manifest(REPO).validate()
    for m in man.doc["per_layer"]:
        for cell in m.get("workloads", man.cells):
            reported = {x["name"] for x in man.cell_end_to_end(cell)}
            assert m["moves"] in reported, (m["name"], cell)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in man.doc[group]:
            assert manifest.NAME.match(x["name"])
    for m in man.doc["end_to_end"] + man.doc["per_layer"]:
        assert manifest.UNIT.match(m["unit"])
    for c in man.doc["configs"]:
        cfg = man.config(c["name"])
        assert set(c["reduced"]) == set(cfg.get("reduced_why", {}))
        assert cfg["limits"], "a limit is set from readings, never missing"
    # every file under paths is named from the characters of a name and /
    for p in man.paths:
        for d, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_a_kernel_with_a_roofline_share_has_no_time_entry_beside_it():
    """A kernel's traced time is its floor (`kernel_work`) over its share
    of the roofline, so a cell that reports `<k>_roofline[.tag]` reports
    no `kernel_ms_per_step.<k>[.tag]`: the file holds 128 per-layer
    entries at most, and a repeat takes a place a new cell needs."""
    man = manifest.Manifest(REPO).validate()
    assert 1 <= len(man.doc["per_layer"]) <= 128
    for cell in man.cells:
        names = [m["name"].split(".") for m in man.cell_per_layer(cell)]
        timed = {n[1] for n in names if n[0] == "kernel_ms_per_step"}
        shared = {n[0][:-len("_roofline")] for n in names
                  if n[0].endswith("_roofline")}
        assert not timed & shared, (cell, sorted(timed & shared))


@pytest.mark.parametrize("breakage", ["moves", "unit", "cell"])
def test_manifest_refuses_a_broken_cross_reference(tmp_path, breakage):
    root = tiny.make_root(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    doc = json.load(open(path))
    if breakage == "moves":
        doc["per_layer"][0]["moves"] = "serve_tokens_per_s"
    elif breakage == "unit":
        doc["per_layer"][0]["unit"] = "tokens per s"
    else:
        doc["per_layer"][0]["workloads"] = ["no_such_cell"]
    json.dump(doc, open(path, "w"))
    with pytest.raises(manifest.ManifestError):
        manifest.Manifest(root).validate()


def test_a_cell_config_traffic_and_metric_are_added_as_files(tiny_root):
    man = manifest.Manifest(tiny_root).validate()
    assert "extra_cell" in man.cells
    rc, res = _run(tiny_root, "--workload", "extra_cell", "--seed", "9",
                   "--seconds", "1", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["steps_in_window.extra"]["value"] >= 1


# -------------------------------------------------------------- the counts
TINY = tiny.TINY


def _hand_forward(rows, Ts, Tt):
    d, di, L, V = 64, 128, 2, 128
    enc = Ts * (2 * d * 3 * d + 2 * d * d + 2 * 2 * d * di    # qkv, o, ffn
                + 2 * Ts * d + 2 * Ts * d)                     # scores, mix
    dec = Tt * (2 * d * 3 * d + 2 * d * d                      # self qkv, o
                + (2 * Tt * d + 2 * Tt * d) // 2               # causal half
                + 2 * d * d + 2 * d * d                        # cross q, o
                + 2 * Ts * d + 2 * Ts * d                      # cross attn
                + 2 * 2 * d * di)
    cross_kv = Ts * 2 * d * 2 * d
    return rows * (L * (enc + dec + cross_kv) + Tt * 2 * d * V)


@pytest.mark.parametrize("rows,Ts,Tt", [(1, 32, 32), (8, 32, 16), (3, 5, 7)])
def test_train_flops_against_a_hand_count(rows, Ts, Tt):
    assert counts.forward_flops(TINY, rows, Ts, Tt) == _hand_forward(
        rows, Ts, Tt)
    assert counts.train_step_flops(TINY, rows, Ts, Tt) == 3 * _hand_forward(
        rows, Ts, Tt)


def test_base_counts_and_peaks():
    base = json.load(open(os.path.join(
        REPO, "chipbench/configs/nmt_base_train_nodrop.json")))
    per_pair = counts.train_step_flops(base, 1, 256, 256) / 256
    assert 318e6 < per_pair < 319e6      # 323e6 less the causal half
    assert counts.peak("TPU v5 lite") == {"flops": 197e12,
                                          "bytes_per_s": 819e9, "hbm": 16e9}
    with pytest.raises(KeyError):
        counts.peak("TPU v9 imaginary")


@pytest.mark.parametrize("B,H,T,S,D,causal", [
    (2, 4, 8, 8, 16, False), (2, 4, 8, 8, 16, True), (3, 2, 5, 7, 8, False)])
def test_attention_kernel_work_against_a_hand_count(B, H, T, S, D, causal):
    scores = 2 * B * H * T * S * D               # q k^T: T x S dots of D
    mix = 2 * B * H * T * S * D                  # p v
    fwd = (scores + mix) // (2 if causal else 1)
    q = out = B * T * H * D * 2                  # bf16
    k = v = B * S * H * D * 2
    assert counts.attention_work(B, H, T, S, D, 2, causal, False) == (
        fwd, q + k + v + out)
    # backward: the scores again, dp = dout v^T, dv = p^T dout, dq = ds k,
    # dk = ds^T q: five products where the forward has two
    assert counts.attention_work(B, H, T, S, D, 2, causal, True) == (
        fwd * 5 // 2, (q + k + v + out) + (out + q + k + v))


def test_attention_floors_of_the_base_cell():
    base = json.load(open(os.path.join(
        REPO, "chipbench/configs/nmt_base_train_nodrop.json")))
    t = _traffic("train_b128_t256")
    fwd = counts.nmt_attention_calls(base, t, backward=False)
    bwd = counts.nmt_attention_calls(base, t, backward=True)
    # 6 layers x (encoder self, causal decoder self, cross)
    assert [n for _, _, n in fwd] == [6, 6, 6] == [n for _, _, n in bwd]
    one = 4 * 128 * 8 * 256 * 256 * 64
    assert [f for f, _, _ in fwd] == [one, one // 2, one]
    assert {b for _, b, _ in fwd} == {4 * 128 * 256 * 512 * 2}
    assert {b for _, b, _ in bwd} == {8 * 128 * 256 * 512 * 2}
    # at this size every call is bound by HBM, causal or not: 0.164 ms
    # forward and 0.328 ms backward an attention, 18 of each a step
    per_call = 4 * 128 * 256 * 512 * 2 / 819e9
    assert per_call > one / 197e12
    assert counts.floor_seconds(fwd, "TPU v5 lite") == pytest.approx(
        18 * per_call)
    assert counts.floor_seconds(bwd, "TPU v5 lite") == pytest.approx(
        36 * per_call)
    # a call the MXU bounds is counted by its FLOPs
    assert counts.floor_seconds([(197e12, 1.0, 2)], "TPU v5 lite") == 2.0
    with pytest.raises(KeyError):
        counts.floor_seconds(fwd, "TPU v9 imaginary")


# ------------------------------------------------------ the trace reduction
def test_busy_union_idle_share_and_gaps():
    ops = [(0.0, 1.0), (0.5, 1.0), (3.0, 1.0), (3.2, 0.1)]   # start, duration
    assert cb_trace.busy_union(ops) == pytest.approx(2.5)
    assert cb_trace.idle_gaps(ops) == [(1.5, 1.5)]
    assert cb_trace.idle_gaps(ops, 0.0, 5.0) == [(1.5, 1.5), (4.0, 1.0)]
    spans = [("exe.run", 0.0, 2.0), ("loop", 1.4, 0.2), ("exe.run", 2.2, 2.0)]
    named = dict(cb_trace.name_gaps([(1.5, 1.5), (4.0, 1.0), (9.0, 1.0)],
                                    spans))
    # every instant goes to the innermost span open then: [1.5, 1.6) to
    # `loop`, [2.0, 2.2) and what follows 4.2 to none
    assert named == pytest.approx({"exe.run": 0.4 + 0.8 + 0.2, "loop": 0.1,
                                   "unattributed": 0.2 + 0.8 + 1.0})


def test_idle_gaps_are_named_by_the_programs_spans_too():
    """`pt/` spans are read beside `cb/`, so a gap is split among what the
    program says it was doing and not put down to `exe.run` whole."""
    assert cb_trace.span_name("cb/exe.run") == "exe.run"
    assert cb_trace.span_name("pt/executor.fetch_readback") == \
        "executor.fetch_readback"
    assert cb_trace.span_name("$profiler.py:91 trace") is None
    ops = [("fusion.1", 1.0, 1.0), ("fusion.1", 3.0, 1.0)]
    raw = {"chips": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
           "spans": [("window", 1.0, 3.0), ("exe.run", 1.0, 1.5),
                     ("executor.run", 1.1, 1.3),
                     ("executor.fetch_readback", 1.2, 1.0),
                     ("executor.release", 2.2, 0.2),
                     ("exe.run", 2.6, 1.4), ("executor.run", 2.7, 1.2),
                     ("executor.feed_put", 2.7, 0.2)]}
    gaps = dict(map(tuple, cb_trace.reduce(raw, 3.0)["breakdown"][
        "idle_gaps"]))
    assert gaps == pytest.approx({
        "executor.fetch_readback": 0.2, "executor.release": 0.2,
        "exe.run": 0.1 + 0.1, "unattributed": 0.1,
        "executor.feed_put": 0.2, "executor.run": 0.1})


def test_reduce_clips_to_the_window_and_averages_chips():
    def chip(name, shift):
        return {"name": name,
                "ops": [("fusion.1", 0.5 + shift, 0.5),        # before window
                        ("fusion.2", 1.0 + shift, 1.0),
                        ("all-reduce.3", 2.5 + shift, 0.5)],
                "modules": [("jit_step(1)", 1.0 + shift, 2.0)]}
    raw = {"chips": [chip("/device:TPU:0", 0.0), chip("/device:TPU:1", 0.1)],
           "spans": [("window", 1.0, 4.0), ("exe.run", 1.0, 1.2)]}
    red = cb_trace.reduce(raw, window_s=99.0)
    assert red["window_s"] == 4.0 and red["chips"] == 2
    assert red["busy_s"] == pytest.approx(1.5)
    assert red["op_seconds"]["fusion.2"] == pytest.approx(1.0)
    assert "fusion.1" not in red["op_seconds"]
    assert red["module_seconds"]["jit_step(1)"] == pytest.approx(2.0)
    assert red["breakdown"]["device_ops"][0][0] == "fusion"
    gaps = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    # of the 0.5 s gap, the 0.2 s inside the span; the rest is no span's
    assert gaps["exe.run"] == pytest.approx(0.2)
    assert gaps["unattributed"] == pytest.approx(0.3 + 2.0)
    idle = manifest.Manifest(REPO).reader("device_idle_share").read(
        {"trace": red}, "device_idle_share")
    assert idle == pytest.approx(100 * (1 - 1.5 / 4.0))


@pytest.mark.parametrize("name", [
    "train_device_step_ms", "device_idle_share", "train_mfu"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    man = manifest.Manifest(REPO)
    empty = cb_trace.reduce({"chips": [], "spans": []}, 1.0)
    facts = {"kind": "train", "trace": empty, "steps": 3, "on_chip": False,
             "tokens": 0}
    assert man.reader(name).read(facts, name) is None


def test_readers_on_a_small_reduced_trace():
    """What each trace reader makes of 3 steps in a 1 s window on a v5e."""
    man = manifest.Manifest(REPO)
    facts = {"kind": "train", "steps": 3, "on_chip": True, "chips": 1,
             "window_s": 1.0, "device_kind": "TPU v5 lite",
             "step_flops": 197e12 / 10, "memory_peak_bytes": 5e9,
             "compiles_in_window": 0,
             "trace": {"busy_s": 0.9, "window_s": 1.0, "op_seconds": {
                 "fusion.2": 0.9}}}
    read = lambda n: man.reader(n).read(facts, n)            # noqa: E731
    assert read("train_device_step_ms") == pytest.approx(300.0)
    assert read("device_idle_share") == pytest.approx(10.0)
    assert read("train_mfu") == pytest.approx(30.0)
    assert read("peak_hbm_bytes") == 5e9
    assert read("compiles_in_window") == 0.0


# ------------------------------------------------------- the load generator
def _traffic(name):
    return json.load(open(os.path.join(REPO, "chipbench/traffic",
                                       name + ".json")))


def test_train_batches_differ_row_by_row_and_follow_the_seed():
    t = _traffic("train_b128_t256")
    cfg = {"src_vocab": 10000, "trg_vocab": 10000}
    a = loadgen.make_train_batches(t, cfg, 2**31 + 12345)
    b = loadgen.make_train_batches(t, cfg, 2**31 + 12345)
    c = loadgen.make_train_batches(t, cfg, 3)
    assert not (a[0]["src"] == c[0]["src"]).all()
    assert len(a) == 8 and a[0]["src"].shape == (128, 256)
    assert all((x["src"] == y["src"]).all() for x, y in zip(a, b))
    rows = np.concatenate([x["src"] for x in a])
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert (a[0]["trg"][:, 1:] == a[0]["label"][:, :-1]).all()


# ---------------------------------------------------------------- the bounds
def test_bounds_rule_on_a_small_table():
    def rec(cell, s, seed, v, setup):
        return {"cell": cell, "set": s, "seed": seed, "trace": 0,
                "result": {"metrics": {"m": {"value": v},
                                       "setup_s": {"value": setup}}}}
    vals = [100.0, 100.2, 100.4, 100.6, 100.8, 101.0]
    runs = [rec("a", s, i, v, 30 + i) for s in ("1", "2")
            for i, v in enumerate(vals)]
    runs += [rec("a", "trial", 0, 500.0, 99.0)]          # not of a full set
    d = bounds.derive(runs)
    q1, _, q3 = statistics.quantiles(vals, n=4)
    wide = (q3 - q1) / statistics.median(vals)
    assert d["m"]["widest_spread"] == pytest.approx(wide)
    # the run farthest from the median (100.0 or 101.0) left out
    t1, _, t3 = statistics.quantiles(vals[1:], n=4)
    trim = (t3 - t1) / statistics.median(vals[1:])
    assert d["m"]["trimmed_spread"] == pytest.approx(trim, rel=1e-2)
    trim = d["m"]["trimmed_spread"]
    assert d["m"]["bound"] == round(5 * wide, 3)          # the contract's rule
    assert 2 * trim < d["m"]["bound"] < 8 * wide
    assert d["setup_s"]["bound"] == 0.1
    tight = [rec("a", "1", i, 100.0 + 0.001 * i, 30) for i in range(6)]
    assert bounds.derive(tight)["m"]["bound"] == 0.01     # never under 1%


def test_benchmark_json_holds_exactly_the_derived_bounds():
    runs = bounds.load_runs(os.path.join(REPO, "chipbench", "runs"))
    derived = bounds.derive(runs)
    doc = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for m in doc["end_to_end"]:
        assert m["name"] in derived, f"no recorded run reports {m['name']}"
        assert m["bound"] == derived[m["name"]]["bound"], m["name"]
        # PR 22's refusal: not under the difference between the sets' medians
        for cell, c in derived[m["name"]]["cells"].items():
            meds = list(c["median"].values())
            if len(meds) == 2 and m["name"] != "setup_s":
                assert abs(meds[1] - meds[0]) / meds[0] <= m["bound"], cell
        # PR 24's refusal: not over eight times the widest spread (or 1%);
        # PR 22's other face: not under twice the trimmed spread
        if m["name"] != "setup_s":
            assert m["bound"] <= max(0.01,
                                     8 * derived[m["name"]]["widest_spread"])
            assert m["bound"] >= 2 * derived[m["name"]]["trimmed_spread"]


# ------------------------------------------- each cell's code path, on the CPU
@pytest.mark.parametrize("cell", CELLS)
def test_cpu_run_of_each_cell_prints_the_contract_line(tiny_root, cell):
    rc, res = _run(tiny_root, "--workload", cell, "--seed",
                   str(2**31 + 77), "--seconds", "1.5", "--trace", "0")
    assert rc == 0
    assert set(res) == RESULT_KEYS | {"checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    man = manifest.Manifest(tiny_root)
    assert set(res["metrics"]) == {m["name"]
                                   for m in man.cell_end_to_end(cell)}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", ["nmt_train_1chip"])
def test_cpu_traced_run_claims_no_device_metric(tiny_root, cell):
    rc, res = _run(tiny_root, "--workload", cell, "--seed", "5",
                   "--seconds", "1", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
    man = manifest.Manifest(tiny_root)
    device_metrics = {m["name"] for m in man.cell_per_layer(cell)
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(res["metrics"])
    assert "train_mfu" not in res["metrics"]
    assert any(n.startswith("compiles_in_window") for n in res["metrics"])


def test_no_accelerator_and_no_explicit_cpu_means_no_result(monkeypatch,
                                                            tiny_root):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rc, res = _run(tiny_root, "--workload", "nmt_train_1chip", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert rc != 0 and res is None


# ------------------------------------- the comparison that decides `correct`
def test_train_numbers_by_the_worst_leaf():
    ref = {"loss": [5.0, 4.0, 3.0],
           "grad_norm": {"a": 1.0, "b": 2.0, "c": 1e-6},
           "delta_norm": {"a": 0.1, "b": 0.2, "c": 0.5}}
    prog = {"loss": [5.05, 4.0, 3.0],
            "grad_norm": {"a": 1.1, "b": 2.0, "c": 0.5},
            "delta_norm": {"a": 0.1, "b": 0.25, "c": 0.0}}
    n = correct.train_numbers(prog, ref)
    assert n["loss_gap"] == pytest.approx(0.01)
    # c's tiny gradient is measured against the median leaf's norm (1.0)
    assert n["grad_gap"] == pytest.approx(0.5, rel=1e-4)
    # c is left out of the change: its reference gradient is nought
    assert n["delta_gap"] == pytest.approx(0.05 / 0.2)
    rows, ok = correct.judge(n, {"loss_gap": 0.02, "grad_gap": 0.4})
    assert not ok and [r[3] for r in rows] == [True, False, False]


# the faults a one-chip training cell can have (no exchange, no token)
FAULTS = [("nmt_train_1chip", "state_unchanged"),
          ("nmt_train_1chip", "half_batch")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_a_broken_timed_path_comes_out_not_correct(tiny_root, monkeypatch,
                                                   cell, fault):
    """The rest of a run driven with the timed path broken underneath."""
    Trainer = manifest.Manifest(tiny_root).driver("train").Trainer
    real = Trainer.step
    if fault == "state_unchanged":
        monkeypatch.setattr(Trainer, "step", lambda self, feed: 5.0)
    else:
        def step(self, feed):
            half = {k: v[:len(v) // 2] for k, v in feed.items()}
            return real(self, half)
        monkeypatch.setattr(Trainer, "step", step)
    rc, res = _run(tiny_root, "--workload", cell, "--seed", "31",
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


# ---------------------------------------------------------------- the controls
# The reference put in the program's place, one precision below the one the
# configuration states, has to come out as not correct. On the chip that was
# read at the cell's own size (PERF.md section 2); here at a size a test run
# can hold. At TransformerConfig.tiny() int8 and bfloat16 read alike (two
# layers of 64 wide round too little to tell them apart), so this runs at a
# middle size, with the limit set between the two readings at THAT size by
# the same rule: bfloat16 read 0.008-0.009 and int8 0.037-0.064 on `grad_gap`.
MID = {"src_vocab": 2000, "trg_vocab": 2000, "max_len": 64, "d_model": 256,
       "d_inner": 1024, "n_head": 4, "n_layer": 3, "label_smooth_eps": 0.1,
       "dropout": 0.0}
MID_LIMITS = {"grad_gap": 0.02}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_training_control_int8_comes_out_not_correct(seed):
    from chipbench import weights
    from chipbench.reference import nmt
    opt = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
    t = {"batch_rows": 4, "src_len": 256, "trg_len": 256, "pool": 1}
    batches = loadgen.make_train_batches(t, MID, seed)
    params = weights.make_params(MID, seed, "bfloat16")
    ref = nmt.train_steps(params, MID, batches, opt, "float32", 4)
    stated = nmt.train_steps(params, MID, batches, opt, "bfloat16", 4)
    control = nmt.train_steps(params, MID, batches, opt, "int8", 4)
    limits = {"loss_gap": 1.0, "delta_gap": 1.0,
              "grad_gap": MID_LIMITS["grad_gap"]}
    assert correct.judge(correct.train_numbers(stated, ref), limits)[1]
    rows, ok = correct.judge(correct.train_numbers(control, ref), limits)
    assert not ok and not dict((r[0], r[3]) for r in rows)["grad_gap"]
