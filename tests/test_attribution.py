"""tpuscope — runtime performance attribution (telemetry/attribution.py,
telemetry/slo.py) and its surfaces: histogram quantiles, the MFU /
goodput gauges (pinned against bench.py's offline formula), the step a
deferred read-back counts to under async_steps (the executor's tags and
`fluid.profiler`'s per-step rows), the recompile explainer, the declarative SLO engine, the BENCH_history
regression gate, per-request serving correlation ids, and the
`tpustat --slo --selftest` CI wiring.
"""
import json
import os
import subprocess
import sys
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import telemetry as tm
from paddle_tpu.telemetry import attribution as attr
from paddle_tpu.telemetry import registry as treg
from paddle_tpu.telemetry import slo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Start disabled/empty, leave nothing behind (the bench-contract
    fast-path test asserts an empty global registry). Attribution's
    per-ckey FLOPs cache and AOT probe reset too."""
    tm.disable()
    tm.reset()
    attr._reset_for_tests()
    yield
    tm.disable()
    tm.reset()
    attr._reset_for_tests()


def _tiny_train_program(width=16):
    x = layers.data("x", shape=[width])
    y = layers.data("y", shape=[1])
    h = layers.fc(x, size=8, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    pt.optimizer.SGD(0.1).minimize(loss)
    return loss


def _feed(batch, width=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(batch, width).astype("float32"),
            "y": rng.rand(batch, 1).astype("float32")}


# ------------------------------------------------- histogram quantiles

def test_histogram_quantiles_interpolate():
    h = tm.histogram("q.h")
    for _ in range(98):
        h.observe(0.0008)                  # (0.0005, 0.001] bucket
    h.observe(0.2)                         # (0.1, 0.25]
    h.observe(2.0)                         # (1.0, 2.5]
    v = h.to_value()
    assert v["count"] == 100
    assert 0.0005 < v["p50"] <= 0.001
    assert 0.1 < v["p99"] <= 2.5
    # module-level helper reads the dict form (what snapshots carry)
    assert treg.quantile_from_buckets(v, 0.5) == v["p50"]
    assert treg.quantile_from_buckets({"count": 0}, 0.5) is None
    # p0/p100 clamp to the observed min/max, not bucket edges
    assert h.quantile(0.0) == pytest.approx(v["min"])
    assert h.quantile(1.0) == pytest.approx(v["max"])


def test_quantiles_in_prometheus_text():
    tm.enable()
    tm.histogram("q.lat_seconds").observe(0.01)
    text = tm.prometheus_text()
    assert "q_lat_seconds_p50" in text
    assert "q_lat_seconds_p99" in text


# ------------------------------------------------------- SLO rule engine

def test_parse_rule_forms():
    r = slo.parse_rule("perf.mfu > 0.3")
    assert (r.metric, r.stat, r.op, r.threshold) == \
        ("perf.mfu", "value", ">", 0.3)
    r = slo.parse_rule("executor.step_seconds.p99 < 0.25")
    assert (r.metric, r.stat) == ("executor.step_seconds", "p99")
    # the step_ms alias reads the seconds histogram in milliseconds
    r = slo.parse_rule("step_ms.p99 < 250")
    assert (r.metric, r.scale) == ("executor.step_seconds", 1e3)
    with pytest.raises(ValueError):
        slo.parse_rule("no operator here")
    with pytest.raises(ValueError):
        slo.parse_rule("metric < not_a_number")


def test_evaluate_pass_fail_skip_strict():
    snap = {"perf.mfu": 0.42,
            "executor.step_seconds": {"count": 4, "sum": 0.4,
                                      "mean": 0.1, "min": 0.09,
                                      "max": 0.12,
                                      "buckets": {"0.1": 3, "0.25": 1}}}
    rep = slo.evaluate(["perf.mfu > 0.3",          # pass
                        "step_ms.p99 < 100",       # fail: ~120ms
                        "serving.queue_depth < 5"  # skip: absent
                        ], snap=snap)
    assert not rep.ok and len(rep.violations) == 1
    assert len(rep.skipped) == 1
    # p99 interpolates into the (0.1, 0.25] bucket, clamped by the
    # observed max (0.12s) -> 120ms
    assert rep.violations[0].observed == pytest.approx(120.0)
    assert "FAIL step_ms.p99" in str(rep)
    d = rep.to_dict()
    assert d["ok"] is False and d["violations"] == 1
    # strict converts the skip into a violation
    strict = slo.evaluate(["serving.queue_depth < 5"], snap=snap,
                          strict=True)
    assert not strict.ok


def test_evaluate_fleet_unwraps_merged_kinds():
    report = {"merged": {"perf.mfu": {"kind": "gauge", "value": 0.5}}}
    rep = slo.evaluate_fleet(["perf.mfu > 0.4"], report)
    assert rep.ok and rep.results[0].observed == 0.5


# --------------------------------------------------- regression gate

def test_check_regression_directional():
    clean = [100.0, 101.0, 99.0, 100.5, 100.0, 99.5, 100.2, 100.1]
    assert not slo.check_regression(clean, 100.3,
                                    direction="higher")["regressed"]
    assert slo.check_regression(clean, 10.0,
                                direction="higher")["regressed"]
    # latency: same numbers, regression is UP
    assert slo.check_regression(clean, 1000.0,
                                direction="lower")["regressed"]
    assert not slo.check_regression(clean, 100.3,
                                    direction="lower")["regressed"]
    # small-sample ratio fallback (n < 4): 1.5x the median
    assert slo.check_regression([100.0, 100.0], 40.0,
                                direction="higher")["regressed"]
    assert not slo.check_regression([100.0, 100.0], 80.0,
                                    direction="higher")["regressed"]


def test_metric_direction_heuristics():
    assert slo.metric_direction("mnist_mlp_steps_per_sec") == "higher"
    assert slo.metric_direction("mfu") == "higher"
    assert slo.metric_direction("deepfm_step_ms", "ms") == "lower"
    assert slo.metric_direction("resnet50_infer_latency_ms") == "lower"


def test_history_gate_flags_injected_regression(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    recs = [{"metric": "deepfm_step_ms", "value": 10.0 + 0.1 * i,
             "unit": "ms", "platform": "cpu"} for i in range(8)]
    slo.append_history(path, recs)
    clean = slo.history_gate(slo.load_history(path), platform="cpu")
    assert clean["ok"] and clean["checked"] == 1
    # inject a 10x step-time regression as the newest record
    slo.append_history(path, [{"metric": "deepfm_step_ms",
                               "value": 100.0, "unit": "ms",
                               "platform": "cpu"}])
    gate = slo.history_gate(slo.load_history(path), platform="cpu")
    assert not gate["ok"]
    assert gate["regressions"][0]["metric"] == "deepfm_step_ms"
    # other-platform records are excluded from the cpu baseline
    assert slo.history_gate(slo.load_history(path),
                            platform="tpu")["checked"] == 0


def test_load_history_skips_garbage(tmp_path):
    path = tmp_path / "h.jsonl"
    path.write_text('{"metric": "m", "value": 1.0}\n'
                    'not json\n'
                    '{"no_metric": true}\n'
                    '{"metric": "m", "value": 2.0}\n')
    recs = slo.load_history(str(path))
    assert [r["value"] for r in recs] == [1.0, 2.0]


# --------------------------------------------- runtime MFU / goodput

def test_runtime_mfu_is_the_offline_formula(monkeypatch):
    """The acceptance pin: the live perf.mfu gauge is the offline
    formula bench.py uses (flops * steps / elapsed / peak, compile
    excluded). The window reads the module's clock once a step; here
    that clock is a counter (0.25 s a reading), so the formula is held
    exactly and no load on the host can move it."""
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
    ticks = iter(range(1, 10 ** 6))
    monkeypatch.setattr(attr, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.25 * next(ticks)))
    loss = _tiny_train_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.reset()
    feed = _feed(8)
    # compile step: captures FLOPs via cost_analysis and re-anchors the
    # window, so compile time is excluded
    exe.run(feed=feed, fetch_list=[loss])
    n = 60
    for _ in range(n):
        exe.run(feed=feed, fetch_list=[loss])
    snap = tm.snapshot()
    flops = snap["perf.flops_per_step"]
    assert flops > 0, "cost_analysis FLOPs not captured at compile"
    elapsed = 0.25 * n
    assert snap["perf.mfu"] == pytest.approx(flops * n / elapsed / 1e12,
                                             rel=1e-9)
    # goodput: examples/s from the feed batch dim over the same window
    assert snap["perf.goodput.examples_per_s"] == pytest.approx(
        8 * n / elapsed, rel=1e-9)
    assert snap.get("perf.aot_fallbacks", 0) == 0, \
        "AOT executable rejected the executor's own compile args"


def test_tokens_goodput_uses_int_feeds():
    assert attr._feed_shape_stats(
        {"ids": np.zeros((4, 32), dtype=np.int64),
         "x": np.zeros((4, 8), dtype=np.float32)}) == (4, 128)
    # dense-only models fall back to examples
    assert attr._feed_shape_stats(
        {"x": np.zeros((4, 8), dtype=np.float32)}) == (4, 4)
    assert attr._feed_shape_stats({}) == (0, 0)


def test_no_mfu_without_peak(monkeypatch):
    """Unknown device and no override: no perf.mfu gauge (never a
    made-up number), but goodput still reports."""
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    loss = _tiny_train_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.reset()
    for _ in range(3):
        exe.run(feed=_feed(8), fetch_list=[loss])
    snap = tm.snapshot()
    assert "perf.mfu" not in snap
    assert snap["perf.goodput.examples_per_s"] > 0


def test_peak_flops_table(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)

    class _Dev:
        def __init__(self, kind, platform="tpu"):
            self.device_kind = kind
            self.platform = platform

    assert attr.peak_flops(_Dev("TPU v5p")) == 459e12
    assert attr.peak_flops(_Dev("TPU v4")) == 275e12
    assert attr.peak_flops(_Dev("TPU v5 lite")) == 197e12
    # a TPU the table does not know has NO peak (never a v5e default)
    assert attr.peak_flops(_Dev("TPU7x")) is None
    assert attr.peak_flops(_Dev("cpu", platform="cpu")) is None
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "5e13")
    assert attr.peak_flops(_Dev("cpu", platform="cpu")) == 5e13


# ------------------------------------------------- recompile explainer

def test_recompile_explainer_names_shape_bucket():
    loss = _tiny_train_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.reset()
    exe.run(feed=_feed(8), fetch_list=[loss])
    exe.run(feed=_feed(8), fetch_list=[loss])     # cache hit: no event
    baseline = tm.snapshot().get("executor.recompile.count", 0)
    exe.run(feed=_feed(16), fetch_list=[loss])    # forced recompile
    exp = exe.last_recompile
    assert exp is not None and exp["kind"] == "executor"
    assert exp["changed"] == ["feed_signature"]
    assert exp["components"] == ["shape bucket"]
    assert "'x' shape (8, 16) -> (16, 16)" in exp["detail"]
    assert "'y' shape (8, 1) -> (16, 1)" in exp["detail"]
    snap = tm.snapshot()
    assert snap["executor.recompile.count"] == baseline + 1
    events = [s for s in tm.iter_spans()
              if s.name == "executor.recompile.explained"]
    assert events and events[-1].args["changed"] == "feed_signature"
    assert "shape (8, 16) -> (16, 16)" in events[-1].args["detail"]
    # the explainer event renders as a Chrome instant event
    trace = [e for e in tm.chrome_trace()["traceEvents"]
             if e.get("ph") == "i"]
    assert any(e["name"] == "executor.recompile.explained"
               for e in trace)


def test_recompile_explainer_names_donate_and_mode():
    loss = _tiny_train_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.reset()
    feed = _feed(8)
    exe.run(feed=feed, fetch_list=[loss])
    exe.donate_state = False
    exe.run(feed=feed, fetch_list=[loss])
    assert exe.last_recompile["changed"] == ["donate"]
    assert exe.last_recompile["components"] == ["donate flag"]
    exe.donate_state = True
    exe.run(feed=feed, fetch_list=[loss], is_test=True)
    assert "is_test" in exe.last_recompile["changed"]
    assert "train/eval mode" in exe.last_recompile["components"]


def test_explainer_picks_nearest_neighbor():
    """With several seen keys, the diff runs against the one sharing
    the most fields — a one-field change reports one field even when a
    very different key is also cached."""
    base = {"program_id": 1, "program_version": 2,
            "feed_signature": (("x", (8, 4), "float32"),),
            "fetch_names": ("loss",), "is_test": False, "seed": 0,
            "fuse_optimizer_tail": True, "fuse_max_elems": 64,
            "donate": True}
    far = dict(base, program_id=99, is_test=True, seed=7,
               fetch_names=("acc",))
    new = dict(base, seed=1)
    exp = attr.explain_recompile("executor", new, [far, base], step=4)
    assert exp["changed"] == ["seed"]
    assert exp["components"] == ["seed"]
    assert exp["step"] == 4 and exp["seen_keys"] == 2
    assert attr.explain_recompile("executor", new, []) is None


# ------------------------------------- the step a deferred span counts to

def test_the_executor_tags_deferred_spans_with_the_step_that_dispatched_them():
    """async_steps=k: the pending_wait / fetch_readback spans a later
    run() materializes carry the step that DISPATCHED the work, which is
    what `profiler.step_of` counts them by (here from the span ring; under
    a session the same spans are the trace's `pt/` events)."""
    from paddle_tpu import profiler
    feeds = [_feed(8, seed=i) for i in range(6)]
    main_p, startup_p = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup_p):
        with pt.unique_name.guard():
            loss = _tiny_train_program()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup_p)
        tm.enable()
        tm.reset()
        outs = [exe.run(main_p, feed=f, fetch_list=[loss],
                        async_steps=2) for f in feeds]
        exe.drain()
        assert all(np.isfinite(np.asarray(o[0])) for o in outs)
    spans = tm.iter_spans()
    dispatch = {s.args["step"]: s for s in spans
                if s.name == "executor.step"}
    waits = [s for s in spans if s.name == "executor.pending_wait"]
    readbacks = [s for s in spans
                 if s.name == "executor.fetch_readback"]
    assert set(dispatch) == {1, 2, 3, 4, 5, 6}
    # every deferred span carries the step that dispatched it
    assert waits and all(s.args["step"] in dispatch for s in waits)
    assert {s.args["step"] for s in readbacks} == set(dispatch)
    # deferral actually happened: some step's wait/readback
    # materialized after a LATER step was dispatched
    assert any(s.ts_us > dispatch[s.args["step"] + 1].ts_us
               for s in waits + readbacks
               if s.args["step"] + 1 in dispatch), \
        "no span materialized after a later step's dispatch"
    # the report's per-step rows, from these very spans
    rows = {r["step"]: r for r in profiler.step_rows(
        [(s.name, 1e-6 * s.ts_us, 1e-6 * s.dur_us, s.tid, s.args or {})
         for s in spans if s.cat != "counter"])}
    assert set(rows) == set(dispatch)
    for r in rows.values():
        assert r["self_ms"]["executor.step"] > 0
        assert r["self_ms"]["executor.fetch_readback"] > 0
    # what ran inside a later step's run is counted to its own step
    assert sum(r["deferred_ms"] for r in rows.values()) > 0


def test_an_async_read_back_counts_to_the_step_that_dispatched_it():
    from paddle_tpu import profiler

    def _span(name, start, end, **stats):
        return (name, start, end - start, "main", stats)

    spans = [
        _span("executor.run", 0.000, 0.010, program=7, step=5),
        _span("executor.step", 0.001, 0.002, step=5),
        # inside step 5's run, the deferred work of step 3
        _span("executor.pending_wait", 0.002, 0.006, step=3),
        _span("executor.fetch_readback", 0.006, 0.009, step=3),
        _span("executor.feed_put", 0.0095, 0.0100),
    ]
    parent = profiler.parents_of(spans)
    assert profiler.step_of(spans, parent) == [5, 5, 3, 3, 5]
    rows = {r["step"]: r for r in profiler.step_rows(spans)}
    assert rows[3]["self_ms"] == pytest.approx(
        {"executor.pending_wait": 4.0, "executor.fetch_readback": 3.0})
    assert rows[3]["deferred_ms"] == pytest.approx(7.0)
    assert rows[5]["self_ms"] == pytest.approx(
        {"executor.run": 1.5, "executor.step": 1.0,
         "executor.feed_put": 0.5})
    assert rows[5]["deferred_ms"] == 0.0 and not rows[5]["compile_run"]
    spans[0][4]["compile_run"] = True
    assert profiler.step_rows(spans)[1]["compile_run"]
    # the report says so, in one line under the host part
    text = profiler.render({
        "header": dict.fromkeys(
            ("program", "busy_share", "peak_bytes", "compiled", "xplane"),
            None) | {"session_s": 0.01, "steps": 1, "compile_steps": [],
                     "sorted_key": "total", "scoped": False},
        "device": None, "host": profiler.host_rows(spans, []),
        "steps": profiler.step_rows(spans)})
    assert "Deferred work (async_steps): 7.000 ms of steps 3..3" in text


# ------------------------------------------------- bench history spine

def _load_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_mod_attr", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_history_records_schema(tmp_path):
    bench = _load_bench()
    result = {"metric": "transformer_base_train_tokens_per_sec",
              "value": 1234.5, "unit": "tokens/sec", "platform": "cpu",
              "device_kind": "cpu", "mfu": 0.0,
              "mnist_mlp_steps_per_sec": 99.0,
              "deepfm_step_ms": 12.0,
              "resnet50_images_per_sec": 0.0,    # falsy: dropped
              "probe": {"attempts": 1}}          # non-numeric: dropped
    recs = bench._history_records(result, now=1700000000.0)
    by_metric = {r["metric"]: r for r in recs}
    assert set(by_metric) == {"transformer_base_train_tokens_per_sec",
                              "mnist_mlp_steps_per_sec",
                              "deepfm_step_ms"}
    for r in recs:
        assert r["schema"] == slo.HISTORY_SCHEMA
        assert r["platform"] == "cpu"
        assert r["unix_time"] == 1700000000.0
        assert isinstance(r["value"], float)
        assert r["stage"] and r["unit"]
    assert by_metric["deepfm_step_ms"]["unit"] == "ms"
    path = tmp_path / "hist.jsonl"
    assert bench._append_history(result, path=str(path)) == str(path)
    assert len(slo.load_history(str(path))) == len(recs)
    # the helper never raises on an unwritable path (bench contract:
    # the final stdout line survives everything)
    assert bench._append_history(
        result, path=str(tmp_path / "no" / "dir" / "h.jsonl")) is None


def test_committed_history_spine_parses_and_gates():
    """BENCH_history.jsonl at the repo root: the committed perf spine
    must parse, carry every bench stage, and pass its own gate."""
    path = os.path.join(REPO, "BENCH_history.jsonl")
    recs = slo.load_history(path)
    assert recs, "BENCH_history.jsonl missing or empty"
    stages = {r.get("stage") for r in recs}
    for stage in ("transformer", "mnist", "deepfm", "resnet",
                  "inference"):
        assert stage in stages, f"no history record for {stage}"
    for r in recs:
        assert r["schema"] == slo.HISTORY_SCHEMA
    gate = slo.history_gate(recs, platform="cpu")
    assert gate["ok"], gate["regressions"]


# --------------------------------------------------- serving request ids

def test_http_request_id_threaded_and_echoed(tmp_path):
    from paddle_tpu.serving import (BatchConfig, HttpFrontend,
                                    ModelServer, ServerConfig)
    img = layers.data("img", shape=[8])
    pred = layers.fc(img, 4, act="softmax")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(str(tmp_path), ["img"], [pred], exe)
    tm.enable()
    server = ModelServer(ServerConfig(
        batch=BatchConfig(max_batch_size=4, buckets=(4,),
                          max_wait_ms=1.0), workers=1))
    server.load("m", str(tmp_path))
    x = np.zeros((2, 8), dtype="float32")
    with HttpFrontend(server, port=0) as fe:
        # caller-supplied id: echoed in body + header, on the spans
        req = urllib.request.Request(
            fe.url + "/v1/models/m:predict",
            data=json.dumps({"inputs": {"img": x.tolist()},
                             "request_id": "req-abc-123"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["X-Request-Id"] == "req-abc-123"
            body = json.loads(resp.read())
        assert body["request_id"] == "req-abc-123"
        # no id supplied: one is generated
        req = urllib.request.Request(
            fe.url + "/v1/models/m:predict",
            data=json.dumps({"inputs": {"img": x.tolist()}}).encode())
        with urllib.request.urlopen(req, timeout=30) as resp:
            gen = json.loads(resp.read())["request_id"]
        assert gen and gen != "req-abc-123"
        # header id echoed even on an error (malformed body -> 400)
        req = urllib.request.Request(
            fe.url + "/v1/models/m:predict",
            data=b'{"inputs": "nope"}',
            headers={"X-Request-Id": "req-err-9"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=30)
        assert err.value.code == 400
        assert json.loads(err.value.read())["request_id"] == "req-err-9"
    server.shutdown(timeout=5.0)
    http_spans = [s for s in tm.iter_spans()
                  if s.name == "serving.http.predict"]
    assert {s.args["request_id"] for s in http_spans} >= \
        {"req-abc-123", gen}
    batch_spans = [s for s in tm.iter_spans()
                   if s.name == "serving.batch" and
                   (s.args or {}).get("request_ids")]
    flat = [rid for s in batch_spans for rid in s.args["request_ids"]]
    assert "req-abc-123" in flat and gen in flat


# ----------------------------------------------------------- CI gate

def test_tpustat_slo_selftest_subprocess():
    """The tier-1 wiring: `tpustat --slo --selftest` parses and
    round-trips rules, runs a live attributed model, proves the
    regression detector flags an injected step-time regression (and
    passes a clean spine), and exits 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("PADDLE_TPU_TELEMETRY", "PADDLE_TPU_PEAK_FLOPS"):
        env.pop(k, None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpustat.py"),
         "--slo", "--selftest", "--json"],
        capture_output=True, text=True, timeout=480, env=env)
    assert p.returncode == 0, (p.stdout[-800:], p.stderr[-800:])
    obj = json.loads(p.stdout.strip().splitlines()[-1])
    assert obj["ok"] is True and obj["problems"] == []


def test_tpustat_slo_gate_on_live_run(tmp_path):
    """`tpustat <model> --slo --rules` end to end, one subprocess: a
    satisfiable rule PASSes in the report while an impossible rule
    fails the run (exit 2) with the violation named."""
    hist = str(tmp_path / "empty_hist.jsonl")   # isolate from the repo spine
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_PEAK_FLOPS="1e12")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpustat.py"),
         "--model", "mnist", "--steps", "4", "--json", "--slo",
         "--history", hist,
         "--rules", "perf.mfu > 0; executor.steps > 1e9"],
        capture_output=True, text=True, timeout=480, env=env)
    assert p.returncode == 2, (p.stdout[-800:], p.stderr[-800:])
    obj = json.loads(p.stdout.strip().splitlines()[-1])
    assert any("SLO violated" in pr for pr in obj["problems"])
    results = {r["rule"]: r for r in obj["slo"]["slo"]["results"]}
    assert results["perf.mfu > 0"]["ok"] is True
    assert results["perf.mfu > 0"]["observed"] > 0
    assert results["executor.steps > 1e9"]["ok"] is False
