"""paddle_tpu.telemetry: registry semantics, span tracer, executor
instrumentation (compile vs cache-hit accounting, disabled-mode no-op),
export surfaces, and the tpustat CLI."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import telemetry as tm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Every test starts disabled and empty, and leaves no state for
    the rest of the suite (the bench-contract fast-path test asserts
    the global registry is empty)."""
    tm.disable()
    tm.reset()
    yield
    tm.disable()
    tm.reset()


def _tiny_program():
    img = layers.data("img", shape=[8])
    h = layers.fc(img, size=4, act="relu")
    out = layers.reduce_mean(h)
    return out


# ---------------------------------------------------------------- registry

def test_counter_semantics():
    c = tm.counter("t.c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert tm.counter("t.c") is c          # same object, same name
    with pytest.raises(ValueError):
        c.inc(-1)
    assert tm.snapshot()["t.c"] == 5


def test_gauge_semantics():
    g = tm.gauge("t.g")
    g.set(3.5)
    g.set_max(2.0)                          # watermark: no decrease
    assert g.value == 3.5
    g.set_max(7.0)
    assert g.value == 7.0
    g.set(1.0)                              # plain set always writes
    assert tm.snapshot()["t.g"] == 1.0


def test_histogram_semantics():
    h = tm.histogram("t.h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    d = tm.snapshot()["t.h"]
    assert d["count"] == 4
    assert d["sum"] == pytest.approx(55.55)
    assert d["buckets"][0.1] == 1
    assert d["buckets"][1.0] == 1
    assert d["buckets"][10.0] == 1
    assert d["buckets"]["+Inf"] == 1
    assert d["min"] == 0.05 and d["max"] == 50.0
    # bucket edges are frozen per name
    with pytest.raises(ValueError):
        tm.histogram("t.h", buckets=(1.0, 2.0))


def test_metric_type_conflict_raises():
    tm.counter("t.x")
    with pytest.raises(TypeError):
        tm.gauge("t.x")
    with pytest.raises(TypeError):
        tm.histogram("t.x")


def test_thread_safety_smoke():
    h = tm.histogram("t.th", buckets=(0.5,))

    def work():
        for _ in range(1000):
            tm.counter("t.tc").inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = tm.snapshot()
    assert snap["t.tc"] == 8000
    assert snap["t.th"]["count"] == 8000
    assert snap["t.th"]["buckets"][0.5] == 8000


def test_snapshot_consistent_while_writers_hammer():
    """Regression (fleet satellite): snapshot()/flush() racing
    observe() must always see internally consistent metrics — bucket
    totals equal the count, nothing torn — and once writers join, the
    final snapshot accounts for every single write. This is what makes
    the periodic fleet spool flush safe while step loops keep
    recording."""
    import paddle_tpu.telemetry.fleet as tf
    tm.enable()
    stop = threading.Event()
    wrote = [0] * 4

    def writer(i):
        n = 0
        while not stop.is_set():
            tm.counter("race.c").inc()
            tm.histogram("race.h", buckets=(0.5, 1.5)).observe(n % 2)
            n += 1
        wrote[i] = n

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    torn = []
    for _ in range(150):
        snap = tm.snapshot()
        h = snap.get("race.h")
        if not h:
            continue
        if sum(h["buckets"].values()) != h["count"]:
            torn.append(h)
        # the spool envelope takes the same read path; it must never
        # raise mid-hammer either
        env = tf.build_envelope(rank_override=0)
        hk = env["metrics"].get("race.h")
        if hk and sum(hk["value"]["buckets"].values()) \
                != hk["value"]["count"]:
            torn.append(hk)
    stop.set()
    for t in threads:
        t.join()
    assert not torn, f"{len(torn)} torn snapshots, e.g. {torn[0]}"
    snap = tm.snapshot()
    assert snap["race.c"] == sum(wrote)
    assert snap["race.h"]["count"] == sum(wrote)
    assert snap["race.h"]["buckets"][0.5] \
        + snap["race.h"]["buckets"][1.5] == sum(wrote)


def test_env_enable_parsing():
    assert tm._env_truthy("1") and tm._env_truthy("true")
    assert not tm._env_truthy("") and not tm._env_truthy("0")
    assert not tm._env_truthy("off") and not tm._env_truthy(None)


# ------------------------------------------------------------------- spans

def test_span_nesting_and_chrome_trace_roundtrip():
    tm.enable()
    with tm.span("outer", k=1):
        with tm.span("inner"):
            pass
    trace = json.loads(json.dumps(tm.chrome_trace()))
    xs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    for e in xs.values():
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["pid"] == os.getpid()
    outer, inner = xs["outer"], xs["inner"]
    assert outer["args"]["depth"] == 0 and inner["args"]["depth"] == 1
    assert outer["args"]["k"] == 1
    # inner nests inside outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_disabled_leaves_ring_and_registry_empty():
    # no profiler session, telemetry off: the span still opens its
    # TraceAnnotation (which records nothing) and touches nothing else
    with tm.span("a", rows=2) as sp:
        sp.set(more=1)
    assert tm.iter_spans() == []
    assert tm.snapshot() == {}
    assert tm.chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------------- executor

def test_disabled_mode_is_noop_on_executor_path():
    out = _tiny_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    x = np.random.rand(2, 8).astype("float32")
    for _ in range(3):
        exe.run(feed={"img": x}, fetch_list=[out])
    assert tm.snapshot() == {}
    assert tm.iter_spans() == []


def test_compile_cache_counters_exact():
    out = _tiny_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.reset()
    x = np.random.rand(2, 8).astype("float32")
    for _ in range(5):
        exe.run(feed={"img": x}, fetch_list=[out])
    snap = tm.snapshot()
    assert snap["executor.compile_count"] == 1
    assert snap["executor.cache_hit_count"] == 4
    assert snap["executor.steps"] == 5
    assert snap["executor.step_seconds"]["count"] == 5
    # a new feed signature is a new compile
    x2 = np.random.rand(4, 8).astype("float32")
    exe.run(feed={"img": x2}, fetch_list=[out])
    assert tm.snapshot()["executor.compile_count"] == 2
    # use_program_cache=False re-traces every call and never hits
    for _ in range(2):
        exe.run(feed={"img": x}, fetch_list=[out],
                use_program_cache=False)
    snap = tm.snapshot()
    assert snap["executor.compile_count"] == 4
    assert snap["executor.cache_hit_count"] == 4
    assert snap["executor.steps"] == 8


def test_executor_spans_on_timeline():
    out = _tiny_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.reset()
    x = np.random.rand(2, 8).astype("float32")
    for _ in range(3):
        exe.run(feed={"img": x}, fetch_list=[out])
    names = [s.name for s in tm.iter_spans()]
    assert names.count("executor.step") == 3
    assert names.count("executor.feed_put") == 3
    assert names.count("executor.fetch_readback") == 3
    assert names.count("executor.compile") == 1


def test_executor_close_clears_caches_and_flushes(tmp_path, monkeypatch):
    out = _tiny_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    tm.enable()
    tm.counter("t.pre_close").inc()
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    exe.close()
    assert exe._cache == {}
    assert exe._seen_keys == set()
    assert exe._step_counters == {}
    # close() flushed the artifacts
    metrics = json.loads((tmp_path / "metrics.json").read_text())
    assert metrics["t.pre_close"] == 1
    assert (tmp_path / "metrics.prom").exists()
    json.loads((tmp_path / "trace.json").read_text())


def test_finite_check_metrics():
    out = _tiny_program()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    exe.check_nan_inf = True
    tm.enable()
    tm.reset()
    x = np.random.rand(2, 8).astype("float32")
    exe.run(feed={"img": x}, fetch_list=[out])
    assert tm.snapshot()["executor.finite_check_seconds"]["count"] == 1


# ------------------------------------------------------------------ reader

def test_pyreader_queue_metrics():
    from paddle_tpu.layers.io import PyReader
    v = layers.data("rq", shape=[4], append_batch_size=False)
    reader = PyReader([v], capacity=4)

    def provider():
        for _ in range(3):
            yield [np.zeros((4,), np.float32)]

    reader._provider = provider
    tm.enable()
    reader.start()
    for _ in range(3):
        reader.next_feed()
    with pytest.raises(pt.EOFException):
        reader.next_feed()
    snap = tm.snapshot()
    assert snap["reader.polls"] == 4
    assert snap["reader.queue_capacity"] == 4
    assert snap["reader.consumer_wait_seconds"]["count"] == 4
    assert "reader.queue_depth" in snap
    assert snap.get("reader.starved_polls", 0) >= 0


# --------------------------------------------------------------- inference

def test_inference_engine_latency_metrics():
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.core.scope import Scope, scope_guard
    scope = Scope()
    with scope_guard(scope):
        out = _tiny_program()
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
    eng = InferenceEngine(pt.default_main_program(), ["img"], [out],
                          scope)
    tm.enable()
    tm.reset()
    x = np.random.rand(2, 8).astype("float32")
    eng.run({"img": x})
    eng.run({"img": x})
    snap = tm.snapshot()
    assert snap["inference.requests"] == 2
    assert snap["inference.latency_seconds"]["count"] == 2
    assert snap["inference.compile_count"] == 1
    assert snap["inference.cache_hit_count"] == 1


# ---------------------------------------------------------------- profiler

def test_record_event_routes_through_telemetry(tmp_path, capsys):
    from paddle_tpu import profiler
    profiler.reset_profiler()
    assert profiler.summary() == []
    tm.enable()
    with profiler.profiler("All", "total", log_dir=str(tmp_path)):
        with profiler.record_event("my_region"):
            pass
    spans = [s for s in tm.iter_spans() if s.name == "my_region"]
    assert len(spans) == 1 and spans[0].cat == "profiler"
    # a region is a span and nothing else: no metric, no second table
    assert "profiler.event_seconds" not in tm.snapshot()
    assert not hasattr(profiler, "_records")
    # summary() is the host part of the session's report, as rows
    row, = [r for r in profiler.summary() if r["span"] == "my_region"]
    assert row["calls"] == 1 and 0 <= row["self_ms"] <= row["total_ms"]
    assert "my_region" in capsys.readouterr().out


def test_device_memory_degrades_on_cpu():
    # this image's CPU devices return no allocator stats: the probe
    # must classify that as unsupported, never raise, and register
    # nothing (tier-1 stays clean)
    tm.enable()
    from paddle_tpu.telemetry import memory
    memory.reset_memory_probe()
    assert memory.device_memory_supported() is False
    assert tm.sample_device_memory() == {}
    assert tm.snapshot() == {}


# ----------------------------------------------------------------- exports

def test_prometheus_text_format():
    tm.counter("a.count").inc(3)
    tm.histogram("a.lat", buckets=(0.1, 1.0)).observe(0.05)
    tm.histogram("a.lat").observe(5.0)
    text = tm.prometheus_text()
    assert "# TYPE a_count counter" in text
    assert "a_count 3" in text
    assert 'a_lat_bucket{le="0.1"} 1' in text
    assert 'a_lat_bucket{le="1"} 1' in text          # cumulative
    assert 'a_lat_bucket{le="+Inf"} 2' in text
    assert "a_lat_count 2" in text


def test_flush_disabled_returns_none(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    tm.counter("z").inc()
    assert tm.flush() is None               # disabled: no writes
    assert not (tmp_path / "metrics.json").exists()


# -------------------------------------------------------------------- CLI

def test_tpustat_validate_metrics_catches_malformed():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tpustat", os.path.join(REPO, "tools", "tpustat.py"))
    tpustat = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tpustat)
    good = {"executor.compile_count": 1, "executor.cache_hit_count": 4,
            "executor.steps": 5,
            "executor.step_seconds": {
                "count": 5, "sum": 1.0,
                "buckets": {0.1: 5, "+Inf": 0}}}
    assert tpustat.validate_metrics(good, 5) == []
    bad = dict(good, **{"executor.cache_hit_count": 2})
    assert any("cache_hit" in p for p in tpustat.validate_metrics(bad, 5))
    broken_hist = dict(good)
    broken_hist["executor.step_seconds"] = {
        "count": 5, "sum": 1.0, "buckets": {0.1: 3, "+Inf": 0}}
    assert any("bucket total" in p
               for p in tpustat.validate_metrics(broken_hist, 5))
    assert any("missing" in p for p in tpustat.validate_metrics({}, 5))


def test_tpustat_cli_json_end_to_end():
    """The acceptance path, small: tpustat runs mnist on CPU, reports
    exact compile/hit accounting, and writes a loadable trace."""
    steps = 4
    trace = "/tmp/tpustat_test.trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_TELEMETRY", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpustat.py"),
         "--model", "mnist", "--steps", str(steps), "--json",
         "--trace", trace],
        capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, (p.stdout[-500:], p.stderr[-800:])
    obj = json.loads(p.stdout.strip().splitlines()[-1])
    assert obj["ok"] is True and obj["problems"] == []
    assert obj["metrics"]["executor.compile_count"] == 1
    assert obj["metrics"]["executor.cache_hit_count"] == steps - 1
    assert obj["trace"]["span_events"] >= steps
    loaded = json.loads(open(trace).read())
    assert sum(1 for e in loaded["traceEvents"]
               if e.get("ph") == "X") >= steps
