"""Tier-1 tests of the readers that take the program's own spans, scopes,
kernel names and compile log out of a run (chipbench/program_trace.py and
the metrics of PR 27), on small synthetic lists and one synthetic xplane
file. All on the CPU; nothing here describes a TPU topology."""
import io
import json
import os
import re
import sys
from contextlib import redirect_stdout

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import manifest  # noqa: E402
from chipbench import program_trace as ptr  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

REPO = tiny.REPO
NEW = ([f"exec_gap_ms_per_step.{s}" for s in (
            "feed_put", "prepare", "step", "fetch_readback", "release",
            "caller")]
       + [f"train_phase_ms_per_step.{s}" for s in (
            "forward", "backward", "optimizer")]
       + [f"train_op_ms_per_step.{s}" for s in (
            "mul", "flash_attention", "split", "layer_norm", "unscoped")]
       + ["kernel_ms_per_step.layer_norm_fwd",
          "kernel_ms_per_step.layer_norm_bwd"]
       + [f"exec_compile_s.{s}" for s in (
            "trace", "lower", "backend", "cache_load")]
       + ["exec_compiled_programs"])
# PR 29's: the attention kernels' times and their shares of the roofline
ADDED = ["kernel_ms_per_step.flash_attention_short_fwd",
         "kernel_ms_per_step.flash_attention_short_bwd",
         "flash_attention_short_fwd_roofline",
         "flash_attention_short_bwd_roofline"]
# of these, the entries BENCHMARK.json no longer has; their readers stay
RETIRED = ["train_op_ms_per_step.split", "exec_compile_s.cache_load",
           "kernel_ms_per_step.flash_attention_short_fwd",
           "kernel_ms_per_step.flash_attention_short_bwd"]

# two steps of 10 ms on the device, 2 ms apart, in a window of 30 ms; the
# host is inside executor.run the whole time but for 0.5 ms between steps
OPS = [("fusion.1", 0.003, 0.006), ("tpu_custom_call/layer_norm_fwd.1",
                                    0.009, 0.001),
       ("tpu_custom_call/layer_norm_bwd.1", 0.010, 0.003),
       ("fusion.1", 0.015, 0.006), ("tpu_custom_call/layer_norm_fwd.1",
                                    0.021, 0.001),
       ("copy.7", 0.022, 0.003)]
LO, HI = 0.0, 0.030


def _span(name, start, end, **stats):
    return (name, start, end - start, "python", stats)


SPANS = [
    _span("executor.run", 0.0005, 0.0140, program=7),
    _span("executor.feed_put", 0.0010, 0.0020),
    _span("executor.prepare", 0.0020, 0.0025),
    _span("executor.step", 0.0025, 0.0040),
    _span("executor.scope_write", 0.0040, 0.0042),
    _span("executor.fetch_readback", 0.0042, 0.0135),
    _span("executor.release", 0.0135, 0.0139),
    _span("executor.run", 0.0145, 0.0300, program=7),
    _span("executor.feed_put", 0.0146, 0.0148),
    _span("executor.step", 0.0148, 0.0160),
    _span("executor.fetch_readback", 0.0160, 0.0290),
]


def test_idle_time_is_split_among_the_innermost_spans():
    idle = ptr.idle_by_span(OPS, SPANS, LO, HI)
    want = {"caller": 0.0005 + 0.0005,       # before the first run, between
            # the parent outside its children
            "run": 0.0005 + 0.0001 + 0.0001 + 0.0010,
            "feed_put": 0.0010 + 0.0002,
            "prepare": 0.0005,
            "step": 0.0005 + 0.0002,   # the device starts inside the dispatch
            "fetch_readback": 0.0005 + 0.0040,   # the wake-up after the step
            "release": 0.0004}
    assert idle == pytest.approx(want)
    # seen from outside: the window less the union of the device's ops
    busy = 0.010 + 0.010
    assert sum(idle.values()) == pytest.approx((HI - LO) - busy)


def test_one_gap_that_runs_through_three_spans_is_not_given_to_one():
    ops = [("fusion.1", 0.000, 0.001), ("fusion.1", 0.010, 0.001)]
    spans = [_span("executor.run", 0.000, 0.011),
             _span("executor.feed_put", 0.001, 0.002),
             _span("executor.prepare", 0.002, 0.009),
             _span("executor.step", 0.009, 0.011)]
    idle = ptr.idle_by_span(ops, spans, 0.0, 0.011)
    assert idle == pytest.approx({"feed_put": 0.001, "prepare": 0.007,
                                  "step": 0.001})


def test_no_entry_span_means_nothing_to_say_not_all_caller():
    assert ptr.idle_by_span(OPS, [], LO, HI) is None
    other = [_span("serving.decode.step", 0.0, 0.01)]
    assert ptr.idle_by_span(OPS, other, LO, HI) is None


def test_spans_get_their_parent_by_containment_on_one_thread():
    spans = SPANS[:3] + [("executor.run", 0.0, 0.02, "other thread", {})]
    got = ptr.with_parents(spans)
    assert [s[5] for s in got] == [None, 0, 0, None]
    assert ptr.window_owner(SPANS) == "executor:7"
    assert ptr.window_owner(SPANS[1:7]) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(stepped)/jvp(mul)/dot_general", ("forward", "mul")),
    ("jit(stepped)/transpose(jvp(mul))/dot_general", ("backward", "mul")),
    ("jit(stepped)/jit(main)/transpose(jvp(layer_norm))/layer_norm_bwd/"
     "pallas_call", ("backward", "layer_norm")),
    ("jit(stepped)/adam/mul", ("optimizer", "adam")),
    ("jit(stepped)/jvp(cross_entropy)/jit(clip)/max",
     ("forward", "cross_entropy")),
    ("jit(stepped)/convert_element_type", (None, None)),
    ("jit(stepped)/jit(_threefry_fold_in)/mul", (None, None)),
    ("reduce_sum", (None, None)),
    ("", (None, None)),
    (None, (None, None)),
])
def test_a_name_stack_gives_phase_and_op_type(op_name, want):
    assert ptr.classify(op_name) == want


HLO = '''HloModule jit_stepped, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %add.3 = f32[2]{0} add(%p, %p), metadata={op_name="jit(stepped)/jvp(mul)/add"}
}

ENTRY %main {
  %fusion.1 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(stepped)/transpose(jvp(mul))/dot_general" stack_frame_id=3}
  %layer_norm_fwd.1 = f32[2]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(stepped)/jvp(layer_norm)/layer_norm_fwd/pallas_call"}, backend_config={"body":"x"}
  %layer_norm_bwd.1 = f32[2]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(stepped)/transpose(jvp(layer_norm))/layer_norm_bwd/pallas_call"}
  %copy.7 = f32[2]{0} copy(%a)
  ROOT %fusion.9 = f32[2]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(stepped)/adam/mul"}
}
'''


def test_phase_split_and_kernels_by_name_on_a_small_list():
    scopes = ptr.scopes_of(HLO)
    assert scopes["fusion.1"].endswith("transpose(jvp(mul))/dot_general")
    assert "copy.7" not in scopes and scopes["add.3"].endswith("jvp(mul)/add")
    assert scopes["fusion.9"] == "jit(stepped)/adam/mul"
    by_phase, by_op = ptr.op_seconds([OPS], scopes)
    assert by_phase == pytest.approx({"backward": 0.012 + 0.003,
                                      "forward": 0.002, "unscoped": 0.003})
    assert by_op == pytest.approx({"mul": 0.012, "layer_norm": 0.005,
                                   "unscoped": 0.003})
    # every op is under exactly one phase: the split adds up to busy time
    assert sum(by_phase.values()) == pytest.approx(sum(d for *_, d in OPS))
    # two chips: the average
    half = [(n, s, d / 2) for n, s, d in OPS]
    assert ptr.op_seconds([OPS, half], scopes)[1]["mul"] == pytest.approx(
        0.009)
    assert ptr.kernel_seconds([OPS]) == pytest.approx(
        {"layer_norm_fwd": 0.002, "layer_norm_bwd": 0.003})


def test_compile_phases_take_the_union_of_nested_traces():
    T, L, B = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
    C = "/jax/compilation_cache/cache_retrieval_time_sec"
    log = [
        (T, 0.1, "executor:7", "tanh", 1.2),        # inside the next one
        (T, 1.0, "executor:7", "stepped", 2.0),
        (L, 0.5, "executor:7", "stepped", 2.5),
        (B, 3.0, "executor:7", "stepped", 5.5),
        (T, 0.2, "executor:9", "stepped", 6.0),
        (L, 0.1, "executor:9", "stepped", 6.1),
        ("/jax/compilation_cache/cache_hits", 0.0, "executor:9", None, 6.3),
        (C, 0.4, "executor:9", None, 6.6),
        (B, 0.5, "executor:9", "stepped", 6.6),     # a load from the cache
        (T, 9.0, None, "reference", 20.0),          # the caller's own
        (B, 9.0, "decode.write_slots", "scatter", 30.0),
    ]
    got = ptr.compile_phases(log)
    assert got == pytest.approx({"trace": 1.2, "lower": 0.6, "backend": 3.1,
                                 "cache_load": 0.4, "programs": 2})
    assert ptr.compile_phases(log[-2:]) is None
    assert ptr.compile_phases(log, "decode.")["programs"] == 1


# ------------------------------------------------ the readers, end to end
_XSPACE = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 500000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 3000000000 duration_ps: 6000000000 }
    events { metadata_id: 2 offset_ps: 9000000000 duration_ps: 1000000000 }
    events { metadata_id: 3 offset_ps: 10000000000 duration_ps: 3000000000 }
    events { metadata_id: 4 offset_ps: 15000000000 duration_ps: 6000000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %a), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2
    name: "%layer_norm_fwd.1 = f32[2]{0} custom-call(%a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3
    name: "%layer_norm_bwd.1 = f32[2]{0} custom-call(%a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.7 = f32[2]{0} copy(%a)" } }
  event_metadata { key: 9 value { id: 9 name: "%warmup.1 = f32[] add()" } } }
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 29000000000 }
    events { metadata_id: 2 offset_ps: 1500000000 duration_ps: 20000000000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 1500000000
             stats { metadata_id: 2 int64_value: 5 } }
    events { metadata_id: 4 offset_ps: 100000000 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "cb/window" } }
  event_metadata { key: 2 value { id: 2 name: "pt/executor.run" } }
  event_metadata { key: 3 value { id: 3 name: "pt/executor.feed_put" } }
  event_metadata { key: 4 value { id: 4 name: "pt/executor.run" } }
  stat_metadata { key: 1 value { id: 1 name: "program" } }
  stat_metadata { key: 2 value { id: 2 name: "puts" } } }
'''


@pytest.fixture()
def traced_root(tmp_path, monkeypatch):
    """A root with BENCHMARK.json, the readers, and one synthetic trace."""
    from jax.profiler import ProfileData
    root = tiny.make_root(tmp_path)
    d = os.path.join(root, ".chipbench_trace", "cell", "plugins", "profile",
                     "t0")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    from paddle_tpu import telemetry
    monkeypatch.setattr(telemetry, "compiled_text",
                        lambda owner: HLO if owner == "executor:7" else None)
    return root


def test_the_trace_is_found_from_the_readers_place_and_clipped(traced_root):
    reader = os.path.join(traced_root, "chipbench", "metrics",
                          "exec_gap_ms_per_step.py")
    assert ptr.find_root(reader) == traced_root
    tr = ptr.load(reader)
    assert tr["window"] == pytest.approx((0.001, 0.030))
    # what started before the window (a warm-up op, an earlier run) is out
    assert [n for n, *_ in tr["chips"][0]] == [
        "fusion.1", "tpu_custom_call/layer_norm_fwd.1",
        "tpu_custom_call/layer_norm_bwd.1", "copy.7"]
    assert [(n, st) for n, _, _, _, st in tr["spans"]] == [
        ("executor.run", {"program": 7}), ("executor.feed_put", {"puts": 5})]
    assert ptr.load(reader) is tr                       # read once


def test_readers_on_the_synthetic_trace(traced_root):
    man = manifest.Manifest(traced_root)
    facts = {"kind": "train", "on_chip": True, "steps": 2}

    def read(name):
        return man.reader(name).read(facts, name)

    gaps = {s: read(f"exec_gap_ms_per_step.{s}") for s in (
        "feed_put", "prepare", "step", "fetch_readback", "release",
        "caller")}
    # idle: [1, 3) ms and [13, 15) ms and [21, 30) ms of the window [1, 30)
    assert gaps == pytest.approx({
        "feed_put": 0.5, "prepare": 0.0, "step": 0.0, "fetch_readback": 0.0,
        "release": 0.0, "caller": (0.5 + 8.5) / 2})
    assert read("train_phase_ms_per_step.backward") == pytest.approx(4.5)
    assert read("train_phase_ms_per_step.forward") == pytest.approx(0.5)
    assert read("train_phase_ms_per_step.optimizer") == 0.0
    assert read("train_op_ms_per_step.mul") == pytest.approx(3.0)
    assert read("train_op_ms_per_step.layer_norm") == pytest.approx(2.0)
    assert read("train_op_ms_per_step.unscoped") == pytest.approx(3.0)
    assert read("kernel_ms_per_step.layer_norm_fwd") == pytest.approx(0.5)
    assert read("kernel_ms_per_step.layer_norm_bwd") == pytest.approx(1.5)
    # off the chip every one of them, being a time, says nothing
    facts["on_chip"] = False
    for name in NEW + ADDED:
        if name != "exec_compiled_programs":
            assert read(name) is None, name


def test_a_kernels_share_of_its_roofline(traced_root):
    """Floor over traced time, by the kernel the metric's name gives; the
    synthetic trace has layer_norm_fwd for 1 ms and _bwd for 3 ms."""
    man = manifest.Manifest(traced_root)
    work = {"layer_norm_fwd": [(0.0, 819e9 * 1e-4, 2)],      # 0.1 ms, twice
            "layer_norm_bwd": [(197e12 * 5e-4, 1.0, 1),      # MXU-bound
                               (1.0, 819e9 * 2.5e-4, 4)]}    # HBM-bound
    facts = {"kind": "train", "on_chip": True, "steps": 2,
             "device_kind": "TPU v5 lite", "kernel_work": work}
    read = man.reader("layer_norm_fwd_roofline").read
    assert read is man.reader("layer_norm_bwd_roofline.a_tag").read
    # per step 0.2 ms of floor; the kernel took 1 ms over 2 steps
    assert read(facts, "layer_norm_fwd_roofline") == pytest.approx(
        100 * 2 * 0.2e-3 / 1e-3)
    assert read(facts, "layer_norm_bwd_roofline.a_tag") == pytest.approx(
        100 * 2 * 1.5e-3 / 3e-3)
    # a kernel that did not run, a model that counts nothing for it, a run
    # off the chip: nothing, never 0
    assert read(facts, "flash_attention_short_fwd_roofline") is None
    assert read(dict(facts, kernel_work={}), "layer_norm_fwd_roofline") \
        is None
    assert read({k: v for k, v in facts.items() if k != "kernel_work"},
                "layer_norm_fwd_roofline") is None
    assert read(dict(facts, on_chip=False), "layer_norm_fwd_roofline") \
        is None
    with pytest.raises(manifest.ManifestError):
        man.reader("no_such_reader")


def test_a_tag_after_the_second_dot_is_not_read(traced_root):
    man = manifest.Manifest(traced_root)
    facts = {"kind": "train", "on_chip": True, "steps": 2}
    for name in ("train_op_ms_per_step.mul", "kernel_ms_per_step."
                 "layer_norm_bwd", "exec_gap_ms_per_step.feed_put",
                 "train_phase_ms_per_step.backward"):
        read = man.reader(name).read
        assert read(facts, name + ".second_model") == read(facts, name) > 0


@pytest.mark.parametrize("name", NEW + ADDED)
def test_a_new_reader_with_nothing_to_read_returns_nothing(
        tmp_path, monkeypatch, name):
    """No trace file, and a program with no compile log or text (the
    parent of PR 27): nothing is reported and nothing raises."""
    from paddle_tpu import telemetry
    root = tiny.make_root(tmp_path)
    monkeypatch.delattr(telemetry, "compile_log")
    monkeypatch.delattr(telemetry, "compiled_text")
    work = {"flash_attention_short_fwd": [(1e9, 1e6, 18)],
            "flash_attention_short_bwd": [(1e9, 1e6, 18)]}
    facts = {"kind": "train", "on_chip": True, "steps": 3,
             "device_kind": "TPU v5 lite", "kernel_work": work}
    assert manifest.Manifest(root).reader(name).read(facts, name) is None


def test_a_program_without_text_leaves_the_scoped_metrics_out(
        traced_root, monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(telemetry, "compiled_text", lambda owner: None)
    man = manifest.Manifest(traced_root)
    facts = {"kind": "train", "on_chip": True, "steps": 2}
    name = "train_phase_ms_per_step.forward"
    assert man.reader(name).read(facts, name) is None
    name = "kernel_ms_per_step.layer_norm_fwd"     # needs no text
    assert man.reader(name).read(facts, name) == pytest.approx(0.5)


def perf_md_layers():
    """The first column of the table of PERF.md section 3."""
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    section = text[text.index("\n## 3."):text.index("\n## 4.")]
    rows = re.findall(r"^\| ([^|]+?) \|", section, re.M)
    return {r for r in rows if r != "Layer" and not r.startswith("---")}


def test_the_manifest_validates_with_the_new_entries():
    """The entries of NEW and ADDED are there, whatever else is and
    wherever they stand (a later PR appends entries and cells), but for
    those that read a constant (`cache_load`: the runs compile cold), an op the
    program no longer has (`split`) or a time that a roofline share of the
    same kernel already gives. Their readers stay, and so do the tests of
    them above."""
    man = manifest.Manifest(REPO).validate()
    by_name = {m["name"]: m for m in man.doc["per_layer"]}
    kept = [n for n in NEW + ADDED if n not in RETIRED]
    assert set(kept) <= set(by_name)
    assert not set(RETIRED) & set(by_name)
    layers = perf_md_layers()
    assert {"kernels", "model step", "device"} <= layers
    for name in kept:
        m = by_name[name]
        assert "nmt_train_1chip" in m["workloads"]
        assert m["moves"] == ("setup_s" if name.startswith("exec_compile")
                              else "train_tokens_per_s")
    for m in man.doc["per_layer"]:
        assert m["layer"] in layers, "a layer spelled as PERF.md spells it"
    for name in ADDED[2:]:
        assert by_name[name]["layer"] == "kernels"
        assert by_name[name]["source"] == "device_trace"
        assert (by_name[name]["unit"], by_name[name]["better"]) == (
            "%", "higher")
    for gone in ("ln_kernel_ms_per_step", "exec_host_ms_per_step"):
        assert gone not in by_name
        with pytest.raises(manifest.ManifestError):
            man.reader(gone)
    for name in RETIRED:
        man.reader(name)
    reported = {m["name"] for m in man.cell_per_layer("nmt_train_1chip")}
    assert set(kept) <= reported


def test_cpu_traced_run_reports_the_count_and_no_time(tmp_path):
    root = tiny.make_root(tmp_path)
    # the count runs from process start, and this process may have run
    # other cells before
    from paddle_tpu import telemetry
    telemetry.compiles._records.clear()
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(["--workload", "nmt_train_1chip", "--seed", "11",
                          "--seconds", "1", "--trace", "1"], root=root)
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    man = manifest.Manifest(root)
    times = {m["name"] for m in man.cell_per_layer("nmt_train_1chip")
             if m["source"] in ("device_trace", "program_span")
             or m["unit"] in ("ms", "s")}
    assert not times & set(res["metrics"])
    # the startup program and the train step: two programs asked of XLA
    assert res["metrics"]["exec_compiled_programs"]["value"] == 2
    # the rehearsal's trace holds the program's spans all the same
    tr = ptr.load(os.path.join(root, "chipbench", "metrics",
                               "exec_gap_ms_per_step.py"))
    assert tr["chips"] == [] and tr["window"] is not None
    names = {n for n, *_ in tr["spans"]}
    assert {"executor.run", "executor.feed_put", "executor.prepare",
            "executor.step", "executor.fetch_readback",
            "executor.release"} <= names
