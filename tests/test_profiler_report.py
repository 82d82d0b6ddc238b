"""`fluid.profiler`'s report from a file and from a session (PR 38): a
synthetic xplane file with a device plane joined to a program's scopes,
every `sorted_key`, and real sessions on the CPU, whose trace has no
device plane: the host part, what compiled inside, the session's own file
and no other, the deferred work of `async_steps`, `tools/tpuprof.py`.
"""
import json
import os
import re
import sys

import jax
import pytest
from jax.profiler import ProfileData

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu import telemetry as tm
from test_name_scope import REPO, TINY, _feed, _toy
from test_profiler_device_rows import HLO, SITES


# ------------------------------------------------------------ a session
XSPACE = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 3 offset_ps: 4000000000 duration_ps: 1000000000 }
    events { metadata_id: 5 offset_ps: 5000000000 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 6000000000 duration_ps: 6000000000 }
    events { metadata_id: 6 offset_ps: 12000000000 duration_ps: 1000000000 }
    events { metadata_id: 4 offset_ps: 14000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 2000000000 } }
  lines { name: "Async XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 6 offset_ps: 0 duration_ps: 20000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4]{0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[4]{0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[4]{0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[4]{0} fusion(%p), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%layer_norm_fwd.1 = f32[4]{0} custom-call(%p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.7 = f32[4]{0} copy(%p)" } } }
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 15000000000
      stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 16000000000 duration_ps: 8000000000
      stats { metadata_id: 1 int64_value: 2 } stats { metadata_id: 2 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "pt/executor.run" } }
  event_metadata { key: 2 value { id: 2 name: "pt/executor.step" } }
  event_metadata { key: 3 value { id: 3 name: "not the program's" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "program" } } }
'''


@pytest.fixture
def xplane(tmp_path):
    d = tmp_path / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(tmp_path)


def test_report_joins_a_device_plane_to_the_programs_scopes(
        xplane, monkeypatch):
    monkeypatch.setitem(tm.compiles._programs, "executor:7",
                        (lambda: HLO, frozenset(SITES)))
    rep = profiler.report(xplane)
    h = rep["header"]
    assert (h["steps"], h["program"], h["scoped"]) == (2, "executor:7", True)
    assert h["session_s"] == pytest.approx(0.024)
    assert h["busy_share"] == pytest.approx(0.016 / 0.024)
    assert h["peak_bytes"] is None and h["compiled"] is None
    rows = {(r["phase"], r["op"], r["scope"], r["kernel"]): r
            for r in rep["device"]}
    assert rows["backward", "mul", "lm_head", ""]["ms_per_step"] \
        == pytest.approx(3.0)
    assert rows["forward", "layer_norm", "ln0", "layer_norm_fwd"]["calls"] == 1
    assert rows["-", "unscoped", "", "copy"]["total_ms"] == pytest.approx(1.0)
    host = {r["span"]: r for r in rep["host"]}
    assert host["executor.run"]["self_ms"] == pytest.approx(21.0)
    assert host["executor.run"]["idle_ms"] == pytest.approx(1.0 + 4.0 + 2.0)
    assert host[profiler.NO_SPAN]["idle_ms"] == pytest.approx(1.0)
    assert [r["step"] for r in rep["steps"]] == [1, 2]
    text = profiler.render(rep)
    assert "2 steps of executor:7" in text and "device busy 66.7%" in text
    assert re.search(r"backward +mul +lm_head +1 +6\.000 +3\.000", text)
    assert re.search(r"\n +layer_norm_fwd +1 +1\.000", text)
    # in a process that never compiled the program: families, and it says so
    monkeypatch.delitem(tm.compiles._programs, "executor:7")
    rep = profiler.report(xplane, "calls")
    assert not rep["header"]["scoped"]
    assert {r["op"] for r in rep["device"] if not r["kernel"]} == {"unscoped"}
    assert "NO HLO TEXT" in profiler.render(rep)


@pytest.mark.parametrize("key", ["calls", "total", "max", "min", "ave",
                                 None, "default"])
def test_report_takes_the_references_sorted_keys(xplane, key):
    rep = profiler.report(xplane, key)
    col = profiler.SORT_KEYS[key if key in profiler.SORT_KEYS else "total"]
    main = [r[col] for r in rep["device"] if not r["kernel"]]
    assert main == sorted(main, reverse=True)


def test_report_refuses_a_key_the_reference_has_not_and_an_empty_dir(
        xplane, tmp_path):
    with pytest.raises(ValueError, match="sorted_key"):
        profiler.report(xplane, "name")
    with pytest.raises(FileNotFoundError):
        profiler.report(str(tmp_path / "nothing"))


def test_a_cpu_session_prints_the_host_part_and_says_the_device_is_absent(
        tmp_path, capsys):
    loss = _toy()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    with profiler.profiler("All", "total", log_dir=str(tmp_path)):
        exe.run(fluid.default_main_program().clone(), feed=_feed(),
                fetch_list=[loss])               # compiles in the session
        for i in range(4):
            with profiler.record_event("my_region"):
                exe.run(feed=_feed(i), fetch_list=[loss])
    out = capsys.readouterr().out
    assert "Profiling Report" in out and "Device, by Fluid op: ABSENT" in out
    assert re.search(r"Session \d+\.\d+ s, 4 steps of executor:", out)
    assert "Compile runs inside the session" in out
    assert re.search(r"Compiled inside the session: executor:\d+, 1 program",
                     out)
    for span in ("profiler.session", "my_region", "executor.run",
                 "executor.fetch_readback"):
        assert re.search(rf"\n{re.escape(span)} +\d", out), span
    rep = profiler.report(str(tmp_path))
    assert rep["device"] is None and rep["header"]["busy_share"] is None
    host = {r["span"]: r for r in rep["host"]}
    assert host["my_region"]["calls"] == 4 and host["executor.run"]["calls"] == 5
    # the self times add up to the session, which the one thread spent
    # inside the session's span
    assert sum(r["self_ms"] for r in rep["host"]) \
        == pytest.approx(1e3 * rep["header"]["session_s"], rel=1e-6)
    assert len(rep["steps"]) == 5 and rep["steps"][0]["compile_run"]
    assert profiler.summary("calls")[0]["calls"] == 5


def test_a_session_reads_its_own_file_and_no_other(tmp_path, capsys):
    """The default directory is the session's own, and in a directory that
    is given, what was there before is not the session's, however new."""
    import tempfile
    seen = []
    for _ in range(2):
        with profiler.profiler("All", "total"):
            with profiler.record_event("mine"):
                pass
        seen.append(profiler.last_report()["header"]["xplane"])
    assert seen[0] != seen[1]
    assert all(os.path.dirname(p).startswith(
        os.path.join(tempfile.gettempdir(), "ptpu_prof_")) for p in seen)
    assert f"Timeline: {seen[1]}" in capsys.readouterr().out
    # a foreign file under the given directory, stamped an hour ahead
    stale = tmp_path / "plugins" / "profile" / "other" / "x.xplane.pb"
    stale.parent.mkdir(parents=True)
    stale.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ahead = os.path.getmtime(seen[1]) + 3600
    os.utime(stale, (ahead, ahead))
    assert profiler.find_xplane(str(tmp_path)) == str(stale)
    with profiler.profiler("All", "total", log_dir=str(tmp_path)):
        with profiler.record_event("mine"):
            pass
    rep = profiler.last_report()
    assert rep["header"]["xplane"] != str(stale)
    assert rep["device"] is None
    assert {r["span"] for r in rep["host"]} == {"profiler.session", "mine"}


def test_a_session_that_never_started_says_so(tmp_path, capsys):
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        with pytest.warns(RuntimeWarning, match="not started"):
            profiler.start_profiler(log_dir=str(tmp_path / "inner"))
        text = profiler.stop_profiler()
    finally:
        jax.profiler.stop_trace()
    assert "never started" in text and text in capsys.readouterr().out
    assert "without a session" in profiler.stop_profiler()


def test_tpuprof_prints_a_cells_report_and_its_rows(capsys):
    sys.path[:0] = [TINY, os.path.join(REPO, "tools")]
    try:
        import chipbench_tiny as tiny
        import tpuprof
    finally:
        del sys.path[:2]
    import tempfile
    root = tiny.make_root(tempfile.mkdtemp())
    tpuprof.main(["--cell", "nmt_train_1chip", "--seed", "4290000031",
                  "--seconds", "0.3"], root=root)
    out = capsys.readouterr().out
    assert "Profiling Report" in out
    got = json.loads(out.splitlines()[-1])
    assert got["steps"] == got["report"]["header"]["steps"] >= 1
    assert got["report"]["device"] is None and got["mul_by_site"] == {}
    assert got["benchmark_op_ms_per_step"] == {}
    # the sites' products are a part of the model's own count of the step
    assert 0 < got["sites_flops"] < got["step_flops"]
    # a tool of the program's: nothing of it poses as a benchmark run
    assert not os.path.exists(os.path.join(root, ".chipbench_trace"))
    assert not got["report"]["header"]["xplane"].startswith(root)

