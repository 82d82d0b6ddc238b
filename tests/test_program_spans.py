"""The program's view of itself (PR 27): `telemetry.span` writes into the
JAX profiler's trace, `core/trace.py` scopes every op, every Pallas kernel
has a name, and `telemetry.compile_log()` says who compiled what.

All on the CPU: a profiler session here records host spans and the CPU
client's op events on one clock, which is what the tests read."""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu import telemetry as tm


@pytest.fixture(autouse=True)
def _telemetry_off():
    tm.disable()
    tm.reset()
    tm.compiles._records.clear()      # tests slice the log by length
    yield
    tm.disable()
    tm.reset()


def _session(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _events(trace_dir):
    """[(line, name, start_ns, end_ns, stats)] of the session's file."""
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events]


def _pt(events):
    return [e for e in events if e[1].startswith("pt/")]


def _train_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            x = layers.data("x", shape=[16])
            y = layers.data("y", shape=[1], dtype="int64")
            h = layers.layer_norm(layers.fc(x, 16, act="relu"),
                                  begin_norm_axis=1)
            pred = layers.fc(h, size=4, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, y))
            pt.optimizer.Adam(1e-3).minimize(loss)
    feed = {"x": np.random.rand(8, 16).astype("float32"),
            "y": np.random.randint(0, 4, (8, 1))}
    return main, startup, loss, feed


# ------------------------------------------------------------ the primitive
def test_span_lands_in_the_profilers_trace_on_the_ops_clock(tmp_path):
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    a = jnp.ones((128, 128))
    f(a).block_until_ready()
    _session(tmp_path)
    with tm.span("outer", n=3):
        with tm.span("inner", rows=2, tag="a") as sp:
            f(a).block_until_ready()
            sp.set(late=5)
    events = _events(tmp_path)
    spans = {e[1]: e for e in _pt(events)}
    assert set(spans) == {"pt/outer", "pt/inner"}
    outer, inner = spans["pt/outer"], spans["pt/inner"]
    assert outer[4] == {"n": 3}
    assert inner[4] == {"rows": 2, "tag": "a", "late": 5}
    assert outer[0] == inner[0]                       # one thread
    assert outer[2] <= inner[2] and inner[3] <= outer[3]
    ops = [e for e in events
           if str(e[4].get("hlo_module", "")).startswith("jit__lambda")]
    assert ops, "the CPU client recorded no op of the jitted call"
    for op in ops:                                    # one clock
        assert inner[2] <= op[2] and op[3] <= inner[3], (op, inner)


def test_span_with_no_session_leaves_ring_and_registry_empty():
    with tm.span("quiet", rows=1):
        pass
    assert tm.iter_spans() == [] and tm.snapshot() == {}
    tm.enable()
    with tm.span("loud", rows=1) as sp:
        sp.set(more=2)
    rec, = tm.iter_spans()
    assert rec.name == "loud" and rec.args == {"rows": 1, "more": 2}


def test_record_event_opens_one_annotation(tmp_path):
    _session(tmp_path)
    with profiler.record_event("my_region"):
        pass
    names = [e[1] for e in _events(tmp_path) if "my_region" in e[1]]
    assert names == ["pt/my_region"]


# ------------------------------------------------------- the entry layer
TABLE_B = ["executor.run", "executor.feed_put", "executor.prepare",
           "executor.compile", "executor.step", "executor.scope_write",
           "executor.fetch_readback", "executor.release"]


@pytest.mark.parametrize("cached", [False, True])
def test_executor_run_yields_the_spans_of_the_table(tmp_path, cached):
    main, startup, loss, feed = _train_program()
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        if cached:
            exe.run(main, feed=feed, fetch_list=[loss])
        _session(tmp_path)
        exe.run(main, feed=feed, fetch_list=[loss])
        spans = _pt(_events(tmp_path))
    want = [n for n in TABLE_B if not (cached and n == "executor.compile")]
    assert sorted(e[1] for e in spans) == sorted("pt/" + n for n in want)
    by = {e[1][3:]: e for e in spans}
    run = by["executor.run"]
    assert run[4]["program"] == main._version
    assert bool(run[4]["compile_run"]) is (not cached)
    for name, e in by.items():
        if name != "executor.run":
            assert run[2] <= e[2] and e[3] <= run[3], name
    order = [n for n in want if n != "executor.run"]
    assert sorted(order, key=lambda n: by[n][2]) == order
    put = by["executor.feed_put"][4]
    assert (put["feeds"], put["puts"], put["reused"]) == (2, 2, 0)
    assert put["bytes"] == sum(
        np.asarray(v).astype("float32" if k == "x" else "int32").nbytes
        for k, v in feed.items())
    n_persist = len([v for v in main.persistable_vars()])
    assert by["executor.prepare"][4]["persist"] == n_persist
    assert by["executor.release"][4]["handles"] == n_persist


def test_parallel_executor_run_is_the_parent_of_its_step(tmp_path):
    main, startup, loss, feed = _train_program()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor(pt.CPUPlace()).run(startup)
        pexe = pt.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                   main_program=main, scope=scope)
        pexe.run(fetch_list=[loss.name], feed=feed)
        _session(tmp_path)
        pexe.run(fetch_list=[loss.name], feed=feed)
        by = {e[1]: e for e in _pt(_events(tmp_path))}
    run, step = by["pt/pexe.run"], by["pt/pexe.step"]
    assert run[2] <= step[2] and step[3] <= run[3]
    assert run[4]["program"] == main._version


# ------------------------------------------------- names on the device side
def test_train_lowering_carries_the_phase_in_its_name_stacks():
    from paddle_tpu.core.trace import build_step_fn
    main, startup, loss, feed = _train_program()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor(pt.CPUPlace()).run(startup)
    persist = {v.name: scope.get(v.name) for v in main.persistable_vars()}
    step = build_step_fn(main, [loss.name], False, None)
    text = jax.jit(step).lower(
        persist, {"x": jnp.asarray(feed["x"]),
                  "y": jnp.asarray(feed["y"].astype("int32"))},
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for op in ("mul", "layer_norm", "cross_entropy"):
        assert f"/jvp({op})/" in text, op
        assert f"/transpose(jvp({op}))/" in text, op
    assert "jit(step)/adam/" in text
    assert "jvp(adam)" not in text


def test_compiled_text_joins_an_instruction_to_its_scope():
    main, startup, loss, feed = _train_program()
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
    text = tm.compiled_text(f"executor:{main._version}")
    assert text.startswith("HloModule jit_stepped")
    assert 'op_name="jit(stepped)/transpose(jvp(mul))/' in text
    assert 'op_name="jit(stepped)/adam/' in text
    assert tm.compiled_text("executor:no-such-program") is None


def _kernel_lowerings():
    from paddle_tpu.ops.pallas import embedding, flash_attention, layer_norm
    from paddle_tpu.ops.kern import decode_attention, quant
    f32, bf16 = jnp.float32, jnp.bfloat16
    x = jnp.zeros((64, 128), f32)
    g = jnp.ones((128,), f32)
    q = jnp.zeros((1, 2, 256, 64), bf16)
    # the dq of one head is over the fused backward's VMEM budget here
    # (flash_attention.FUSED_BWD_VMEM): dq and dk / dv are two kernels
    long = jax.ShapeDtypeStruct((1, 1, 131072, 64), bf16)
    ln = lambda a, s, b: layer_norm.layer_norm(a, s, b)          # noqa: E731
    fa = lambda a, b, c: flash_attention.flash_attention(a, b, c)  # noqa
    return {
        "layer_norm_fwd": (ln, (x, g, g)),
        "layer_norm_bwd": (jax.grad(lambda *a: ln(*a).sum()), (x, g, g)),
        "flash_attention_fwd": (fa, (q, q, q)),
        "flash_attention_bwd": (
            jax.grad(lambda *a: fa(*a).astype(f32).sum(), argnums=(0, 1, 2)),
            (q, q, q)),
        "flash_attention_dq": (
            jax.grad(lambda *a: fa(*a).astype(f32).sum()),
            (long, long, long)),
        "flash_attention_dkv": (
            jax.grad(lambda *a: fa(*a).astype(f32).sum(), argnums=(1, 2)),
            (long, long, long)),
        "embedding_lookup": (
            lambda t, i: embedding.lookup_pool(t, i, None),
            (jnp.zeros((512, 128), f32), jnp.zeros((64, 4), jnp.int32))),
        "decode_attention": (
            decode_attention.decode_attend,
            (jnp.zeros((8, 8, 64), f32), jnp.zeros((8, 128, 8, 64), f32),
             jnp.zeros((8, 128, 8, 64), f32), jnp.zeros((8,), jnp.int32))),
        "quant_int8": (quant.quantize_int8_pallas,
                       (jnp.zeros((8 * 256 * 4,), f32),)),
    }


@pytest.mark.parametrize("kernel", [
    "layer_norm_fwd", "layer_norm_bwd", "flash_attention_fwd",
    "flash_attention_bwd", "flash_attention_dq", "flash_attention_dkv",
    "embedding_lookup",
    "decode_attention", "quant_int8"])
def test_each_pallas_kernel_has_its_name_in_its_lowering(kernel):
    """Lowered for the TPU without one: Mosaic lowering needs no device,
    and the kernel's `name=` is the `kernel_name` of its custom call."""
    fn, args = _kernel_lowerings()[kernel]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{kernel}"' in text


# ------------------------------------------------------ compile accounting
def _mine(records, since):
    return [r for r in records[since:] if r.event.endswith(
        "backend_compile_duration")]


def test_compile_log_says_who_compiled_what():
    main, startup, loss, feed = _train_program()
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        n0 = len(tm.compile_log())
        exe.run(main, feed=feed, fetch_list=[loss])
        first = tm.compile_log()[n0:]
        owners = {r.owner for r in first}
        assert owners == {f"executor:{main._version}"}
        backend = _mine(tm.compile_log(), n0)
        assert len(backend) == 1 and backend[0].seconds > 0
        assert backend[0].fun_name == "jit(stepped)"
        # the step's own trace stands for the hundreds nested in it
        traces = [r for r in first if r.event.endswith("trace_duration")]
        assert [r.fun_name for r in traces] == ["stepped"]
        assert {r.event.rsplit("/", 1)[-1] for r in first} >= {
            "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration"}
        for r in first:                  # a phase ran up to its report
            assert r.t_end - r.seconds <= r.t_end
        n1 = len(tm.compile_log())
        exe.run(main, feed=feed, fetch_list=[loss])   # the same key again
        assert len(tm.compile_log()) == n1
    jax.jit(lambda a: a * 3 + 1)(jnp.ones((7,)))      # a stray jit
    stray = tm.compile_log()[n1:]
    assert stray and {r.owner for r in stray} == {None}
    assert tm.compiles.owner is None


@pytest.fixture(scope="module")
def tiny_engine():
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving.decode import DecodeEngine, DecodeEngineConfig
    cfg = tfm.TransformerConfig(src_vocab=32, trg_vocab=32, max_len=16,
                                d_model=16, d_inner=32, n_head=2, n_layer=1,
                                dropout=0.0)
    main, startup = pt.Program(), pt.Program()
    scope = pt.Scope()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            tfm.build_program(cfg, maxlen=8)
    with pt.scope_guard(scope):
        pt.Executor(pt.CPUPlace()).run(startup)
        return DecodeEngine.from_scope(
            scope, cfg, config=DecodeEngineConfig(
                num_slots=4, max_len=8, src_max_len=8,
                prefill_buckets=(1, 2, 4)))


def test_write_slots_and_decoder_builds_are_logged_under_their_owner(
        tiny_engine):
    eng = tiny_engine
    n0 = len(tm.compile_log())
    state = eng.init_state()
    for rows in (1, 3):
        out = eng.decoder.prefill(np.zeros((4, 8), np.int64),
                                  np.ones((4,), np.int64))
        state = eng.decoder.write_slots(state, out, list(range(rows)))
    eng.decoder.step(state, np.zeros(4, np.int64), np.zeros(4, np.int64))
    by_owner = {}
    for r in _mine(tm.compile_log(), n0):
        by_owner.setdefault(r.owner, []).append(r)
    # each row count slices and scatters at its own shapes
    assert len(by_owner["decode.write_slots"]) >= 2
    assert len(by_owner["decode.prefill:4"]) == 1
    assert len(by_owner["decode.step"]) == 1
    n1 = len(tm.compile_log())
    out = eng.decoder.prefill(np.zeros((4, 8), np.int64),
                              np.ones((4,), np.int64))
    eng.decoder.write_slots(state, out, [0, 1, 2])
    eng.decoder.step(state, np.zeros(4, np.int64), np.zeros(4, np.int64))
    assert not _mine(tm.compile_log(), n1)            # all seen before


def test_decode_result_has_one_increasing_time_per_token(tiny_engine,
                                                         tmp_path):
    from paddle_tpu.serving.decode import ContinuousScheduler, DecodeConfig
    sched = ContinuousScheduler(
        tiny_engine, config=DecodeConfig(bos=1, eos=None), warmup=False)
    futs = [sched.submit(np.arange(2, 6), max_new_tokens=n)
            for n in (3, 5)]
    _session(tmp_path)
    for _ in range(6):
        sched.run_iteration()
    spans = _pt(_events(tmp_path))
    for fut, n in zip(futs, (3, 5)):
        res = fut.result(timeout=5)
        assert len(res.tokens) == n
        assert res.token_t.shape == (n,) and res.token_t.dtype == np.float64
        assert np.all(np.diff(res.token_t) > 0)
        # the last token's time closes the request's decode
        assert res.token_t[-1] - res.token_t[0] <= res.decode_s
    names = [e[1][3:] for e in spans]
    assert names.count("serving.sched.iteration") == 6
    assert names.count("serving.decode.step") == 5     # the sixth was idle
    assert names.count("serving.decode.write_slots") == 1
    it = [e for e in spans if e[1] == "pt/serving.sched.iteration"]
    assert it[0][4] == {"active": 0, "queued": 2, "admitted": 2}
    assert it[1][4] == {"active": 2, "queued": 0, "admitted": 0}
    step = [e for e in spans if e[1] == "pt/serving.decode.step"]
    assert step[0][4] == {"slots": 4, "active": 2}
    ws, = [e for e in spans if e[1] == "pt/serving.decode.write_slots"]
    assert ws[4] == {"rows": 2}
    assert it[0][2] <= ws[2] and ws[3] <= it[0][3]


# ------------------------------------------------ profiler.device_op_times
_XSPACE = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 500000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.12 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.3 = f32[2]{0} copy(%p)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } } }
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.99 = not a device" } } }
'''


def test_device_op_times_reads_device_planes_through_profile_data(tmp_path):
    d = tmp_path / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    fam = profiler.device_op_times(str(tmp_path))
    assert fam == pytest.approx({"fusion": 3e-6, "copy": 0.5e-6})
    ops = profiler.device_op_times(str(tmp_path), family=False)
    assert ops == pytest.approx({"fusion.12": 3e-6, "copy.3": 0.5e-6})
    # and a trace recorded here, on the CPU, has no device plane: nothing
    # is read from the host's lines, and profile_step_fn says so loudly
    rec = tmp_path / "recorded"
    f = jax.jit(lambda a: a + 1)
    _session(rec)
    f(jnp.ones((4,))).block_until_ready()
    jax.profiler.stop_trace()
    assert profiler.device_op_times(str(rec)) == {}
    with pytest.raises(RuntimeError, match="no device-plane"):
        profiler.profile_step_fn(lambda: f(jnp.ones((4,))), steps=2)
