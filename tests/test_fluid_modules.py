"""Top-level fluid module parity: average, evaluator, transpilers,
quantization, slim pruning, async executor, beam-search decoder, misc
(ref tests/unittests/test_{memory_optimization_transpiler,
inference_transpiler, quantize_transpiler, async_executor, calc_memory,
op_frequence}*.py)."""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def test_weighted_average():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        avg = pt.average.WeightedAverage()
    avg.add(value=2.0, weight=1)
    avg.add(value=4.0, weight=2)
    assert avg.eval() == pytest.approx(10.0 / 3.0)


def test_memory_usage_and_op_freq():
    x = layers.data("x", shape=[784])
    y = layers.fc(x, size=10)
    loss = layers.reduce_sum(y)
    low, high, unit = pt.contrib.memory_usage(pt.default_main_program(),
                                              batch_size=32)
    assert high > low >= 0 and unit in ("B", "KB", "MB", "GB")
    uni, adj = pt.contrib.op_freq_statistic(pt.default_main_program())
    assert uni.get("mul", 0) >= 1 or uni.get("fc", 0) >= 1


def test_inference_transpiler_conv_bn_fold():
    img = layers.data("img", shape=[2, 8, 8])
    c = layers.conv2d(img, num_filters=3, filter_size=3, padding=1)
    out = layers.batch_norm(c, is_test=True)
    test_prog = pt.default_main_program().clone(for_test=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    # make bn stats non-trivial
    scope = pt.global_scope()
    for v in pt.default_main_program().list_vars():
        if "batch_norm" in v.name and v.persistable:
            val = np.asarray(scope.get(v.name))
            scope.set(v.name, np.abs(np.random.RandomState(0)
                                     .randn(*val.shape)).astype("float32")
                      + 0.5)
    xv = np.random.RandomState(1).randn(2, 2, 8, 8).astype("float32")
    before, = exe.run(test_prog, feed={"img": xv}, fetch_list=[out],
                      is_test=True)
    n_ops_before = len(test_prog.global_block().ops)
    pt.InferenceTranspiler().transpile(test_prog)
    n_ops_after = len(test_prog.global_block().ops)
    after, = exe.run(test_prog, feed={"img": xv}, fetch_list=[out],
                     is_test=True)
    assert n_ops_after < n_ops_before            # bn op removed
    np.testing.assert_allclose(before, after, rtol=2e-4, atol=2e-5)


def test_memory_optimize_remat_still_trains():
    x = layers.data("x", shape=[16])
    y = layers.data("y", shape=[1])
    h = layers.fc(x, size=32, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    pt.optimizer.SGD(0.1).minimize(loss)
    saved = pt.memory_optimize(pt.default_main_program())
    assert saved > 0
    assert pt.release_memory(pt.default_main_program()) is not None
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    xv = rng.randn(8, 16).astype("float32")
    yv = (xv.sum(1, keepdims=True) * 0.1).astype("float32")
    losses = [float(exe.run(feed={"x": xv, "y": yv},
                            fetch_list=[loss])[0]) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_quantize_transpiler_qat_and_freeze():
    x = layers.data("x", shape=[8])
    y = layers.data("y", shape=[1])
    h = layers.fc(x, size=16, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    pt.optimizer.SGD(0.05).minimize(loss)
    qt = pt.contrib.quantize.QuantizeTranspiler(weight_bits=8,
                                                activation_bits=8)
    qt.training_transpile(pt.default_main_program())
    types = [op.type for op in pt.default_main_program().global_block().ops]
    assert "fake_quantize_abs_max" in types
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    xv = rng.randn(16, 8).astype("float32")
    yv = (xv.sum(1, keepdims=True) * 0.2).astype("float32")
    losses = [float(exe.run(feed={"x": xv, "y": yv},
                            fetch_list=[loss])[0]) for _ in range(10)]
    assert losses[-1] < losses[0]      # STE gradients train through quant
    # freeze: int8 weights + dequant ops, same prediction ballpark
    test_prog = pt.default_main_program().clone(for_test=True)
    qt2 = pt.contrib.quantize.QuantizeTranspiler()
    qt2.training_transpile(test_prog)
    qt2.freeze_program(test_prog)
    types = [op.type for op in test_prog.global_block().ops]
    assert "dequantize_abs_max" in types
    out_q, = exe.run(test_prog, feed={"x": xv}, fetch_list=[pred.name],
                     is_test=True)
    assert np.isfinite(out_q).all()


def test_slim_magnitude_pruning():
    x = layers.data("x", shape=[8])
    out = layers.fc(x, size=8, bias_attr=False)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    params = pt.default_main_program().all_parameters()
    wname = params[0].name
    masks = pt.contrib.slim.prune_program(pt.default_main_program(), 0.5)
    w = np.asarray(pt.global_scope().get(wname))
    sparsity = float((w == 0).mean())
    assert 0.4 <= sparsity <= 0.6
    assert masks[wname].dtype == bool


def test_async_executor_with_data_feed_desc(tmp_path):
    # MultiSlot text file: two slots (dense feature len 4, label len 1)
    data_path = os.path.join(tmp_path, "part-0")
    rng = np.random.RandomState(0)
    with open(data_path, "w") as f:
        for i in range(6):
            feats = " ".join(str(round(v, 3)) for v in rng.randn(4))
            f.write(f"4 {feats} 1 {i % 2}\n")
    proto_path = os.path.join(tmp_path, "data.proto")
    with open(proto_path, "w") as f:
        f.write('name: "MultiSlotDataFeed"\nbatch_size: 2\n'
                'multi_slot_desc {\n'
                '  slots { name: "feat" type: "float32" is_dense: true '
                'is_used: true }\n'
                '  slots { name: "lab" type: "int64" is_dense: true '
                'is_used: true }\n}\n')
    feed = pt.DataFeedDesc(proto_path)
    assert feed.batch_size == 2 and len(feed.slots) == 2
    feat = layers.data("feat", shape=[4], append_batch_size=False)
    lab = layers.data("lab", shape=[1], dtype="int64",
                      append_batch_size=False)
    s = layers.reduce_sum(feat)
    ae = pt.AsyncExecutor()
    ae.executor.run(pt.default_startup_program())
    results = ae.run(pt.default_main_program(), feed, [data_path],
                     fetch=[s], debug=True)
    assert len(results) == 3         # 6 samples / batch 2


def test_beam_search_decoder_loop():
    import jax.numpy as jnp
    V, B, beam, T = 6, 2, 3, 5
    init = layers.data("init", shape=[B], dtype="int64",
                       append_batch_size=False)

    def step_fn(ids, states):
        # deterministic LM: always prefer token (id+1) % V; end at 4
        logits = -10.0 * jnp.ones((ids.shape[0], V))
        nxt = (ids + 1) % V
        logits = logits.at[jnp.arange(ids.shape[0]), nxt].set(0.0)
        return logits, states

    dec = pt.contrib.decoder.BeamSearchDecoder(
        init_ids=init, target_dict_dim=V, max_len=T, beam_size=beam,
        end_id=4, step_fn=step_fn)
    seqs, scores = dec.decode()
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    s, sc = exe.run(feed={"init": np.array([0, 2], "int64")},
                    fetch_list=[seqs, scores])
    assert s.shape == (B, beam, T)
    # row 0 starts at 0 → best beam emits 1,2,3,4 then stays at 4
    np.testing.assert_array_equal(s[0, 0], [1, 2, 3, 4, 4])
    # row 1 starts at 2 → 3,4 then finished
    np.testing.assert_array_equal(s[1, 0][:2], [3, 4])


def test_detection_map_evaluator():
    det = layers.data("det", shape=[1, 4, 6], dtype="float32",
                      append_batch_size=False)
    gt_label = layers.data("gl", shape=[1, 2], dtype="int32",
                           append_batch_size=False)
    gt_box = layers.data("gb", shape=[1, 2, 4], dtype="float32",
                         append_batch_size=False)
    ev = pt.evaluator.DetectionMAP(det, gt_label, gt_box, class_num=3,
                                   overlap_threshold=0.5)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    detv = np.array([[[1, 0.9, 0.1, 0.1, 0.4, 0.4],
                      [2, 0.8, 0.5, 0.5, 0.9, 0.9],
                      [-1, -1, 0, 0, 0, 0],
                      [-1, -1, 0, 0, 0, 0]]], "float32")
    m, = exe.run(feed={"det": detv,
                       "gl": np.array([[1, 2]], "int32"),
                       "gb": np.array([[[0.1, 0.1, 0.4, 0.4],
                                        [0.5, 0.5, 0.9, 0.9]]], "float32")},
                 fetch_list=[ev.get_map_var()])
    ev.update(m)
    assert float(ev.eval()[0]) == pytest.approx(1.0)


def test_net_drawer_and_default_scope():
    x = layers.data("x", shape=[4])
    layers.fc(x, size=2)
    dot = pt.net_drawer.draw_graph(pt.default_startup_program(),
                                   pt.default_main_program())
    assert "digraph" in dot and "fc" in dot or "mul" in dot
    from paddle_tpu.default_scope_funcs import (enter_local_scope,
                                                leave_local_scope,
                                                get_cur_scope,
                                                scoped_function)
    outer = get_cur_scope()
    enter_local_scope()
    assert get_cur_scope() is not outer
    leave_local_scope()
    assert get_cur_scope() is outer
    called = []
    scoped_function(lambda: called.append(1))
    assert called == [1]


def test_training_decoder_teacher_forcing():
    B, T, D = 2, 4, 3
    emb = layers.data("emb", shape=[B, T, D], dtype="float32",
                      append_batch_size=False)
    init = layers.data("h0", shape=[B, D], dtype="float32",
                       append_batch_size=False)
    cell = pt.contrib.decoder.StateCell(
        inputs={"x": None}, states={"h": pt.contrib.decoder.InitState(init)},
        out_state="h")

    @cell.state_updater
    def updater(c):
        x = c.get_input("x")
        h = c.get_state("h")
        c.set_state("h", layers.elementwise_add(h, x))

    dec = pt.contrib.decoder.TrainingDecoder(cell)
    with dec.block():
        x = dec.step_input(emb)
        cell.compute_state(inputs={"x": x})
        cell.update_states()
        dec.output(cell.get_state("h"))
    out = dec()
    rng = np.random.RandomState(0)
    ev = rng.randn(B, T, D).astype("float32")
    h0 = rng.randn(B, D).astype("float32")
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    res, = exe.run(feed={"emb": ev, "h0": h0}, fetch_list=[out])
    want = h0[:, None, :] + np.cumsum(ev, axis=1)
    np.testing.assert_allclose(res, want, rtol=1e-5)


def test_compat_helpers():
    from paddle_tpu import compat as cpt
    assert cpt.to_text(b"abc") == "abc"
    assert cpt.to_text(["a", b"b"]) == ["a", "b"]
    assert cpt.to_bytes("abc") == b"abc"
    s = {b"x", "y"}
    assert cpt.to_text(s, inplace=True) is s and s == {"x", "y"}
    # half-away-from-zero, not banker's
    assert cpt.round(0.5) == 1.0
    assert cpt.round(-0.5) == -1.0
    assert cpt.round(2.675, 2) == pytest.approx(2.68)
    assert cpt.floor_division(7, 2) == 3
    assert cpt.get_exception_message(ValueError("boom")) == "boom"


def test_top_level_batch_keeps_tail():
    # reference default drop_last=False: tail batch is yielded
    r = pt.batch(lambda: iter(range(5)), 2)
    assert [list(b) for b in r()] == [[0, 1], [2, 3], [4]]
    with pytest.raises(ValueError):
        pt.batch(lambda: iter(range(5)), 0)


def test_annotations_deprecated_decorator():
    from paddle_tpu.annotations import deprecated

    @deprecated(since="1.0", instead="new_api")
    def old_api(v):
        return v + 1

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert old_api(1) == 2
    assert any("deprecated since 1.0" in str(x.message) for x in w)
    assert "new_api" in old_api.__doc__


def test_graphviz_dot_builder(tmp_path):
    from paddle_tpu.graphviz import Graph, GraphPreviewGenerator
    g = Graph("net", rankdir="LR")
    a = g.add_node("fc_w", shape="ellipse")
    b = g.add_node("matmul", shape="rect")
    g.add_edge(a, b, color="blue")
    g.rank_group("same", [a, b])
    code = g.code()
    assert 'digraph "net"' in code and "-> " in code and "rank=same" in code
    out = g.compile(str(tmp_path / "net.dot"))
    assert os.path.exists(out)
    gen = GraphPreviewGenerator("preview")
    op = gen.add_op("conv2d")
    arg = gen.add_arg("conv2d.w_0", is_param=True)
    gen.add_edge(arg, op)
    assert "conv2d" in gen.graph.code()


def test_inferencer_shim_reexports():
    from paddle_tpu.inferencer import Inferencer
    assert Inferencer is pt.Inferencer


def test_reference_module_import_paths():
    """paddle.fluid.{framework,executor,parallel_executor,backward} are
    real modules in the reference; the same import paths must work
    after the s/paddle.fluid/paddle_tpu/ swap."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import Program, default_main_program
    from paddle_tpu.executor import Executor, global_scope
    from paddle_tpu.parallel_executor import ParallelExecutor
    from paddle_tpu.backward import append_backward
    assert fluid.framework.Program is Program
    assert fluid.executor.Executor is Executor
    assert fluid.parallel_executor.ParallelExecutor is ParallelExecutor
    assert callable(append_backward) and callable(global_scope)
    assert default_main_program() is not None


def test_as_numpy_and_fetch_var():
    """ref executor.py module-level helpers: as_numpy converts fetched
    values (raising on LoD-carrying tensors) and _fetch_var reads a
    persistable var from the scope by name."""
    import numpy as np
    import pytest
    import paddle_tpu as fluid
    from paddle_tpu.executor import as_numpy, _fetch_var
    from paddle_tpu.lod import LoDTensor

    out = as_numpy([np.arange(3), LoDTensor(np.ones((2, 2)))])
    assert isinstance(out, list) and out[1].shape == (2, 2)
    with pytest.raises(RuntimeError):
        as_numpy(LoDTensor(np.ones((3, 2)), seq_lens=[1, 2]))

    x = fluid.layers.data("x", shape=[4])
    fluid.layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    pname = [v.name for v in
             fluid.default_main_program().persistable_vars()][0]
    assert _fetch_var(pname).shape == (4, 2)
    with pytest.raises(AssertionError):
        _fetch_var("nonexistent_var_xyz")


def test_data_feeder_decorate_reader():
    """ref data_feeder.py:decorate_reader — single- and multi-device
    wrapping produce ready feed dicts (mesh shards the batch axis, so
    the multi-device variant concatenates the per-place batches)."""
    import numpy as np
    import paddle_tpu as fluid

    x = fluid.layers.data("dx", shape=[3])
    y = fluid.layers.data("dy", shape=[1], dtype="int64")
    feeder = fluid.DataFeeder(feed_list=[x, y], place=fluid.CPUPlace())

    def rdr():
        for i in range(4):
            yield [(np.full(3, i, "float32"), np.array([i])) for _ in
                   range(2)]

    single = list(feeder.decorate_reader(rdr, multi_devices=False)())
    assert len(single) == 4 and single[0]["dx"].shape == (2, 3)

    multi = list(feeder.decorate_reader(rdr, multi_devices=True,
                                        num_places=2)())
    assert len(multi) == 2 and multi[0]["dx"].shape == (4, 3)


def test_async_executor_multi_thread(tmp_path):
    """thread_num > 1: multiple parser threads feed the queue; every
    sample from every file shard is trained on exactly once."""
    rng = np.random.RandomState(1)
    paths = []
    for p in range(4):
        path = os.path.join(tmp_path, f"part-{p}")
        with open(path, "w") as f:
            for i in range(4):
                feats = " ".join(str(round(v, 3)) for v in rng.randn(4))
                f.write(f"4 {feats} 1 {i % 2}\n")
        paths.append(path)
    proto_path = os.path.join(tmp_path, "data.proto")
    with open(proto_path, "w") as f:
        f.write('name: "MultiSlotDataFeed"\nbatch_size: 2\n'
                'multi_slot_desc {\n'
                '  slots { name: "mfeat" type: "float32" is_dense: true '
                'is_used: true }\n'
                '  slots { name: "mlab" type: "int64" is_dense: true '
                'is_used: true }\n}\n')
    feed = pt.DataFeedDesc(proto_path)
    feat = layers.data("mfeat", shape=[4], append_batch_size=False)
    lab = layers.data("mlab", shape=[1], dtype="int64",
                      append_batch_size=False)
    s = layers.reduce_sum(feat)
    ae = pt.AsyncExecutor()
    ae.executor.run(pt.default_startup_program())
    results = ae.run(pt.default_main_program(), feed, paths,
                     thread_num=3, fetch=[s], debug=True)
    assert len(results) == 8         # 16 samples / batch 2


def test_async_executor_worker_error_surfaces(tmp_path):
    """A malformed line in one shard must raise, not silently drop the
    shard's remaining data (worker errors propagate to the consumer)."""
    import pytest
    good = os.path.join(tmp_path, "good-0")
    bad = os.path.join(tmp_path, "bad-0")
    with open(good, "w") as f:
        for i in range(4):
            f.write("2 0.5 0.5 1 0\n")
    with open(bad, "w") as f:
        f.write("2 0.5 oops 1 0\n")
    proto_path = os.path.join(tmp_path, "data.proto")
    with open(proto_path, "w") as f:
        f.write('name: "MultiSlotDataFeed"\nbatch_size: 2\n'
                'multi_slot_desc {\n'
                '  slots { name: "efeat" type: "float32" is_dense: true '
                'is_used: true }\n'
                '  slots { name: "elab" type: "int64" is_dense: true '
                'is_used: true }\n}\n')
    feed = pt.DataFeedDesc(proto_path)
    feat = layers.data("efeat", shape=[2], append_batch_size=False)
    lab = layers.data("elab", shape=[1], dtype="int64",
                      append_batch_size=False)
    s = layers.reduce_sum(feat)
    ae = pt.AsyncExecutor()
    ae.executor.run(pt.default_startup_program())
    with pytest.raises(Exception):
        ae.run(pt.default_main_program(), feed, [good, bad],
               thread_num=2, fetch=[s], debug=True)


def test_utils_ploter(tmp_path, monkeypatch):
    """paddle.utils.plot.Ploter (book demos): record, draw headless
    (Agg) to a file, reset — plus the call-time DISABLE_PLOT knob."""
    import paddle_tpu as pt_pkg
    from paddle_tpu.utils.plot import Ploter
    assert pt_pkg.utils.plot.Ploter is Ploter  # pt.utils exposed
    monkeypatch.delenv("DISABLE_PLOT", raising=False)
    p = Ploter("train", "test")
    for i in range(3):
        p.append("train", i, 1.0 / (i + 1))
    p.append("test", 0, 1.2)
    path = os.path.join(tmp_path, "curve.png")
    p.plot(path)
    if p._pyplot() is not None:
        assert os.path.exists(path)
    p.reset()
    assert p.__plot_data__["train"].step == []
    # plotting with nothing recorded writes no file (and no warning)
    p3 = Ploter("empty")
    empty_path = os.path.join(tmp_path, "empty.png")
    p3.plot(empty_path)
    assert not os.path.exists(empty_path)
    # knob is captured at construction (reference behavior)
    monkeypatch.setenv("DISABLE_PLOT", "True")
    p2 = Ploter("x")
    p2.append("x", 0, 1.0)
    none_path = os.path.join(tmp_path, "none.png")
    p2.plot(none_path)
    assert not os.path.exists(none_path)


def test_is_compiled_with_cuda_compat():
    """ref core.is_compiled_with_cuda: the device-branch predicate. It
    asks the backend: False on the CPU test config, so reference
    programs branch to CPUPlace here and to CUDAPlace→TPUPlace where
    JAX holds a TPU."""
    from paddle_tpu import core
    assert core.is_compiled_with_cuda() is False  # conftest forces cpu
    assert core.is_compiled_with_tpu() is False


def test_explicit_accelerator_place_needs_the_chip():
    """An explicit TPUPlace/CUDAPlace never resolves to another
    device: no TPU backend, or an id past the last device, raises.
    Executor() with no place keeps choosing (CPU here)."""
    import pytest
    import paddle_tpu as fluid
    for place in (fluid.TPUPlace(0), fluid.CUDAPlace(0)):
        with pytest.raises(RuntimeError, match="no 'tpu' backend"):
            place.jax_device()
    assert fluid.CPUPlace().jax_device().platform == "cpu"
    with pytest.raises(RuntimeError, match="TPUPlace"):
        fluid.Executor(fluid.TPUPlace(0)).run(
            fluid.default_startup_program())
    assert fluid.Executor().place == fluid.CPUPlace()

    class _NinthCpu(fluid.CPUPlace):
        def __init__(self):
            fluid.core.place.Place.__init__(self, 8)
    with pytest.raises(RuntimeError, match="8 local 'cpu' device"):
        _NinthCpu().jax_device()      # conftest gives 8: ids 0..7


def test_async_executor_native_parser_matches_python(tmp_path):
    """native/multislot.cc vs the python tokenizer: identical sample
    content, including ragged (variable-length) sparse slots and an
    unused slot that must be skipped."""
    import paddle_tpu.async_executor as ax
    from paddle_tpu import native as pt_native
    if pt_native.lib() is None:
        import pytest
        pytest.skip("native library unavailable")
    rng = np.random.RandomState(3)
    data_path = os.path.join(tmp_path, "part-0")
    with open(data_path, "w") as f:
        for i in range(7):
            n = rng.randint(1, 5)
            ids = " ".join(str(rng.randint(0, 100)) for _ in range(n))
            feats = " ".join(str(round(v, 4)) for v in rng.randn(3))
            skip = "2 9 9"
            # last line WITHOUT trailing newline: the C parser must not
            # scan past its buffer on the file's final token
            tail = "\n" if i < 6 else ""
            f.write(f"{n} {ids} {skip} 3 {feats} 1 {i % 2}{tail}")
    proto_path = os.path.join(tmp_path, "data.proto")
    with open(proto_path, "w") as f:
        f.write('name: "MultiSlotDataFeed"\nbatch_size: 3\n'
                'multi_slot_desc {\n'
                '  slots { name: "ids" type: "uint64" is_dense: false '
                'is_used: true }\n'
                '  slots { name: "junk" type: "uint64" is_dense: false '
                'is_used: false }\n'
                '  slots { name: "feat" type: "float32" is_dense: true '
                'is_used: true }\n'
                '  slots { name: "lab" type: "int64" is_dense: true '
                'is_used: true }\n}\n')
    feed = pt.DataFeedDesc(proto_path)
    ae = pt.AsyncExecutor()

    native = ae._parse_file_native(data_path, feed)
    assert native is not None, "native parser did not engage"
    samples, slot_data = native
    assert samples == 7
    py_samples = list(ae._parse_file(data_path, feed))
    assert len(py_samples) == 7
    for j in range(3):
        vals, lens = slot_data[j]
        off = 0
        for i, s in enumerate(py_samples):
            n = s[j].shape[0]
            assert lens[i] == n
            np.testing.assert_allclose(vals[off:off + n], s[j],
                                       rtol=1e-6)
            off += n
        assert off == vals.shape[0]


def test_async_executor_uint64_feasigns_bitcast_both_paths(tmp_path):
    """ADVICE r5 regression: uint64 feasigns >= 2^63 must BIT-CAST to
    int64 two's-complement on BOTH parse paths (the reference's
    uint64_t semantics). The native parser used strtoll, silently
    clamping to INT64_MAX with the endptr guard never firing, while
    the python path raised OverflowError — breaking the documented
    'batch stream is byte-identical whether or not the native library
    built' guarantee for large sparse ids. Tokens past uint64 range
    must error on both paths."""
    import paddle_tpu.async_executor as ax
    from paddle_tpu import native as pt_native

    big = [2 ** 63, 2 ** 64 - 1, 2 ** 63 + 12345, 7, 0]
    want = np.array([v - (1 << 64) if v >= (1 << 63) else v
                     for v in big], dtype=np.int64)
    data_path = os.path.join(tmp_path, "part-0")
    with open(data_path, "w") as f:
        f.write(f"{len(big)} " + " ".join(str(v) for v in big)
                + " 1 1\n")
    proto_path = os.path.join(tmp_path, "data.proto")
    with open(proto_path, "w") as f:
        f.write('name: "MultiSlotDataFeed"\nbatch_size: 2\n'
                'multi_slot_desc {\n'
                '  slots { name: "ids" type: "uint64" is_dense: false '
                'is_used: true }\n'
                '  slots { name: "lab" type: "int64" is_dense: true '
                'is_used: true }\n}\n')
    feed = pt.DataFeedDesc(proto_path)
    ae = pt.AsyncExecutor()

    (py_ids, py_lab), = list(ae._parse_file(data_path, feed))
    assert py_ids.dtype == np.int64
    np.testing.assert_array_equal(py_ids, want)

    if pt_native.lib() is not None:
        samples, slot_data = ae._parse_file_native(data_path, feed)
        assert samples == 1
        vals, lens = slot_data[0]
        assert lens[0] == len(big)
        np.testing.assert_array_equal(vals, want)

    # out-of-uint64-range errors on both paths (no silent wrap)
    bad_path = os.path.join(tmp_path, "part-bad")
    with open(bad_path, "w") as f:
        f.write(f"1 {2 ** 64} 1 0\n")
    with pytest.raises(ValueError):
        list(ae._parse_file(bad_path, feed))
    if pt_native.lib() is not None:
        with pytest.raises(ValueError):
            ae._parse_file_native(bad_path, feed)


def test_async_executor_batch_stream_native_vs_python(tmp_path):
    """The batch stream must be identical whether the native parser
    engaged or not — partial batches carry across files in both paths
    (7+7 samples at batch 3 -> 3,3,3,3,2). Exercises run()'s real
    parse_shard via AsyncExecutor.run in both modes."""
    rng = np.random.RandomState(5)
    paths = []
    for fidx in range(2):
        p = os.path.join(tmp_path, f"part-{fidx}")
        with open(p, "w") as f:
            for i in range(7):
                feats = " ".join(str(round(v, 4)) for v in rng.randn(2))
                f.write(f"2 {feats} 1 {i % 2}\n")
        paths.append(p)
    proto_path = os.path.join(tmp_path, "data.proto")
    with open(proto_path, "w") as f:
        f.write('name: "MultiSlotDataFeed"\nbatch_size: 3\n'
                'multi_slot_desc {\n'
                '  slots { name: "nfeat" type: "float32" is_dense: true '
                'is_used: true }\n'
                '  slots { name: "nlab" type: "int64" is_dense: true '
                'is_used: true }\n}\n')
    feed = pt.DataFeedDesc(proto_path)

    def run_once(force_python):
        from paddle_tpu.core import framework as fw, scope as sc
        fw._main_program, fw._startup_program = fw.Program(), fw.Program()
        sc._global_scope = sc.Scope()
        feat = layers.data("nfeat", shape=[2], append_batch_size=False)
        lab = layers.data("nlab", shape=[1], dtype="int64",
                          append_batch_size=False)
        s = layers.reduce_sum(feat)
        ae = pt.AsyncExecutor()
        if force_python:
            ae._parse_file_native = lambda *a, **k: None
        ae.executor.run(pt.default_startup_program())
        return ae.run(pt.default_main_program(), feed, paths,
                      fetch=[s], debug=True)

    native_r = run_once(False)
    python_r = run_once(True)
    # 14 samples at batch 3 with cross-file carry -> 5 batches
    assert len(native_r) == len(python_r) == 5
    for nb, pb in zip(native_r, python_r):
        np.testing.assert_allclose(np.asarray(nb[0]), np.asarray(pb[0]),
                                   rtol=1e-6)


def test_transpiler_details_helpers(tmp_path):
    """ref transpiler/details/{program_utils,ufind,checkport}."""
    from paddle_tpu.transpiler import details as D

    x = layers.data("dx", shape=[4])
    h = layers.fc(x, 3)
    out = layers.relu(h)
    block = pt.default_main_program().global_block()
    i_h = D.find_op_by_output_arg(block, h.name)
    assert i_h >= 0
    assert D.find_op_by_input_arg(block, h.name) > i_h
    assert D.find_op_by_output_arg(block, "nope") == -1
    relu_ops = [op for op in block.ops if op.type == "relu"]
    n_before = len(block.ops)
    D.delete_ops(block, relu_ops)
    assert len(block.ops) == n_before - 1
    assert all(op.type != "relu" for op in block.ops)

    uf = D.UnionFind(["a", "b", "c"])
    assert not uf.is_connected("a", "b")
    uf.union("a", "b")
    uf.union("b", "c")
    assert uf.is_connected("a", "c")
    assert uf.find("zzz") == -1
    uf.union("new1", "new2")
    assert uf.is_connected("new1", "new2")

    # checkport: a live local listener is detected; a dead port times out
    import socket
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    D.wait_server_ready([f"127.0.0.1:{port}"], timeout_s=5)
    srv.close()
    import pytest
    with pytest.raises(TimeoutError):
        D.wait_server_ready(["127.0.0.1:1"], timeout_s=0.1,
                            poll_interval=0.05)
