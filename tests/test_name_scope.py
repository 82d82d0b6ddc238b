"""The site an op was declared at (PR 38): `op_namescope` from
`fluid.name_scope` and a layer's `name=`, through `clone()`, the bf16 cast
and the tracer's pruning, to the name stack of the compiled module, under
the op type; the three scopes of what the step emits outside any op; and
that all of it is metadata. On the CPU, toy sizes.
"""
import contextlib
import importlib
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import telemetry as tm
from paddle_tpu.core import trace as core_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "tests", "chipbench_tests")


# ------------------------------------------------ the op carries its site
def _sites(program=None):
    program = program or fluid.default_main_program()
    return [(op.type, op.attrs.get("op_namescope"))
            for op in program.global_block().ops]


def _toy(dropout=False):
    """A two-layer classifier with its sites; returns the loss."""
    img = layers.data("img", shape=[16])
    label = layers.data("label", shape=[1], dtype="int64")
    with fluid.name_scope("enc"):
        h = layers.fc(img, 8, act="relu", name="fc1")
    if dropout:         # something has to draw from the step's key
        h = layers.dropout(h, 0.5)
    pred = layers.fc(h, size=4, act="softmax", name="head")
    loss = layers.mean(layers.cross_entropy(pred, label))
    fluid.optimizer.Adam(1e-3).minimize(loss)
    return loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    return {"img": rng.randn(8, 16).astype("float32"),
            "label": rng.randint(0, 4, (8, 1))}


def test_name_scope_is_the_ops_namescope_nested_scopes_joined():
    x = layers.data("x", shape=[4])
    with fluid.name_scope("blk0"):
        a = layers.relu(x)
        with fluid.name_scope("attn.q-1"):
            layers.relu(a)
    layers.relu(x)
    assert _sites() == [("relu", "blk0"), ("relu", "blk0/attn.q-1"),
                        ("relu", None)]
    assert fluid.default_main_program().name_scopes() \
        == {"blk0", "blk0/attn.q-1"}


def test_a_layers_name_is_the_last_element_of_its_ops_namescope():
    _toy()
    assert _sites()[:6] == [
        ("mul", "enc/fc1"), ("elementwise_add", "enc/fc1"),
        ("relu", "enc/fc1"), ("mul", "head"), ("elementwise_add", "head"),
        ("softmax", "head")]
    # what the model did not name has no site: nothing is invented
    assert all(s is None for t, s in _sites()
               if t in ("cross_entropy", "mean", "backward_macro", "adam"))


@pytest.mark.parametrize("bad", ["", "a b", "a//b", "jvp(mul)", None])
def test_a_name_scope_that_cannot_stand_in_a_name_stack_is_refused(bad):
    with pytest.raises(ValueError, match="name_scope"):
        with fluid.name_scope(bad):
            pass
    # a layer's own name is the model's to choose: it is then no site
    x = layers.data("x", shape=[4])
    if bad:
        layers.fc(x, 2, name=bad)
        assert {s for _, s in _sites()} == {None}


def test_the_site_rides_clone_the_bf16_cast_and_the_tracers_pruning():
    loss = _toy()
    main = fluid.default_main_program()
    want = _sites(main)
    with fluid.name_scope("elsewhere"):      # a clone declares nothing
        test_prog = main.clone(for_test=True)
        train_prog = main.clone()
    assert _sites(train_prog) == want
    assert _sites(test_prog) == [w for w in want if w[0] not in
                                 ("backward_macro", "adam")]
    version = main._version
    fluid.amp.cast_program_to_bf16(main)
    assert _sites(main) == want and main._version == version + 1
    kept = core_trace._prune_ops(main, list(main.global_block().ops),
                                 [loss.name])
    assert [(o.type, o.attrs.get("op_namescope")) for o in kept] == want


def test_no_kernel_is_handed_the_site(monkeypatch):
    seen = []
    real = core_trace.get_kernel

    def spy(op_type):
        kern = real(op_type)

        def wrapped(ctx, ins, attrs):
            seen.append((op_type, dict(attrs)))
            return kern(ctx, ins, attrs)
        return wrapped

    monkeypatch.setattr(core_trace, "get_kernel", spy)
    with fluid.name_scope("opt"):            # the stacked Adam's ops too
        loss = _toy()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    assert {"mul", "adam"} <= {t for t, _ in seen}
    assert not any("op_namescope" in attrs for _, attrs in seen)
    # nor by the linter's shape pass, which runs the kernels abstractly
    from paddle_tpu.ops import registry
    del seen[:]
    monkeypatch.setattr(registry, "get_kernel", spy)
    fluid.default_main_program().verify(fetch_list=[loss.name])
    assert "mul" in {t for t, _ in seen}
    assert not any("op_namescope" in attrs for _, attrs in seen)


# ------------------------------------------- the tracer writes the site
def _compiled_step(loss):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(), fetch_list=[loss])
    main = fluid.default_main_program()
    return tm.compiled_text(f"executor:{main._version}")


def _stacks(text):
    """The name stacks of a module's instructions, `jit(...)` taken off."""
    return {"/".join(p for p in m.split("/") if not p.startswith("jit("))
            for m in re.findall(r'op_name="([^"]*)"', text)}


def test_the_name_stack_holds_the_site_under_the_op_type_and_the_new_scopes():
    stacks = _stacks(_compiled_step(_toy(dropout=True)))
    assert any(s.startswith("jvp(mul)/enc/fc1/") for s in stacks)
    assert any(s.startswith("transpose(jvp(mul))/head/") for s in stacks)
    # the stacked Adam keeps its one name; the key's fold_in has one now
    assert any(s.startswith("adam/") for s in stacks)
    assert any(s.startswith("rng_key/") for s in stacks)
    assert not any(s.startswith("jvp()") for s in stacks)
    sites = tm.compiles.program_sites(
        f"executor:{fluid.default_main_program()._version}")
    assert sites == {"enc/fc1", "head"}


def test_scopes_are_metadata_the_optimized_module_is_the_same(monkeypatch):
    """The step with its scopes and the step with none at all: one module,
    once `metadata={...}` is stripped."""
    def stripped(text):
        # the header's table of source lines is metadata too
        text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                      r"\n(?:\d+ .*\n)+", "\n", text)
        return re.sub(r",? ?metadata=\{[^}]*\}", "", text)

    loss = _toy()
    with_scopes = _compiled_step(loss)
    assert "enc/fc1" in with_scopes
    monkeypatch.setattr(core_trace, "op_scope",
                        lambda *a: contextlib.nullcontext())
    from paddle_tpu.core import executor as core_executor
    monkeypatch.setattr(core_executor, "op_scope",
                        lambda *a: contextlib.nullcontext())
    fluid.default_main_program()._bump_version()     # a new compile key
    without = _compiled_step(loss)
    assert "enc/fc1" not in without and "adam/" not in without
    assert stripped(with_scopes) == stripped(without)


def _tiny_cell(cfg_name, traffic_name):
    cfg = json.load(open(os.path.join(REPO, "chipbench", "configs",
                                      cfg_name + ".json")))
    cfg.update(json.load(open(os.path.join(TINY, "tiny", "configs",
                                           cfg_name + ".json"))))
    traffic = json.load(open(os.path.join(TINY, "tiny", "traffic",
                                          traffic_name + ".json")))
    model = importlib.import_module(
        "chipbench.models." + cfg.get("model", "nmt"))
    return cfg, traffic, model


@pytest.mark.parametrize("cfg_name,traffic_name,site,others", [
    ("solar_open2_250b_train_ep40_tp8", "train_b1_t8192", "lm_head",
     ["l0_q", "l0_shared_w1", "l1_a_down"]),
    ("nmt_base_train_nodrop", "train_b128_t256", "proj",
     ["enc0_qkv", "dec0_cross_kv", "dec1_ffn_fc2"]),
    ("lfm2_24b_a2b_train_ep8", "train_b2_t8192", "l0_ffn_w1",
     ["l1_q", "l2_conv_out"]),
], ids=["solar", "nmt", "lfm2"])
def test_a_benchmark_models_products_carry_their_sites_both_ways(
        cfg_name, traffic_name, site, others):
    cfg, traffic, model = _tiny_cell(cfg_name, traffic_name)
    main, startup, loss = model.build(cfg, traffic, fluid)
    fluid.amp.cast_program_to_bf16(main)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=model.make_batches(traffic, cfg, 5)[0],
            fetch_list=[loss])
    stacks = _stacks(tm.compiled_text(f"executor:{main._version}"))
    for s in [site] + others:
        assert any(x.startswith(f"jvp(mul)/{s}/") for x in stacks), s
        assert any(x.startswith(f"transpose(jvp(mul))/{s}/")
                   for x in stacks), s
    # every product of the model has a site (lfm2's tied head: `matmul`)
    assert not [op.type for op in main.global_block().ops
                if op.type in ("mul", "matmul")
                and not op.attrs.get("op_namescope")]
    # the float32 sum of the loss belongs to the loss's op
    assert not any(x.startswith("jvp()") for x in stacks)

