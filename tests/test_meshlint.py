"""meshlint — the whole-program sharding & collective static verifier.

Per-pass seeded-defect fixtures (each pass fires with the right
location and verdict), the shared ckey vocabulary regression (static
diagnostics and the runtime recompile explainer must name components
with the SAME words), the green control set (the passing parallel
tests' configs, the 18 multichip ones among them, lint silent), the
executor/farm verify() gates, and the tpulint CLI."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import meshlint as ml
from paddle_tpu.analysis.diagnostics import ProgramVerificationError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _errors(diags):
    return [d for d in diags if d.severity == "error"]


def _of_pass(diags, name):
    return [d for d in diags if d.pass_name == name]


def _mlp_program(feed_shape=(8,)):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data("x", shape=list(feed_shape))
            label = layers.data("label", shape=[1], dtype="int64")
            pred = layers.fc(x, size=4, act="softmax")
            loss = layers.mean(
                layers.cross_entropy(input=pred, label=label))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


# ------------------------------------------------------------ mesh-spec
def test_spec_unknown_axis_and_divisibility():
    mesh = ml.MeshSpec({"dp": 4, "tp": 2})
    use = ml.ShardMapUse("u", in_specs=[("xx",), ("dp", "tp")],
                         arg_shapes=[(8,), (6, 4)])
    diags = ml.run_mesh_passes(ml.MeshLintContext(mesh, uses=[use]),
                               passes=["mesh-spec"])
    errs = _errors(diags)
    assert any("names axis 'xx'" in d.message for d in errs)
    assert any("does not divide" in d.message for d in errs)
    # messages carry the call site and the argument
    assert all("shard_map 'u'" in d.message for d in errs)


def test_spec_rank_too_long():
    mesh = ml.MeshSpec({"dp": 2})
    use = ml.ShardMapUse("u", in_specs=[("dp", None, None)],
                         arg_shapes=[(4, 4)])
    errs = _errors(ml.run_mesh_passes(
        ml.MeshLintContext(mesh, uses=[use]), passes=["mesh-spec"]))
    assert len(errs) == 1 and "longer (rank 3)" in errs[0].message


def test_static_spec_verdict_pure():
    mesh = ml.MeshSpec({"dp": 2, "tp": 2})
    ok, reasons = ml.static_spec_verdict(mesh, ("dp", "tp"), (4, 4))
    assert ok and not reasons
    ok, reasons = ml.static_spec_verdict(mesh, (("dp", "tp"),), (6,))
    assert not ok and "dp*tp" in reasons[0]


def test_inner_vjp_scan_not_flagged():
    """The 1F1B shape — vjp INSIDE the body, no boundary transpose —
    must stay quiet (test_1f1b_trains is green)."""
    mesh = ml.MeshSpec({"pp": 4})
    use = ml.ShardMapUse(
        "pipeline.1f1b", in_specs=[("pp",), ()],
        out_specs=[(), ("pp",)])
    assert not _errors(ml.run_mesh_passes(
        ml.MeshLintContext(mesh, uses=[use])))


def test_axis_reuse_is_an_error():
    """jax binds a mesh axis to at most one dimension of one value:
    reuse within one spec fails at trace time, so it is an ERROR
    (tests/test_meshlint_property.py holds the rule to jax.shard_map)."""
    mesh = ml.MeshSpec({"dp": 2, "tp": 2})
    use = ml.ShardMapUse("u", in_specs=[("dp", "dp"), ("dp", "tp")],
                         arg_shapes=[(4, 4), (4, 4)])
    errs = _errors(ml.run_mesh_passes(
        ml.MeshLintContext(mesh, uses=[use]), passes=["mesh-spec"]))
    assert len(errs) == 1
    assert "uses axis 'dp' more than once" in errs[0].message
    assert "input 'arg0'" in errs[0].message


# ------------------------------------------- collective-consistency
def test_member_policy_divergence():
    mctx = ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}),
        member_policies=["int8:bucket_mb=4", "int8:bucket_mb=1"])
    errs = _errors(ml.run_mesh_passes(
        mctx, passes=["collective-consistency"]))
    assert len(errs) == 1 and "deadlock" in errs[0].message
    # identical policies: quiet
    assert not _errors(ml.run_mesh_passes(
        ml.MeshLintContext(ml.MeshSpec({"dp": 2}),
                           member_policies=["int8", "int8"]),
        passes=["collective-consistency"]))


def test_policy_grammar_errors():
    mctx = ml.MeshLintContext(ml.MeshSpec({"dp": 2}),
                              grad_sync="int7:wat=1")
    errs = _errors(ml.run_mesh_passes(
        mctx, passes=["collective-consistency"]))
    assert any("does not parse" in d.message for d in errs)
    mctx = ml.MeshLintContext(ml.MeshSpec({"dp": 2}),
                              sparse="shard:stale=banana")
    errs = _errors(ml.run_mesh_passes(
        mctx, passes=["collective-consistency"]))
    assert any("sparse policy grammar" in d.message for d in errs)


def test_gradsync_needs_dp_axis():
    mctx = ml.MeshLintContext(ml.MeshSpec({"tp": 4}), grad_sync="fp32")
    errs = _errors(ml.run_mesh_passes(
        mctx, passes=["collective-consistency"]))
    assert len(errs) == 1 and "'dp'" in errs[0].message


def test_pipeline_schedule_sanity():
    mctx = ml.MeshLintContext(ml.MeshSpec({"dp": 2}),
                              pipeline_schedule="2f2b")
    msgs = [d.message for d in _errors(ml.run_mesh_passes(
        mctx, passes=["collective-consistency"]))]
    assert any("unknown pipeline schedule" in m for m in msgs)
    assert any("needs a 'pp' axis" in m for m in msgs)


def test_conditional_collective_deadlock():
    """A distributed lookup_table inside a cond branch: members whose
    predicate differs skip the engine's all-to-all — ERROR."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        with fluid.unique_name.guard():
            ids = layers.data("ids", shape=[1], dtype="int64")
            flag = layers.data("flag", shape=[1], dtype="bool")

            def true_fn():
                return layers.embedding(
                    ids, size=(64, 8), is_sparse=True,
                    is_distributed=True)

            def false_fn():
                return layers.fill_constant([1, 8], "float32", 0.0)

            layers.cond(flag, true_fn, false_fn)
    mctx = ml.MeshLintContext(ml.MeshSpec({"dp": 2}), program=main,
                              sparse="shard")
    errs = _of_pass(_errors(ml.run_mesh_passes(
        mctx, passes=["collective-consistency"])),
        "collective-consistency")
    assert any("deadlock" in d.message and d.op_type == "lookup_table"
               for d in errs)
    # no parallel policy -> no collective lowering -> quiet
    assert not _errors(ml.run_mesh_passes(
        ml.MeshLintContext(ml.MeshSpec({"dp": 2}), program=main),
        passes=["collective-consistency"]))


# ---------------------------------------------- donation-aliasing
def test_fetch_of_donated_state():
    main, _, _ = _mlp_program()
    param = next(v.name for v in main.list_vars() if v.persistable)
    # synchronous: warning; async: error
    warns = ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, fetch_names=[param]),
        passes=["donation-aliasing"])
    assert any(d.severity == "warning" and param in d.message
               for d in warns)
    errs = _errors(ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, fetch_names=[param],
        async_steps=2), passes=["donation-aliasing"]))
    assert len(errs) == 1 and "donated" in errs[0].message


def test_feed_written_by_op_is_identity_cache_hazard():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data("x", shape=[8])
        blk = main.global_block()
        blk.append_op("relu", {"X": [x]}, {"Out": [x.name]}, {})
    errs = _errors(ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, feed_names=["x"]),
        passes=["donation-aliasing"]))
    assert len(errs) == 1 and "id(array)" in errs[0].message


# ---------------------------------------------- device-footprint
def test_footprint_estimate_and_cap():
    main, _, _ = _mlp_program()
    diags = ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main),
        passes=["device-footprint"])
    infos = [d for d in diags if d.severity == "info"]
    assert len(infos) == 1 and "per-member state floor" in \
        infos[0].message
    assert not _errors(diags)
    # a 1-byte cap must blow up, naming the largest params
    errs = _errors(ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, memory_cap_bytes=1),
        passes=["device-footprint"]))
    assert len(errs) == 1 and "OOM" in errs[0].message


def test_footprint_sharding_divides_bytes():
    main, _, _ = _mlp_program()
    from paddle_tpu.analysis.meshlint.footprint import member_footprint
    base = member_footprint(ml.MeshLintContext(
        ml.MeshSpec({"tp": 4}), program=main))
    specs = {v.name: ("tp", None)
             for v in main.list_vars()
             if v.persistable and len(v.shape) == 2}
    shard = member_footprint(ml.MeshLintContext(
        ml.MeshSpec({"tp": 4}), program=main, param_specs=specs))
    assert shard["params"] < base["params"]
    # optimizer slots shard with their params
    assert shard["optimizer"] <= base["optimizer"]


def test_footprint_counts_gradsync_error_feedback():
    main, _, _ = _mlp_program()
    from paddle_tpu.analysis.meshlint.footprint import member_footprint
    off = member_footprint(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main))
    on = member_footprint(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, grad_sync="int8"))
    assert off["gradsync_ef"] == 0
    assert on["gradsync_ef"] > 0
    assert on["total"] == off["total"] + on["gradsync_ef"]


# ------------------------------------------ mesh-recompile-hazard
def test_recompile_hazard_shares_explainer_vocabulary():
    """THE satellite pin: the static hazard and the runtime recompile
    explainer name the ckey component with the same words, from the
    same table (telemetry/ckey_vocab.py)."""
    from paddle_tpu.telemetry import attribution, ckey_vocab

    # one table object, not two copies that can drift
    assert attribution._COMPONENT is ckey_vocab.COMPONENT
    assert ckey_vocab.component_name("feed_signature") == "shape bucket"

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        layers.data("tokens", shape=[8, -1])  # non-leading wildcard
    diags = ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, feed_names=["tokens"]),
        passes=["mesh-recompile-hazard"])
    warns = [d for d in diags if d.severity == "warning"]
    assert len(warns) == 1
    static_msg = warns[0].message

    # runtime: a feed_signature change explained by explain_recompile
    old = {"feed_signature": (("tokens", (4, 8, 3), "float32"),)}
    new = {"feed_signature": (("tokens", (4, 8, 9), "float32"),)}
    out = attribution.explain_recompile("pexe", new, [old], step=1)
    assert out["components"] == ["shape bucket"]
    # the SAME component phrase appears in both outputs
    assert "shape bucket" in static_msg
    assert "shape bucket" in out["detail"]
    # and the vocabulary formatter is what produced the detail
    assert out["detail"] == ckey_vocab.fmt_field(
        "feed_signature", old["feed_signature"],
        new["feed_signature"])


def test_recompile_hazard_leading_batch_is_info():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        layers.data("x", shape=[8])  # (-1, 8): leading wildcard only
    diags = ml.run_mesh_passes(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=main, feed_names=["x"]),
        passes=["mesh-recompile-hazard"])
    assert not _errors(diags)
    assert all(d.severity == "info" for d in diags)


# ------------------------------------------------- control set
def test_green_configs_zero_false_positives():
    greens = ml.green_configs()
    assert len(greens) == 25
    # the 18 multichip tests whose configs are spelled out one by one
    assert sum(label.startswith("tests/") for label, _ in greens) == 18
    for label, mctx in greens:
        errs = _errors(ml.run_mesh_passes(mctx))
        assert not errs, (label, [d.message for d in errs])


# ------------------------------------------------- executor gates
def _run_pexe(validate=None, fetch_param=False, **pexe_kw):
    from paddle_tpu.parallel.parallel_executor import ParallelExecutor
    main, startup, loss = _mlp_program()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        pexe = ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=main, **pexe_kw)
        fetch = [loss.name]
        if fetch_param:
            fetch.append(next(v.name for v in main.list_vars()
                              if v.persistable))
        out = pexe.run(
            fetch_list=fetch,
            feed={"x": np.random.rand(8, 8).astype("float32"),
                  "label": np.random.randint(0, 4, (8, 1))},
            validate=validate)
    return out


def test_pexe_verify_clean_and_gate_runs():
    out = _run_pexe(validate=True)
    assert np.isfinite(float(np.asarray(out[0]).ravel()[0]))


def test_pexe_verify_method_reports():
    from paddle_tpu.parallel.parallel_executor import ParallelExecutor
    main, startup, loss = _mlp_program()
    pexe = ParallelExecutor(use_cuda=False, loss_name=loss.name,
                            main_program=main)
    diags = pexe.verify(fetch_list=[loss.name], feed_names=["x"])
    assert not _errors(diags)
    # seeded defect: an absurd memory cap must raise through verify()
    with pytest.raises(ProgramVerificationError) as ei:
        pexe.verify(fetch_list=[loss.name], memory_cap_bytes=1)
    assert any(d.pass_name == "device-footprint"
               for d in ei.value.diagnostics)


def test_farm_config_verify():
    from paddle_tpu.serving.farm import FarmConfig
    from paddle_tpu.serving.decode import DecodeEngineConfig
    assert not _errors(FarmConfig().verify())
    bad = FarmConfig(engine=DecodeEngineConfig(kv_quant="int4"))
    with pytest.raises(ProgramVerificationError):
        bad.verify(raise_on_error=True)
    # KV footprint rides the device-footprint pass
    import types
    mc = types.SimpleNamespace(hidden=64, layers=4, max_len=128)
    diags = FarmConfig(engine=DecodeEngineConfig(num_slots=8,
                                                 max_len=128)) \
        .verify(model_config=mc)
    assert any("per-member state floor" in d.message for d in diags)


def test_verify_mesh_raises_and_unknown_pass():
    mctx = ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}),
        uses=[ml.ShardMapUse("u", in_specs=[("xx",)])])
    with pytest.raises(ProgramVerificationError):
        ml.verify_mesh(mctx, raise_on_error=True)
    with pytest.raises(ValueError):
        ml.run_mesh_passes(mctx, passes=["no-such-pass"])


# ------------------------------------------------------ tpulint CLI
def test_tpulint_selftest_subprocess():
    """The tier-1 wiring (tpudoctor pattern): last stdout line is the
    JSON verdict and every check holds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpulint.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=480, env=env)
    assert p.returncode == 0, (p.stdout[-800:], p.stderr[-800:])
    obj = json.loads(p.stdout.strip().splitlines()[-1])
    assert obj["ok"] is True
    assert all(obj["checks"].values()), obj["checks"]


def test_validate_off_never_imports_meshlint():
    """Bench-contract pin: the default (validate-off) executor paths —
    plain AND parallel — never import analysis.meshlint."""
    code = (
        "import sys, numpy as np\n"
        "import paddle_tpu as fluid\n"
        "from paddle_tpu import layers\n"
        "from paddle_tpu.parallel.parallel_executor import "
        "ParallelExecutor\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = layers.data('x', shape=[8])\n"
        "    label = layers.data('label', shape=[1], dtype='int64')\n"
        "    pred = layers.fc(x, size=4, act='softmax')\n"
        "    loss = layers.mean(layers.cross_entropy(input=pred, "
        "label=label))\n"
        "    fluid.optimizer.SGD(0.1).minimize(loss)\n"
        "exe = fluid.Executor(fluid.CPUPlace())\n"
        "exe.run(startup)\n"
        "pexe = ParallelExecutor(use_cuda=False, loss_name=loss.name, "
        "main_program=main)\n"
        "pexe.run(fetch_list=[loss.name], feed={'x': "
        "np.random.rand(8, 8).astype('float32'), 'label': "
        "np.random.randint(0, 4, (8, 1))})\n"
        "assert 'paddle_tpu.analysis.meshlint' not in sys.modules, "
        "'validate-off path imported meshlint'\n"
        "print('LAZY_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_VALIDATE", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-800:])
    assert "LAZY_OK" in p.stdout
