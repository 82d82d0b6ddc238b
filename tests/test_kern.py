"""ops/kern: registry dispatch, parity, meshlint pass.

The registry's invariants, each pinned here:
  - every registered kernel passes its parity gate on its own example
    (interpret mode — the numerics are backend-independent)
  - dispatch counts what it ran and what its kernel's gate rejected
  - the meshlint kern-capability pass warns exactly when a program op
    with a registered kernel probes False on the per-shard shapes
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.ops.kern import registry as kreg
from paddle_tpu.ops.pallas import flash_attention as fa

KERNELS = ("decode_attend", "dequant_attend_int8", "flash_attention",
           "int8_quant", "kda_attention", "layer_norm", "lookup_pool",
           "moe_expert_ffn", "selective_scan")


@pytest.fixture
def interpret_mode():
    fa.set_mode("interpret")
    try:
        yield
    finally:
        fa.set_mode("auto")


# ------------------------------------------------------------- parity
def test_the_eight_kernels_are_registered():
    assert tuple(kreg.names()) == KERNELS
    for name in kreg.names():
        spec = kreg.get(name)
        assert spec.example is not None, name
        assert spec.reference is not None, name


@pytest.mark.parametrize("name", KERNELS)
def test_every_kernel_parity_on_its_example(interpret_mode, name):
    args, kwargs = kreg.get(name).example(np.random.RandomState(0))
    ok, detail = kreg.parity_check(name, args, kwargs)
    assert ok is True, detail


@pytest.mark.parametrize("name", KERNELS)
def test_static_probe_accepts_every_example(name):
    import jax
    spec = kreg.get(name)
    args, kwargs = spec.example(np.random.RandomState(1))
    structs = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a
        for a in args)
    assert spec.probe(*structs, interpret=True, **kwargs)


def test_dispatch_counts_stats(interpret_mode):
    spec = kreg.get("int8_quant")
    args, kwargs = spec.example(np.random.RandomState(2))
    before = dict(kreg.STATS)
    out = kreg.dispatch("int8_quant", *args, **kwargs)
    assert out is not None
    assert kreg.STATS["dispatches"] == before["dispatches"] + 1
    assert kreg.STATS["accepted"] == before["accepted"] + 1
    fa.set_mode("off")       # the gate every try_* asks first
    assert kreg.dispatch("int8_quant", *args, **kwargs) is None
    assert kreg.STATS["rejected"] == before["rejected"] + 1
    assert kreg.adapter("int8_quant") is not None
    assert kreg.adapter("no_such_op") is None


# ------------------------------------------------- meshlint pass
def _ln_program(rows, C):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[rows, C],
                              append_batch_size=False, dtype="float32")
        fluid.layers.layer_norm(x, begin_norm_axis=1)
    return main


def _kern_diags(mctx):
    from paddle_tpu.analysis import meshlint as ml
    return [d for d in ml.run_mesh_passes(mctx, passes=["kern-capability"])
            if d.pass_name == "kern-capability"]


def test_meshlint_warns_on_probe_reject():
    from paddle_tpu.analysis import meshlint as ml
    diags = _kern_diags(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=_ln_program(4, 128)))
    assert len(diags) == 1
    d = diags[0]
    assert d.severity == "warning" and d.op_type == "layer_norm"
    assert "jnp fallback" in d.message


def test_meshlint_quiet_on_probe_accept():
    from paddle_tpu.analysis import meshlint as ml
    assert _kern_diags(ml.MeshLintContext(
        ml.MeshSpec({"dp": 2}), program=_ln_program(16, 128))) == []


def test_meshlint_probes_per_shard_shapes():
    """16 rows probe fine globally, but dp=4 leaves 4 rows per device
    — the pass judges what each device actually traces."""
    from paddle_tpu.analysis import meshlint as ml
    diags = _kern_diags(ml.MeshLintContext(
        ml.MeshSpec({"dp": 4}), program=_ln_program(16, 128),
        data_axis="dp"))
    assert len(diags) == 1
    assert "per-device view" in diags[0].message


def test_meshlint_quiet_without_program():
    from paddle_tpu.analysis import meshlint as ml
    assert _kern_diags(ml.MeshLintContext(ml.MeshSpec({"dp": 2}))) == []
