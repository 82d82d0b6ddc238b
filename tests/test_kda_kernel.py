"""The Mosaic kernels of the gated delta rule (ops/pallas/kda.py) in the
Pallas interpreter at the benchmark cell's head size, Dk = Dv = 128:
the op through `layers.kda_attention`, forward and every gradient, against
the token-by-token recurrence (`kernels_scan.kda_recurrent`) over the
decays and lengths the composition's own test has
(tests/test_solar_open2.py), and the policy that decides between kernels
and composition from what the arrays show.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import layers
from paddle_tpu.ops import kernels_scan as scan
from paddle_tpu.ops import registry as ops_registry
from paddle_tpu.ops.kern import registry as kreg
from paddle_tpu.ops.pallas import kda

from test_lfm2_moe import _op_and_grads

RNG = np.random.default_rng(35)
NAMES = ("q", "k", "v", "g", "beta")


@pytest.fixture
def interpret():
    ops_registry.set_mode("interpret")
    yield
    ops_registry.set_mode("auto")


def _unit(*shape):
    x = RNG.standard_normal(shape)
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype("float32")


def _case(T, g_min, B=1, H=2, D=128):
    return {"q": _unit(B, T, H, D), "k": _unit(B, T, H, D),
            "v": RNG.standard_normal((B, T, H, D)).astype("float32"),
            "g": RNG.uniform(g_min, 0.0, (B, T, H, D)).astype("float32"),
            "beta": RNG.uniform(0.0, 2.0, (B, T, H)).astype("float32")}


def _op(v):
    return layers.kda_attention(v["q"], v["k"], v["v"], v["g"], v["beta"])


def _recurrence_and_grads(vals, probe):
    def loss(*args):
        out = scan.kda_recurrent(*args)
        return jnp.sum(out * probe), out
    with jax.default_matmul_precision("highest"):
        (_, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                         has_aux=True)(
            *(jnp.asarray(vals[n]) for n in NAMES))
    return np.asarray(out), dict(zip(NAMES, map(np.asarray, g)))


@pytest.mark.parametrize("T,g_min", [
    (128, -0.1),       # two whole chunks, a slow decay
    (200, -1.6),       # off a multiple of 64; the initialisation's range
    (150, -10.0),      # strong decay: exp(-G) over a chunk is exp(640)
    (40, -10.0),       # shorter than a chunk
], ids=["T128", "T200_off_the_chunk", "T150_strong_decay",
        "T40_strong_decay"])
def test_the_kernels_match_the_token_by_token_recurrence(interpret, T,
                                                         g_min):
    """kda_fwd and kda_bwd (steps up to 2, so I + A is far from the
    identity) at the composition's tolerances, finite whatever the
    decay."""
    vals = _case(T, g_min)
    taken = kda.STATS["pallas_calls"]
    out, grads, probe = _op_and_grads(_op, vals)
    assert kda.STATS["pallas_calls"] > taken
    assert np.isfinite(out).all()
    assert all(np.isfinite(g).all() for g in grads.values())
    want, want_g = _recurrence_and_grads(vals, probe)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    assert set(grads) == set(vals)
    for n in vals:
        np.testing.assert_allclose(
            grads[n], want_g[n], rtol=2e-4,
            atol=2e-4 * float(np.abs(want_g[n]).max()), err_msg=n)


def test_bfloat16_in_float32_inside_bfloat16_out(interpret):
    """As the cell calls it: bf16 q, k, v, a float32 log-decay; the output
    and the gradients of q, k, v in bf16, g's in float32, all within a
    bf16 spacing of the recurrence on the same (rounded) operands."""
    vals = _case(100, -1.6)
    args = [jnp.asarray(vals[n]).astype(
        jnp.bfloat16 if n in "qkv" else jnp.float32) for n in NAMES]
    probe = jnp.asarray(RNG.standard_normal(vals["v"].shape), jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe)

    out = kda.try_kda(*args)
    assert out.dtype == jnp.bfloat16 and out.shape == vals["v"].shape
    got = jax.grad(loss(kda.try_kda), argnums=(0, 1, 2, 3, 4))(*args)
    assert [g.dtype for g in got] == [a.dtype for a in args]
    wide = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        want = scan.kda_recurrent(*wide)
        want_g = jax.grad(loss(scan.kda_recurrent),
                          argnums=(0, 1, 2, 3, 4))(*wide)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    for g, w in zip((out,) + got, (want,) + want_g):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)), w,
                                   rtol=eps, atol=eps * np.abs(w).max())


def test_the_policy_reads_the_shapes_and_nothing_else(interpret):
    """Heads of 16 are the composition's and `kda.STATS` does not move;
    heads of 128 are the kernels'; with the kernels off, nothing is."""
    def counts():
        per = kreg.STATS["by_kernel"].get("kda_attention", {})
        return (kda.STATS["pallas_calls"], per.get("accepted", 0),
                per.get("rejected", 0))

    small = _case(70, -1.0, D=16)
    args = [jnp.asarray(small[n]) for n in NAMES]
    assert not kda.supports(*args) and kda.try_kda(*args) is None
    before = counts()
    out, _grads, _ = _op_and_grads(_op, small)
    assert counts() == (before[0], before[1], before[2] + 1)
    np.testing.assert_allclose(
        out, np.asarray(scan.kda_recurrent(*args)), atol=2e-5, rtol=2e-5)

    wide = _case(70, -1.0)
    assert kda.supports(*(jax.ShapeDtypeStruct(wide[n].shape, jnp.float32)
                          for n in NAMES))
    before = counts()
    _op_and_grads(_op, wide)
    assert counts() == (before[0] + 1, before[1] + 1, before[2])

    # keys of 128 and values of 256: two vregs of value channels
    mixed = dict(wide, v=RNG.standard_normal((1, 70, 2, 256)).astype(
        "float32"))
    args = [jnp.asarray(mixed[n]) for n in NAMES]
    got = kda.try_kda(*args)
    assert got.shape == (1, 70, 2, 256)
    np.testing.assert_allclose(got, scan.kda_recurrent(*args), atol=2e-5,
                               rtol=2e-5)

    ops_registry.set_mode("off")
    assert kda.try_kda(*args) is None
    ops_registry.set_mode("auto")          # the CPU: no Mosaic target
    assert kda.try_kda(*args) is None


def test_a_scale_of_the_callers_and_a_batch_of_two(interpret):
    vals = _case(64, -0.5, B=2, H=1)
    args = [jnp.asarray(vals[n]) for n in NAMES]
    got = kda.try_kda(*args, scale=0.25)
    np.testing.assert_allclose(
        got, scan.kda_recurrent(*args, scale=0.25), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got, scan.kda_chunked(*args, scale=0.25),
                               atol=2e-5, rtol=2e-5)


def test_bench_kda_tool_refuses_without_a_chip(tmp_path, monkeypatch,
                                               capsys):
    """tools/bench_kda.py (how the one-scan timings of PERF.md were
    measured) times nothing off the chip: no `ms` line, no file, exit code
    2; its operands are the cell's kinds and its gap is relative."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "bench_kda.py")
    spec = importlib.util.spec_from_file_location("bench_kda", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.chdir(tmp_path)
    assert tool.main(["[[1, 64, 1, 128]]", "1"]) == 2
    said = capsys.readouterr()
    assert "ms" not in said.out and "not a TPU" in said.err
    assert not (tmp_path / "chiprun_out").exists()
    q, k, v, g, beta = tool.operands(1, 64, 2, 128)
    assert (q.dtype, v.dtype, g.dtype) == (jnp.bfloat16, jnp.bfloat16,
                                           jnp.float32)
    assert float(g.max()) < 0 and 0 <= float(beta.min()) \
        and float(beta.max()) <= 2
    assert set(tool.pieces()) == {"kernels_fwd", "kernels_fwd_bwd",
                                  "composition_fwd", "composition_fwd_bwd"}
    assert tool.gap((jnp.ones(3), 2 * jnp.ones(2)),
                    (jnp.ones(3), 4 * jnp.ones(2))) == [0.0, 0.5]
