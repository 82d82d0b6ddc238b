"""The Mosaic kernels of the diagonal selective scan
(ops/pallas/selective_scan.py) in the Pallas interpreter: the op through
`layers.selective_scan`, forward and every gradient, against the
token-by-token recurrence (`kernels_scan.selective_scan_recurrent`) at a
length off the chunk, at two blocks of channels, at a strong decay, at a
short chunk of whole wide loop steps; the float32 state under bfloat16
operands; the recurrence's blocks, which are the op's path off the TPU;
and the policy that decides between them from the shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import layers
from paddle_tpu.ops import kernels_scan as scan
from paddle_tpu.ops import registry as ops_registry
from paddle_tpu.ops.kern import registry as kreg
from paddle_tpu.ops.pallas import selective_scan as ssm

from test_lfm2_moe import _op_and_grads

RNG = np.random.default_rng(42)
NAMES = ("x", "dt", "s.w_0", "B", "C", "s.w_1")


@pytest.fixture
def interpret():
    ops_registry.set_mode("interpret")
    yield
    ops_registry.set_mode("auto")


def _case(T, C, Bsz=1, N=16, dt_max=0.1):
    """x, B, C signed; a step log-uniform in [0.001, dt_max]; A_log about
    log(1 .. N) (the initialisation, jittered), D signed."""
    return {
        "x": RNG.standard_normal((Bsz, T, C)).astype("float32"),
        "dt": np.exp(RNG.uniform(np.log(1e-3), np.log(dt_max),
                                 (Bsz, T, C))).astype("float32"),
        "s.w_0": (np.log(np.broadcast_to(np.arange(1, N + 1), (C, N)))
                  + RNG.uniform(-0.5, 0.5, (C, N))).astype("float32"),
        "B": RNG.standard_normal((Bsz, T, N)).astype("float32"),
        "C": RNG.standard_normal((Bsz, T, N)).astype("float32"),
        "s.w_1": RNG.standard_normal(C).astype("float32")}


def _op(v):
    return layers.selective_scan(v["x"], v["dt"], None, v["B"], v["C"], None,
                                 name="s")


def _recurrence_and_grads(vals, probe, fn=scan.selective_scan_recurrent):
    def loss(*args):
        out = fn(*args)
        return jnp.sum(out * probe), out
    with jax.default_matmul_precision("highest"):
        (_, out), g = jax.value_and_grad(loss, argnums=tuple(range(6)),
                                         has_aux=True)(
            *(jnp.asarray(vals[n]) for n in NAMES))
    return np.asarray(out), dict(zip(NAMES, map(np.asarray, g)))


def _close(got, want, grads, want_g):
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max(),
                               rtol=2e-5)
    assert set(grads) == set(NAMES)
    for n in NAMES:
        np.testing.assert_allclose(
            grads[n], want_g[n], rtol=2e-4,
            atol=2e-4 * float(np.abs(want_g[n]).max()), err_msg=n)


@pytest.mark.parametrize("T,C,Bsz,dt_max", [
    (300, 256, 1, 0.1),     # off a multiple of the 256-token chunk
    (70, 512, 2, 0.1),      # two blocks of 256 channels, two rows
    (256, 128, 1, 2.0),     # one whole chunk, a strong decay: exp(-32)
    (90, 128, 2, 0.5),      # a chunk of 96: three wide loop steps, padded
], ids=["T300_off_the_chunk", "C512_two_blocks", "T256_strong_decay",
        "T90_wide_loop_steps"])
def test_the_kernels_match_the_token_by_token_recurrence(interpret, T, C,
                                                         Bsz, dt_max):
    vals = _case(T, C, Bsz, dt_max=dt_max)
    taken = ssm.STATS["pallas_calls"]
    out, grads, probe = _op_and_grads(_op, vals)
    assert ssm.STATS["pallas_calls"] > taken
    assert np.isfinite(out).all()
    assert all(np.isfinite(g).all() for g in grads.values())
    want, want_g = _recurrence_and_grads(vals, probe)
    _close(out, want, grads, want_g)


def test_bfloat16_in_float32_state_inside_bfloat16_out(interpret):
    """As the cell calls it: bf16 x, B, C, a float32 step; y and the
    gradients of x, B, C in bf16, those of dt, A_log, D in float32, all
    within a bf16 spacing of the recurrence on the same (rounded)
    operands: the state is float32 whatever the operands are."""
    vals = _case(260, 256)
    bf = ("x", "B", "C")
    args = [jnp.asarray(vals[n]).astype(jnp.bfloat16 if n in bf
                                        else jnp.float32) for n in NAMES]
    probe = jnp.asarray(RNG.standard_normal(vals["x"].shape), jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe)

    out = ssm.try_selective_scan(*args)
    assert out.dtype == jnp.bfloat16 and out.shape == vals["x"].shape
    got = jax.grad(loss(ssm.try_selective_scan), argnums=tuple(range(6)))(
        *args)
    assert [g.dtype for g in got] == [a.dtype for a in args]
    wide = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        want = scan.selective_scan_recurrent(*wide)
        want_g = jax.grad(loss(scan.selective_scan_recurrent),
                          argnums=tuple(range(6)))(*wide)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    for g, w in zip((out,) + got, (want,) + want_g):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)), w,
                                   rtol=eps, atol=eps * np.abs(w).max())


def _plain_walk(x, dt, A_log, B, C, D):
    """The recurrence in one `lax.scan` over every token: no blocks, no
    padding, no recomputation."""
    a = -jnp.exp(A_log)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt[..., None] * a) * h \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, ct)

    h0 = jnp.zeros((x.shape[0], x.shape[2], A_log.shape[-1]))
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(v, 1, 0)
                                        for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D * x


@pytest.mark.parametrize("T", [300, 40], ids=["T300_two_chunks",
                                              "T40_one_short_chunk"])
def test_the_blocked_recurrence_matches_one_plain_walk(T):
    """The op's path off the TPU (and at widths off 128) walks blocks of
    SCAN_CHUNK tokens, the last one padded, each recomputed in the
    backward pass: forward and every gradient as one plain walk."""
    vals = _case(T, 24, Bsz=2, N=4, dt_max=1.0)
    probe = RNG.standard_normal(vals["x"].shape).astype("float32")
    got, got_g = _recurrence_and_grads(vals, probe)
    want, want_g = _recurrence_and_grads(vals, probe, _plain_walk)
    _close(got, want, got_g, want_g)


def test_the_recurrence_by_hand_at_the_first_two_tokens():
    vals = _case(2, 3, N=2, dt_max=1.0)
    x, dt, a_log, B, C, D = (vals[n][0] if vals[n].ndim == 3 else vals[n]
                             for n in NAMES)
    a = -np.exp(a_log)                                         # [C, N]
    h0 = dt[0][:, None] * B[0][None, :] * x[0][:, None]
    h1 = np.exp(dt[1][:, None] * a) * h0 \
        + dt[1][:, None] * B[1][None, :] * x[1][:, None]
    want = np.stack([h0 @ C[0] + D * x[0], h1 @ C[1] + D * x[1]])
    got = scan.selective_scan_recurrent(*(jnp.asarray(vals[n])
                                          for n in NAMES))
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-5,
                               atol=1e-6)


def test_the_policy_reads_the_shapes_and_nothing_else(interpret):
    """Channels of 64 are the recurrence's and `ssm.STATS` does not move;
    channels of 128 are the kernels'; with the kernels off, nothing is."""
    def counts():
        per = kreg.STATS["by_kernel"].get("selective_scan", {})
        return (ssm.STATS["pallas_calls"], per.get("accepted", 0),
                per.get("rejected", 0))

    small = _case(50, 64)
    args = [jnp.asarray(small[n]) for n in NAMES]
    assert not ssm.supports(*args) and ssm.try_selective_scan(*args) is None
    before = counts()
    out, _grads, _ = _op_and_grads(_op, small)
    assert counts() == (before[0], before[1], before[2] + 1)
    np.testing.assert_allclose(
        out, np.asarray(scan.selective_scan_recurrent(*args)), atol=2e-5,
        rtol=2e-5)

    wide = _case(50, 128)
    assert ssm.supports(*(jax.ShapeDtypeStruct(wide[n].shape, jnp.float32)
                          for n in NAMES))
    before = counts()
    _op_and_grads(_op, wide)
    assert counts() == (before[0] + 1, before[1] + 1, before[2])
    assert ssm._blocks(8192, 1280) == (256, 256)   # the cell: 5 blocks
    assert ssm._blocks(50, 512) == (56, 256)
    # tokens a loop step: the wide ones where the chunk holds them whole
    # (the cell's chunk of 256 takes both), else UNROLL
    for u in (ssm.FWD_UNROLL, ssm.BWD_UNROLL):
        assert ssm._unroll(256, u) == u
    assert ssm._unroll(96, 32) == 32 and ssm._unroll(56, 32) == ssm.UNROLL

    args = [jnp.asarray(wide[n]) for n in NAMES]
    ops_registry.set_mode("off")
    assert ssm.try_selective_scan(*args) is None
    ops_registry.set_mode("auto")          # the CPU: no Mosaic target
    assert ssm.try_selective_scan(*args) is None
