"""LFM2-MoE through the Program IR, against the benchmark's plain reference
(chipbench/reference/lfm2_moe.py, which imports nothing of the program):
each new op's output and gradient, grouped-query attention through the
`flash_attention` op, the expert layer's share of an expert-parallel
deployment, the router's ties and bias, the whole model's first steps
through `Executor.run`, what `amp.cast_program_to_bf16` keeps float32, and
the counters whose values the device computes.

Sizes: hidden 64, 8 experts of which 2 are held, top-2, T 32 (the cell's
tiny configuration, tests/chipbench_tests/tiny/), all on the CPU.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, telemetry
from paddle_tpu.core.backward import append_backward
from paddle_tpu.models import lfm2_moe as lfm2
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import grouped_matmul as gm

from chipbench import manifest
from chipbench.reference import lfm2_moe as ref

RNG = np.random.default_rng(30)


def _f32(*shape, scale=1.0):
    return (RNG.standard_normal(shape) * scale).astype("float32")


def _op_and_grads(build, values):
    """Run `build(vars) -> out` over parameters holding `values`; returns
    (out, {name: d sum(out * probe) / d value}, probe)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        # a name with a dot is a parameter the layer under test declares
        vs = {n: layers.create_parameter(list(v.shape), "float32",
                                         attr=fluid.ParamAttr(name=n))
              for n, v in values.items() if "." not in n}
        out = build(vs)
        probe = layers.data("probe", shape=list(out.shape),
                            append_batch_size=False)
        loss = layers.reduce_sum(layers.elementwise_mul(out, probe))
        grads = dict((p.name, g) for p, g in append_backward(loss))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    p = _f32(*out.shape)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for n, v in values.items():
            scope.set(n, v)
        names = [n for n in values if n in grads]
        got = exe.run(main, feed={"probe": p},
                      fetch_list=[out] + [grads[n] for n in names])
    return got[0], dict(zip(names, got[1:])), p


def _ref_and_grads(fn, values, probe):
    def loss(vals):
        out = fn(vals)
        return jnp.sum(out * probe), out
    (_, out), g = jax.value_and_grad(loss, has_aux=True)(
        {k: jnp.asarray(v) for k, v in values.items()})
    return np.asarray(out), {k: np.asarray(v) for k, v in g.items()}


def _check(build, fn, values, tol=2e-5):
    out, grads, probe = _op_and_grads(build, values)
    want, want_g = _ref_and_grads(fn, values, probe)
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)
    assert set(grads) == set(values)
    for n in values:
        np.testing.assert_allclose(grads[n], want_g[n], atol=tol * 10,
                                   rtol=tol * 10, err_msg=n)


# ------------------------------------------------------ each op by itself
@pytest.mark.parametrize("shape", [(2, 8, 64), (2, 8, 4, 16)])
def test_rms_norm_matches_the_reference(shape):
    """Over the hidden size, and over one head's width (the per-head norm
    of q and k)."""
    vals = {"x": _f32(*shape), "n.w_0": 1 + _f32(shape[-1], scale=0.1)}
    _check(lambda v: layers.rms_norm(v["x"], 1e-5, name="n"),
           lambda v: ref.rms_norm(v["x"], v["n.w_0"], 1e-5), vals)


@pytest.mark.parametrize("theta", [1e4, 1e6])
def test_rotary_embedding_matches_the_reference(theta):
    vals = {"x": _f32(2, 32, 4, 16)}
    _check(lambda v: layers.rotary_embedding(v["x"], theta),
           lambda v: ref.rope(v["x"], theta), vals)


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_matches_the_reference_and_is_causal(taps):
    vals = {"x": _f32(2, 32, 24), "c.w_0": _f32(24, taps, scale=0.5)}
    _check(lambda v: layers.short_conv(v["x"], taps, name="c"),
           lambda v: ref.dwconv_causal(v["x"], v["c.w_0"]), vals)
    # position t reads t-(K-1) .. t and nothing later: by hand at t = 0, 5
    out = np.asarray(ref.dwconv_causal(vals["x"], vals["c.w_0"]))
    x, k = vals["x"], vals["c.w_0"]
    np.testing.assert_allclose(out[:, 0], x[:, 0] * k[:, -1], rtol=1e-5)
    np.testing.assert_allclose(
        out[:, 5], sum(x[:, 5 - (taps - 1) + j] * k[:, j]
                       for j in range(taps)), rtol=1e-5, atol=1e-6)


def test_swiglu_matches_the_reference():
    vals = {"a": _f32(2, 8, 48), "b": _f32(2, 8, 48)}
    _check(lambda v: layers.swiglu(v["a"], v["b"]),
           lambda v: v["a"] * jax.nn.sigmoid(v["a"]) * v["b"], vals)


def _route_program(x, w, bias, k, **kw):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data("x", shape=list(x.shape), append_batch_size=False)
        idx, tw = layers.moe_route(xv, w.shape[1], k, name="r", **kw)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set("r.w_0", w)
        if bias is not None:
            scope.set("r.bias", bias)
        return exe.run(main, feed={"x": x}, fetch_list=[idx, tw])


def test_moe_route_matches_the_reference_and_its_weight_gets_a_gradient():
    x, w = _f32(2, 16, 64), _f32(64, 8, scale=0.3)
    bias = _f32(8, scale=0.2)
    idx, tw = _route_program(x, w, bias, 2)
    want_idx, want_w = ref.route(x, w, bias, 2)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(tw, want_w, rtol=1e-5)
    assert idx.dtype == np.int32 and tw.dtype == np.float32
    np.testing.assert_allclose(tw.sum(-1), 1.0, atol=1e-4)

    # the routing weights are differentiable in x and in the router's
    # weight; the selection is not, and the bias gets no gradient at all
    vals = {"x": x, "r.w_0": w}

    def build(v):
        main = fluid.default_main_program()
        _, tw = layers.moe_route(v["x"], 8, 2, name="r")
        assert not isinstance(main.global_block().var("r.bias"),
                              fluid.core.framework.Parameter)
        return tw

    out, grads, probe = _op_and_grads(build, vals)
    want, want_g = _ref_and_grads(
        lambda v: ref.route(v["x"], v["r.w_0"], None, 2)[1], vals, probe)
    np.testing.assert_allclose(out, want, rtol=1e-5)
    for n in vals:
        np.testing.assert_allclose(grads[n], want_g[n], atol=1e-5, rtol=1e-4)


def test_moe_route_selects_by_score_plus_bias_and_weighs_by_score():
    """Two experts with the same score: the lower id wins. A bias moves
    the selection and leaves the weights the scores' own."""
    H, E = 8, 4
    x = np.ones((1, 3, H), "float32")
    w = np.zeros((H, E), "float32")
    w[:, 0] = w[:, 2] = 0.25           # logits 2, 0, 2, 0: a tie twice over
    idx, tw = _route_program(x, w, None, 2, use_expert_bias=False)
    np.testing.assert_array_equal(idx, np.tile([0, 2], (1, 3, 1)))
    np.testing.assert_allclose(tw, 0.5, atol=1e-5)
    # k = 1 among equals: the lower id
    idx1, _ = _route_program(x, w, None, 1, use_expert_bias=False)
    assert (idx1 == 0).all()
    # a bias lifts expert 3 over expert 2 (0.5 + 0.6 > 0.88): selected by
    # score + bias, weighed by the scores alone
    bias = np.array([0.0, 0.0, 0.0, 0.6], "float32")
    idx, tw = _route_program(x, w, bias, 2)
    np.testing.assert_array_equal(idx, np.tile([3, 0], (1, 3, 1)))
    s = 1 / (1 + np.exp(-np.array([0.0, 2.0])))      # experts 3, 0
    np.testing.assert_allclose(tw[0, 0], s / (s.sum() + 1e-6), rtol=1e-5)
    # without renormalising and with a scale the weights are the scores
    idx, tw = _route_program(x, w, bias, 2, norm_topk_prob=False,
                             routed_scaling_factor=2.0)
    np.testing.assert_allclose(tw[0, 0], 2.0 * s, rtol=1e-5)


# ------------------------------------------------------- the expert layer
def _expert_case(N=64, H=32, F=48, E_all=8, k=2):
    x = _f32(N, H)
    idx = np.stack([RNG.permutation(E_all)[:k] for _ in range(N)]).astype(
        "int32")
    tw = RNG.uniform(0.1, 1.0, (N, k)).astype("float32")
    w1, w3 = _f32(E_all, H, F, scale=0.2), _f32(E_all, H, F, scale=0.2)
    w2 = _f32(E_all, F, H, scale=0.2)
    return x, idx, tw, w1, w3, w2


def _expert_program(x, idx, tw, w1, w3, w2, first):
    """moe_expert_ffn as an op of a program, with its gradients."""
    vals = {"x": x, "tw": tw, "e.w_0": w1, "e.w_1": w3, "e.w_2": w2}

    def build(v):
        return layers.moe_expert_ffn(
            v["x"], layers.assign(idx), v["tw"], w1.shape[0], first,
            w1.shape[2], name="e")[0]

    return _op_and_grads(build, vals), vals


@pytest.mark.parametrize("first", [0, 2, 6])
def test_moe_expert_ffn_matches_the_references_share(first):
    x, idx, tw, w1, w3, w2 = _expert_case()
    held = slice(first, first + 2)
    (out, grads, probe), vals = _expert_program(
        x, idx, tw, w1[held], w3[held], w2[held], first)
    want, want_g = _ref_and_grads(
        lambda v: ref.expert_share(v["x"], idx, v["tw"], v["e.w_0"],
                                   v["e.w_1"], v["e.w_2"], first, "float32"),
        vals, probe)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for n in vals:
        np.testing.assert_allclose(grads[n], want_g[n], atol=2e-4,
                                   rtol=2e-4, err_msg=n)


@pytest.mark.parametrize("path", ["kernels", "composition"])
def test_the_shares_of_all_ranks_add_up_to_the_uncut_expert_layer(path):
    """THE SHARE TEST. Eight experts, two held by each of four ranks: the
    four `moe_expert_ffn` outputs add up to the whole layer as the
    reference computes it with all eight experts held."""
    x, idx, tw, w1, w3, w2 = _expert_case()
    fn = gm.expert_ffn_reference if path == "composition" else \
        (lambda *a, **kw: gm.expert_ffn(*a, tile_rows=8, interpret=True,
                                        **kw))
    total, pairs = 0.0, 0
    for first in range(0, 8, 2):
        held = slice(first, first + 2)
        out, counts = fn(jnp.asarray(x), jnp.asarray(idx), jnp.asarray(tw),
                         w1[held], w3[held], w2[held], first_expert=first)
        total = total + np.asarray(out)
        pairs += int(np.sum(counts))
        np.testing.assert_array_equal(
            counts, [(idx == first).sum(), (idx == first + 1).sum()])
    whole = ref.expert_share(x, idx, tw, w1, w3, w2, 0, "float32")
    np.testing.assert_allclose(total, whole, atol=3e-5, rtol=3e-5)
    assert pairs == idx.size          # every pair computed once, none lost


@pytest.mark.parametrize("N", [64, 200])
def test_no_token_is_dropped_when_every_token_picks_one_held_expert(N):
    """The worst imbalance: every token's first choice is held expert 1
    and its second an absent one. Static shapes, nothing dropped."""
    x, _, tw, w1, w3, w2 = _expert_case(N=N)
    idx = np.zeros((N, 2), "int32")
    idx[:, 0], idx[:, 1] = 1, 5
    out, counts = gm.expert_ffn(jnp.asarray(x), jnp.asarray(idx),
                                jnp.asarray(tw), w1[:2], w3[:2], w2[:2],
                                first_expert=0, tile_rows=8, interpret=True)
    np.testing.assert_array_equal(counts, [0, N])
    want = ref.expert_share(x, idx, tw, w1[:2], w3[:2], w2[:2], 0, "float32")
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)
    # and the other extreme: nothing routed here at all
    idx[:] = [[4, 7]]
    out, counts = gm.expert_ffn(jnp.asarray(x), jnp.asarray(idx),
                                jnp.asarray(tw), w1[:2], w3[:2], w2[:2],
                                first_expert=0, tile_rows=8, interpret=True)
    assert int(np.sum(counts)) == 0 and not np.asarray(out).any()


def _twice(idx):
    """Every third token names its first expert twice, with two weights."""
    idx = idx.copy()
    idx[::3, 1] = idx[::3, 0]
    return idx


@pytest.mark.parametrize("tile_rows", [8, 16])
@pytest.mark.parametrize("choices", ["distinct", "one expert twice"])
def test_grouped_kernels_gradients_match_the_composition(choices, tile_rows):
    """Through `expert_ffn`: the gradient of the rows (dx comes back
    through `moe_combine`, unweighted) and of the weights, which the
    forward's weighted combine applies."""
    x, idx, tw, w1, w3, w2 = _expert_case()
    if choices == "one expert twice":
        idx = _twice(idx)

    def grads(fn):
        def loss(x, tw, w1, w3, w2):
            out, _ = fn(x, jnp.asarray(idx), tw, w1, w3, w2, first_expert=2)
            return jnp.sum(out * jnp.cos(out))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
            *(jnp.asarray(a) for a in (x, tw, w1[2:5], w3[2:5], w2[2:5])))

    got = grads(lambda *a, **kw: gm.expert_ffn(*a, tile_rows=tile_rows,
                                               interpret=True, **kw))
    want = grads(gm.expert_ffn_reference)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


def _take_combine(rows, dest, weights=None):
    """What `moe_combine` replaced (PR 31), kept as its oracle: XLA's
    gather over every (token, choice), a pair that is not held reading a
    filled-in zero; float32 weights and sum, one rounding."""
    picked = jnp.take(rows, dest, axis=0, mode="fill", fill_value=0)
    picked = picked.astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[..., None]
    return jnp.sum(picked, axis=1).astype(rows.dtype)


def _combine_case(routing, N=40, k=2):
    """Experts 2, 3, 4 of 8 are held."""
    idx = np.stack([RNG.permutation(8)[:k] for _ in range(N)]).astype(
        "int32")
    if routing == "all to one held expert":     # ranges of a whole block
        idx[:, 0], idx[:, 1] = 3, 7
    elif routing == "none held":
        idx[:, 0], idx[:, 1] = 0, 6
    elif routing == "a held expert with no pair":
        idx[idx == 3] = 6
    elif routing == "one expert twice":
        idx = _twice(idx)
    return idx


@pytest.mark.parametrize("tile_rows", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("routing,N", [
    ("random", 40), ("random", 300),     # 300: off a multiple of the block
    ("all to one held expert", 520), ("none held", 24),
    ("one expert twice", 270)])
def test_moe_combine_matches_the_gather_it_replaced(routing, N, weighted,
                                                    dtype, tile_rows):
    """The kernel alone, on a buffer whose rows past the tiles in use are
    NaN (the grouped kernels never write them): the same sum as the
    gather's, to the last bit in float32 rows (a token's at most k
    products are added in another order, so a bf16 result may round the
    other way once)."""
    idx = _combine_case(routing, N)
    plan = gm.make_plan(jnp.asarray(idx), 2, 3, tile_rows)
    M, in_use = plan["src"].shape[0], int(plan["n_active"][0]) * tile_rows
    rows = np.full((M, 16), np.nan, "float32")
    rows[:in_use] = _f32(in_use, 16)
    rows = jnp.asarray(rows, dtype)
    w = jnp.asarray(RNG.uniform(0.1, 1.0, idx.shape), jnp.float32) \
        if weighted else None
    got = gm._combine(rows, plan, w, 3, tile_rows, True)
    want = _take_combine(rows, plan["dest"], w)
    assert got.shape == (N, 16) and got.dtype == rows.dtype
    if routing == "none held":
        assert not np.asarray(got, "float32").any()
    held = int(np.sum(plan["counts"]))
    assert held == ((idx >= 2) & (idx < 5)).sum()
    if routing == "all to one held expert":
        # a block's range of expert 1 is the whole block: many chunks
        off = np.asarray(plan["block_off"]).reshape(-1, 3)
        tb, chunk, _, _ = gm._combine_tiling(N, tile_rows)
        np.testing.assert_array_equal(np.diff(off[:, 1]), [tb, tb, N % tb])
        assert tb >= 16 * chunk
    tol = dict(atol=1e-6, rtol=1e-6) if dtype == "float32" else \
        dict(atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(got, "float32"),
                               np.asarray(want, "float32"), **tol)


def _take_gather(x, plan, tm):
    """What `_gather_rows` did until PR 37, kept as its oracle: the same
    loop of `jnp.take` over the tiles in use, into a buffer of zeros that
    XLA wrote out whole first."""
    src = plan["src"]

    def tile(t, out):
        rows = jax.lax.dynamic_slice(src, (t * tm,), (tm,))
        blk = jnp.take(x, rows, axis=0, mode="fill", fill_value=0)
        return jax.lax.dynamic_update_slice(out, blk, (t * tm, 0))

    return jax.lax.fori_loop(0, plan["n_active"][0], tile,
                             jnp.zeros((src.shape[0], x.shape[1]), x.dtype))


@pytest.fixture
def rng_put_back():
    """A case draws from the module's generator and puts it back, so that
    the tests after it see the numbers they were written against."""
    state = RNG.bit_generator.state
    yield
    RNG.bit_generator.state = state


GATHER_ROUTINGS = [("random", 40), ("random", 301),   # off a multiple
                   ("all to one held expert", 77),
                   ("a held expert with no pair", 50), ("none held", 24)]


@pytest.mark.parametrize("tile_rows", [8, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing,N", GATHER_ROUTINGS)
@pytest.mark.usefixtures("rng_put_back")
def test_gather_rows_fills_the_tiles_in_use_as_the_loop_it_replaced(
        routing, N, dtype, tile_rows):
    """The sorted buffer starts unwritten (PR 37): inside the tiles in use
    it holds, bit for bit, what the loop over a zero-filled buffer wrote
    (a copy of the tokens' rows, zeros in the padding rows); past them it
    holds whatever the memory held (the interpreter: NaN), and the count
    of tiles in use is all that decides where that starts."""
    idx = _combine_case(routing, N)
    plan = gm.make_plan(jnp.asarray(idx), 2, 3, tile_rows)
    M, in_use = plan["src"].shape[0], int(plan["n_active"][0]) * tile_rows
    x = jnp.asarray(_f32(N, 16), dtype)
    before = gm.STATS["gather_kernel"]
    got = gm._gather_rows(x, plan, tile_rows, True)
    assert gm.STATS["gather_kernel"] == before + 1
    want = _take_gather(x, plan, tile_rows)
    assert got.shape == want.shape == (M, 16) and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got[:in_use], "float32"),
                                  np.asarray(want[:in_use], "float32"))
    src = np.asarray(plan["src"])
    held = ((idx >= 2) & (idx < 5)).sum()
    assert (src[:in_use] < N).sum() == held and (src[in_use:] == N).all()
    assert not np.asarray(got[:in_use], "float32")[src[:in_use] == N].any()
    if routing == "none held":
        assert in_use == 3 * tile_rows       # a tile an expert, all padding
        assert not np.asarray(got[:in_use], "float32").any()
    if routing == "a held expert with no pair":
        assert int(plan["counts"][1]) == 0
    # the worst case still fits, and the zero-filled rows are gone
    assert in_use < M
    assert np.isnan(np.asarray(got[in_use:], "float32")).all()


@pytest.mark.parametrize("routing,N", GATHER_ROUTINGS)
@pytest.mark.usefixtures("rng_put_back")
def test_no_kernel_reads_the_buffer_past_the_tiles_in_use(routing, N,
                                                          monkeypatch):
    """Every gather's rows past `n_active * tile_rows` are set to NaN on
    the way out (on the chip they hold what the memory held): the layer's
    output and all five gradients are finite and the composition's."""
    x, _, tw, w1, w3, w2 = _expert_case(N=N)
    idx = _combine_case(routing, N)
    real, poisoned = gm._gather_rows, []

    def gather_then_poison(x, plan, tm, interpret=False):
        out = real(x, plan, tm, interpret)
        row = jnp.arange(out.shape[0])[:, None]
        poisoned.append(out.shape)
        return jnp.where(row < plan["n_active"][0] * tm, out, jnp.nan)

    monkeypatch.setattr(gm, "_gather_rows", gather_then_poison)

    def run(fn):
        def loss(x, tw, w1, w3, w2):
            out, _ = fn(x, jnp.asarray(idx), tw, w1, w3, w2, first_expert=2)
            return jnp.sum(out * jnp.cos(out)), out
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(
            *(jnp.asarray(a) for a in (x, tw, w1[2:5], w3[2:5], w2[2:5])))

    (_, out), grads = run(lambda *a, **kw: gm.expert_ffn(
        *a, tile_rows=8, interpret=True, **kw))
    # the forward's xs, the backward's xs and dys
    M = gm.buffer_tiles(N, 2, 3, 8) * 8
    assert poisoned == [(M, x.shape[1])] * 3
    (_, want), want_g = run(gm.expert_ffn_reference)
    for g, w in zip((out,) + grads, (want,) + want_g):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


@pytest.mark.usefixtures("rng_put_back")
def test_the_gathers_of_a_layer_are_counted_where_they_are_traced():
    """`STATS["gather_kernel"]`: three a layer (the forward's xs, the
    backward's xs and dys), through the op and the registry, beside the
    registry's own count of the op's traces; the composition moves
    neither."""
    from paddle_tpu.ops import registry as ops_registry
    from paddle_tpu.ops.kern import registry as kreg

    def counts():
        per = kreg.STATS["by_kernel"].get("moe_expert_ffn", {})
        return gm.STATS["gather_kernel"], per.get("accepted", 0)

    x, idx, tw, w1, w3, w2 = _expert_case()
    before = counts()
    _expert_program(x, idx, tw, w1[:2], w3[:2], w2[:2], 0)
    assert counts() == before               # the CPU: the composition
    ops_registry.set_mode("interpret")
    try:
        _expert_program(x, idx, tw, w1[:2], w3[:2], w2[:2], 0)
    finally:
        ops_registry.set_mode("auto")
    assert counts() == (before[0] + 3, before[1] + 1)


def test_bench_expert_ffn_tool_refuses_without_a_chip(tmp_path, monkeypatch,
                                                      capsys, rng_put_back):
    """tools/bench_expert_ffn.py (how the one-layer timings of PERF.md
    were measured) times nothing off the chip: no `ms` line, no file,
    exit code 2; its copies of the replaced gathers are the oracles'."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "bench_expert_ffn.py")
    spec = importlib.util.spec_from_file_location("bench_expert_ffn", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.chdir(tmp_path)
    assert tool.main(["[[64, 2, 16, 24, 2, 8]]", "1"]) == 2
    said = capsys.readouterr()
    assert "ms" not in said.out and "not a TPU" in said.err
    assert not (tmp_path / "chiprun_out").exists()
    rows, dest = jnp.asarray(_f32(12, 16)), jnp.asarray([[0, 12], [3, 3]])
    w = jnp.asarray(_f32(2, 2))
    np.testing.assert_array_equal(tool.take_combine(rows, dest, w),
                                  _take_combine(rows, dest, w))
    plan = gm.make_plan(jnp.asarray(_combine_case("random", 40)), 2, 3, 8)
    x = jnp.asarray(_f32(40, 16))
    np.testing.assert_array_equal(tool.take_gather(x, plan, 8),
                                  _take_gather(x, plan, 8))


def test_the_buffer_holds_the_worst_case_and_a_tile_has_one_expert():
    assert gm.buffer_tiles(16384, 4, 8, 512) == 16384 * 4 // 512 + 8
    assert gm.buffer_tiles(64, 4, 2, 8) == 64 * 2 // 8 + 2
    idx = jnp.asarray(RNG.integers(0, 8, (40, 2)), jnp.int32)
    plan = gm.make_plan(idx, 2, 3, 8)
    counts = np.asarray(plan["counts"])
    np.testing.assert_array_equal(
        counts, [(np.asarray(idx) == e).sum() for e in (2, 3, 4)])
    tiles = np.maximum(1, -(-counts // 8))
    assert int(plan["n_active"][0]) == tiles.sum()
    np.testing.assert_array_equal(
        np.asarray(plan["tile_expert"])[:tiles.sum()],
        np.repeat(np.arange(3), tiles))
    # every held pair has a row of its own; an absent expert's points past
    dest = np.asarray(plan["dest"])
    M = plan["src"].shape[0]
    held = (np.asarray(idx) >= 2) & (np.asarray(idx) < 5)
    assert (dest[~held] == M).all()
    assert len(set(dest[held])) == held.sum() and (dest[held] < M).all()


# --------------------------------------------- grouped-query attention
@pytest.mark.parametrize("use_flash", [True, False])
def test_grouped_query_attention_through_the_op_matches_the_reference(
        use_flash):
    """32-over-8 in small: 4 query heads over 2 key-value heads, causal,
    through the `flash_attention` op (on the CPU: `_sdpa`) and the plain
    op, against the reference's blockwise attention."""
    B, T, H, KV, D = 2, 32, 4, 2, 16
    vals = {"q": _f32(B, T, H, D), "k": _f32(B, T, KV, D),
            "v": _f32(B, T, KV, D)}

    def want(v):
        q = v["q"].reshape(B, T, KV, H // KV, D)
        return ref._attend_block(q, v["k"], v["v"], 0, "float32").reshape(
            B, T, H, D)

    _check(lambda v: layers.flash_attention(v["q"], v["k"], v["v"],
                                            causal=True,
                                            use_flash=use_flash),
           want, vals, tol=1e-5)


@pytest.mark.parametrize("with_bias", [False, True])
def test_the_tiled_kernels_take_fewer_key_value_heads(with_bias):
    """flash_attention_fwd / _dq / _dkv with 4 query heads over 2
    key-value heads, several blocks each way, in interpret mode."""
    B, H, KV, T, D = 2, 4, 2, 256, 64
    q, k, v = _f32(B, H, T, D), _f32(B, KV, T, D), _f32(B, KV, T, D)
    bias = _f32(B, T) if with_bias else None

    def grads(fn, **kw):
        def loss(q, k, v, b):
            out = fn(q, k, v, bias=b, causal=True, **kw)
            return jnp.sum(out * jnp.sin(out)), out
        argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
        return jax.value_and_grad(loss, argnums=argnums, has_aux=True)(
            q, k, v, bias)

    (_, out), got = grads(fa.flash_attention, block_q=128, block_k=128,
                          interpret=True)
    (_, want_out), want = grads(fa.flash_attention_reference)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    assert got[1].shape == (B, KV, T, D) and got[2].shape == (B, KV, T, D)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4)
    assert fa.supports(q, k, v)
    assert not fa.supports(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))


# ---------------------------------------------------------- the whole model
def _tiny_cell():
    """The benchmark's own tiny configuration of the cell and its model
    file (hidden 64, 8 experts of which 2 held, top-2, T 32)."""
    here = os.path.dirname(os.path.abspath(__file__))
    man = manifest.Manifest()
    cfg = man.config("lfm2_24b_a2b_train_ep8")
    with open(os.path.join(here, "chipbench_tests", "tiny", "configs",
                           "lfm2_24b_a2b_train_ep8.json")) as f:
        cfg.update(json.load(f))
    traffic = {"kind": "lm_stream_batches", "rows": 4, "length": 32,
               "pool": 3}
    return man.model("lfm2_moe"), cfg, traffic


def _train(model, cfg, traffic, seed, dtype, steps=3, bf16=False):
    main, startup, loss = model.build(cfg, traffic, fluid)
    if bf16:
        fluid.amp.cast_program_to_bf16(main)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    params = model.make_params(cfg, seed, dtype)
    batches = model.make_batches(traffic, cfg, seed)
    names = [n for n, _, _ in model.param_specs(cfg)]
    # the executor donates its state: keep what the steps started from
    start = {n: np.asarray(a, "float32") for n, a in params.items()}
    seen = {"loss": []}
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert {v.name for v in main.all_parameters()} == set(names)
        for n, a in params.items():
            assert scope.get(n) is not None, n   # the biases too
            scope.set(n, a)
        for t, b in enumerate(batches[:steps]):
            out = exe.run(main, feed=b, fetch_list=[loss])
            seen["loss"].append(float(out[0]))
            if t == 0:
                seen["grad_norm"] = {
                    n: float(np.linalg.norm(np.asarray(
                        scope.get(n + "_moment1_0"), "float32")))
                    / (1 - cfg["optimizer"]["beta1"]) for n in names}
        seen["delta_norm"] = {
            n: float(np.linalg.norm(
                np.asarray(scope.get(n), "float32")
                - start[n])) for n in names}
        bias_after = {n: np.asarray(scope.get(n))
                      for n in model.bias_names(cfg)}
    return seen, model.make_params(cfg, seed, dtype), batches, bias_after


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_three_steps_through_executor_run_match_the_reference(seed):
    """Loss of each step, the first gradient per leaf, the parameters'
    change per leaf after three Adam steps, float32 on both sides."""
    from chipbench import correct
    model, cfg, traffic = _tiny_cell()
    seen, params, batches, bias_after = _train(model, cfg, traffic, seed,
                                               "float32")
    want = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                 "float32", 2)
    np.testing.assert_allclose(seen["loss"], want["loss"], rtol=2e-5)
    for n, g in want["grad_norm"].items():
        assert seen["grad_norm"][n] == pytest.approx(g, rel=2e-3, abs=1e-7), n
    numbers = correct.train_numbers(seen, want)
    assert numbers["loss_gap"] < 1e-4 and numbers["grad_gap"] < 2e-3
    assert numbers["delta_gap"] < 0.02
    # the expert bias is state that nothing trains: no gradient, no Adam
    # moment, and the three steps left it as it was made
    assert set(bias_after) == {"l1_router.bias", "l2_router.bias"}
    for n, b in bias_after.items():
        np.testing.assert_array_equal(b, np.asarray(params[n]))
        assert n not in want["grad_norm"]


def test_the_model_is_built_from_one_op_type_a_mechanism():
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    types = {op.type for op in main.global_block().ops}
    assert {"rms_norm", "rotary_embedding", "short_conv", "swiglu",
            "moe_route", "moe_expert_ffn", "flash_attention"} <= types
    block = main.global_block()
    for op in block.ops:
        if op.type == "moe_route":
            bias = block.var(op.inputs["Bias"][0])
            assert bias.persistable and not isinstance(
                bias, fluid.core.framework.Parameter)
            assert not any(bias.name in o.input_names()
                           for o in block.ops if o.type == "adam")
        if op.type == "moe_expert_ffn":
            assert op.attrs["first_expert"] == cfg["first_expert"]
            assert block.var(op.inputs["W1"][0]).shape[0] \
                == cfg["experts_held"]
    # the published shape of the full model
    full = lfm2.Lfm2MoeConfig()
    assert len(full.layer_types) == 40
    assert full.layer_types.count("full_attention") == 10
    assert full.layer_types[:3] == ["conv", "conv", "full_attention"]
    assert full.layer_types[-2:] == ["full_attention", "conv"]
    assert full.experts_held == 64 and full.head_dim == 64
    with pytest.raises(ValueError):
        lfm2.Lfm2MoeConfig(experts_held=8, first_expert=60)


# ------------------------------------------------------------- bfloat16
def test_cast_to_bf16_keeps_what_the_configuration_says_float32():
    """RMSNorm weights, the router's weight and the expert bias keep
    float32 by the slot an op reads them through, whatever their names;
    every other parameter is bfloat16."""
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    fluid.amp.cast_program_to_bf16(main)
    kinds = {n: kind for n, _, kind in model.param_specs(cfg)}
    keep = set(cfg["precision"]["float32_parameters"])
    assert keep == {"norm", "router"}
    block = main.global_block()
    for p in main.all_parameters():
        want = "float32" if kinds[p.name] in keep \
            else cfg["precision"]["parameters"]
        assert p.dtype == want, (p.name, p.dtype)
        for suffix in ("_moment1_0", "_moment2_0"):     # Adam's moments
            assert block.var(p.name + suffix).dtype == "float32"
    for n in model.bias_names(cfg):
        assert block.var(n).dtype == "float32"
    for op in block.ops:
        if op.type == "moe_route":
            assert block.var(op.outputs["TopkW"][0]).dtype == "float32"


def test_the_float32_rule_does_not_read_names():
    """The same layers under names that say nothing."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[8, 16])
        h = layers.rms_norm(x, param_attr=fluid.ParamAttr(name="alpha"))
        idx, tw = layers.moe_route(
            h, 4, 2, param_attr=fluid.ParamAttr(name="beta"), name="gamma")
        layers.moe_expert_ffn(h, idx, tw, 2, 0, 8, name="delta")
        layers.fc(h, 16, num_flatten_dims=2, name="epsilon")
    fluid.amp.cast_program_to_bf16(main)
    dt = {p.name: p.dtype for p in main.all_parameters()}
    assert dt["alpha"] == "float32" and dt["beta"] == "float32"
    assert dt["delta.w_0"] == dt["epsilon.w_0"] == "bfloat16"
    assert main.global_block().var("gamma.bias").dtype == "float32"


def test_bf16_steps_stay_near_the_reference():
    model, cfg, traffic = _tiny_cell()
    seen, params, batches, _ = _train(model, cfg, traffic, 7, "bfloat16",
                                      bf16=True)
    want = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                 "float32", 2)
    np.testing.assert_allclose(seen["loss"], want["loss"], rtol=5e-3)


# ------------------------------------------------ counters from the device
def test_marked_variables_come_back_with_the_fetches_and_are_counted():
    model, cfg, traffic = _tiny_cell()
    main, startup, loss = model.build(cfg, traffic, fluid)
    assert set(main._device_counters) == {"moe.local_pairs",
                                          "moe.max_expert_pairs"}
    assert set(main.clone()._device_counters) == set(main._device_counters)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    batches = model.make_batches(traffic, cfg, 3)
    before = telemetry.snapshot()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for n, a in model.make_params(cfg, 3, "float32").items():
            scope.set(n, a)
        for b in batches[:2]:
            out = exe.run(main, feed=b, fetch_list=[loss])
            assert len(out) == 1              # the caller's fetches only
        none = exe.run(main, feed=batches[2], fetch_list=[])
        assert list(none) == []
    after = telemetry.snapshot()
    steps = after["moe.steps"] - before.get("moe.steps", 0)
    pairs = after["moe.local_pairs"] - before.get("moe.local_pairs", 0)
    assert steps == 3
    tokens, layers_ = 4 * 32, 2
    assert 0 < pairs <= 3 * tokens * layers_ * 2
    assert after["moe.max_expert_pairs"] \
        - before.get("moe.max_expert_pairs", 0) >= pairs / 2


def test_a_program_without_marks_fetches_nothing_more():
    x = layers.data("x", shape=[4])
    y = layers.fc(x, 3)
    main = fluid.default_main_program()
    assert main._device_counters == {}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = telemetry.snapshot()
    out = exe.run(feed={"x": np.ones((2, 4), "float32")}, fetch_list=[y])
    assert len(out) == 1
    after = telemetry.snapshot()
    assert {k for k in after if k.endswith(".steps")} \
        == {k for k in before if k.endswith(".steps")}


def test_mark_counter_counts_and_gauges():
    x = layers.data("x", shape=[4])
    total = layers.reduce_sum(x)
    most = layers.reduce_max(x)
    main = fluid.default_main_program()
    main.mark_counter(total, "t30.sum")
    main.mark_counter(most, "t30.max", kind="gauge")
    with pytest.raises(ValueError):
        main.mark_counter(most, "t30.bad", kind="histogram")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for v in (1.0, 2.0):
        exe.run(feed={"x": np.full((2, 4), v, "float32")}, fetch_list=[])
    snap = telemetry.snapshot()
    assert snap["t30.sum"] == 8 + 16 and snap["t30.max"] == 2.0
    assert snap["t30.steps"] == 2
