"""Mellum 2 through the Program IR, against the benchmark's plain reference
(chipbench/reference/mellum2.py, which imports nothing of the program):
YaRN's frequencies against numbers worked by hand for the published
config, the softmax router against `jax.nn.softmax` + top-k, the window as
an attribute of the one attention op, each kind of attention layer (the
composition, and the tiled kernels in the interpreter), the share of an
expert-parallel deployment, the whole model's first steps through
`Executor.run`, the planted faults (the window left out among them), and
what `amp.cast_program_to_bf16` keeps float32.

Sizes: hidden 64, 4 heads over 2 of 16, 8 experts top 2, window 32, T
128-256, all on the CPU.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import mellum2
from paddle_tpu.ops import kernels_nn
from paddle_tpu.ops import registry as ops_registry
from paddle_tpu.ops.pallas import flash_attention as fa

from chipbench import correct, manifest
from chipbench.reference import mellum2 as ref

from test_lfm2_moe import _op_and_grads, _train
from test_solar_open2 import _check, _f32, _part

# the published rope_parameters, letter for letter
FULL = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
SLIDING = {"rope_type": "default", "rope_theta": 500000}

SMALL = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
             layer_types=["sliding_attention", "full_attention"],
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             moe_intermediate_size=48, num_experts=8, num_experts_per_tok=2,
             sliding_window=32)


# ------------------------------------------------- positions by layer type
def test_yarn_by_hand_for_the_published_config():
    """dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): dim(32) = 18.08,
    dim(1) = 34.98, so the ramp runs over pairs 18 .. 35 of the 64; the
    fast pairs keep theta^(-2j/128), the slow ones a sixteenth of it; the
    scale is 0.1 ln 16 + 1."""
    def dim(r):
        return 128 * math.log(8192 / (2 * math.pi * r)) \
            / (2 * math.log(500000))
    assert round(dim(32), 2) == 18.08 and round(dim(1), 2) == 34.98
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (18, 35)
    e = [500000 ** (-2 * j / 128) for j in range(64)]
    want = []
    for j in range(64):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        want.append(e[j] / 16 * ramp + e[j] * (1 - ramp))
    assert want[0] == 1.0 and want[18] == e[18]             # untouched
    assert want[35] == e[35] / 16 and want[63] == e[63] / 16
    assert want[63] == pytest.approx(1.5346e-7, rel=1e-3)   # the last
    assert want[26] == pytest.approx(e[26] * (1 - 8 / 17 * 15 / 16))
    assert FULL["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1, abs=1e-15)
    # the reference and the op's kernel both give these, in float32
    got, m = ref.inv_freq(FULL, 128)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6)
    assert m == FULL["attention_factor"]
    inv = jnp.asarray(e, jnp.float32)
    attrs = {"theta": 500000.0, **{k: v for k, v in FULL.items()
                                   if k != "rope_theta"}}
    np.testing.assert_allclose(
        np.asarray(kernels_nn._yarn_inv_freq(inv, attrs, 128)), want,
        rtol=2e-6)
    # the sliding layers' block: the plain power law, no scale
    plain, one = ref.inv_freq(SLIDING, 128)
    np.testing.assert_allclose(np.asarray(plain), e, rtol=2e-6)
    assert one == 1.0


@pytest.mark.parametrize("rope", [FULL, SLIDING], ids=["yarn", "default"])
def test_rotary_embedding_by_a_rope_parameters_block(rope):
    """The op, forward and gradient, against the reference's rotation at
    head 128 (the ramp lies inside its 64 pairs) and T = 40."""
    vals = {"x": _f32(2, 40, 3, 128)}
    _check(lambda v: mellum2._rope(v["x"], rope),
           lambda v: ref.rope(v["x"], rope), vals, tol=1e-4)


def test_the_default_rotary_call_is_unchanged_and_yarn_needs_its_keys():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[2, 8, 2, 16], append_batch_size=False)
        layers.rotary_embedding(x, 1e6)
        layers.rotary_embedding(x, theta=5e5, rope_type="yarn", factor=16,
                                original_max_position_embeddings=8192,
                                attention_factor=FULL["attention_factor"])
        with pytest.raises(ValueError):
            layers.rotary_embedding(x, rope_type="yarn")
        with pytest.raises(ValueError):     # the published block gives it
            layers.rotary_embedding(x, rope_type="yarn", factor=16,
                                    original_max_position_embeddings=8192)
        with pytest.raises(ValueError):
            layers.rotary_embedding(x, rope_type="linear")
    plain, yarn = [op for op in main.global_block().ops
                   if op.type == "rotary_embedding"]
    assert plain.attrs == {"theta": 1e6}
    assert yarn.attrs["rope_type"] == "yarn"
    assert yarn.attrs["attention_factor"] == FULL["attention_factor"]
    assert (yarn.attrs["beta_fast"], yarn.attrs["beta_slow"]) == (32.0, 1.0)


# ------------------------------------------------------------- the router
def test_moe_route_softmax_against_jax_softmax_and_top_k():
    """scoring="softmax": the softmax over ALL the experts, the top k of
    it, renormalised over (their sum + 1e-6); no bias; the gradient
    reaches the router's weight through the weights."""
    vals = {"x": _f32(2, 24, 64), "r.w_0": _f32(64, 8, scale=0.3)}

    def build(v):
        _, w = layers.moe_route(v["x"], 8, 2, use_expert_bias=False,
                                scoring="softmax", name="r")
        return w

    def want(v):
        p = jax.nn.softmax(jnp.einsum(
            "bth,he->bte", v["x"], v["r.w_0"],
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        top, _ = jax.lax.top_k(p, 2)
        return top / (jnp.sum(top, -1, keepdims=True) + 1e-6)

    _check(build, want, vals)
    out, _, _ = _op_and_grads(build, vals)
    assert np.allclose(out.sum(-1), 1.0, atol=1e-4) and (out > 0).all()
    # the chosen ids are the reference's, the lower id first among equals
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[2, 24, 64], append_batch_size=False)
        idx, _ = layers.moe_route(x, 8, 2, use_expert_bias=False,
                                  scoring="softmax", name="r")
        with pytest.raises(ValueError):
            layers.moe_route(x, 8, 2, scoring="tanh")
    op = [o for o in main.global_block().ops if o.type == "moe_route"][0]
    assert op.attrs["scoring"] == "softmax" and "Bias" not in op.inputs
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set("r.w_0", vals["r.w_0"])
        got = np.asarray(exe.run(main, feed={"x": vals["x"]},
                                 fetch_list=[idx])[0])
    chosen, _ = ref.route(jnp.asarray(vals["x"]),
                          jnp.asarray(vals["r.w_0"]), 2)
    np.testing.assert_array_equal(got, np.asarray(chosen))


def test_the_sigmoid_routers_op_is_as_it_was():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[2, 8, 64], append_batch_size=False)
        layers.moe_route(x, 8, 2, name="r")
    op = [o for o in main.global_block().ops if o.type == "moe_route"][0]
    # ... and the site its `name=` declares (PR 38), which no kernel sees
    assert set(op.attrs) == {"k", "norm_topk_prob", "routed_scaling_factor",
                             "op_namescope"}
    assert op.attrs["op_namescope"] == "r"
    assert "Bias" in op.inputs


# ------------------------------------------- the window, on the one op
def _attn_program(window, causal=True):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data("q", shape=[2, 128, 4, 16], append_batch_size=False)
        k = layers.data("k", shape=[2, 128, 2, 16], append_batch_size=False)
        out = layers.flash_attention(q, k, k, causal=causal, window=window)
    return main, out


def test_the_window_is_an_attribute_of_the_one_attention_op():
    main, _ = _attn_program(32)
    op, = [o for o in main.global_block().ops
           if o.type == "flash_attention"]
    assert op.attrs["window"] == 32 and op.attrs["causal"] is True
    plain, _ = _attn_program(None)
    op, = [o for o in plain.global_block().ops
           if o.type == "flash_attention"]
    assert set(op.attrs) == {"causal", "scale", "layout"}     # as it was
    for bad in (dict(window=32, causal=False), dict(window=0)):
        with pytest.raises(ValueError):
            _attn_program(**bad)


@pytest.mark.parametrize("mode", ["auto", "interpret"])
@pytest.mark.parametrize("window", [32, 128, 1000, None])
def test_the_op_masks_the_band_by_composition_and_by_kernel(window, mode):
    """q, k over [2, 128, 4 over 2, 16]: the op's output against the
    reference's explicit mask, on the composition (`auto` on the CPU) and
    on the tiled kernels in the interpreter; a window at or over the
    length is no window, and the kernel's STATS say so."""
    main, out = _attn_program(window)
    q, k = _f32(2, 128, 4, 16), _f32(2, 128, 2, 16)
    before = dict(fa.STATS)
    ops_registry.set_mode(mode)
    try:
        exe = fluid.Executor(fluid.CPUPlace())
        got = np.asarray(exe.run(main, feed={"q": q, "k": k},
                                 fetch_list=[out])[0])
    finally:
        ops_registry.set_mode("auto")
    with jax.default_matmul_precision("highest"):
        want = ref._attend_block(
            jnp.asarray(q).reshape(2, 128, 2, 2, 16), jnp.asarray(k),
            jnp.asarray(k), 0, window, "float32").reshape(2, 128, 4, 16)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5, rtol=2e-5)
    assert fa.STATS["pallas_calls"] - before["pallas_calls"] \
        == (mode == "interpret")
    assert fa.STATS["tiled_window"] == before["tiled_window"]   # no vjp
    if window == 32:        # and the band is not the causal half
        with jax.default_matmul_precision("highest"):
            full = ref._attend_block(
                jnp.asarray(q).reshape(2, 128, 2, 2, 16), jnp.asarray(k),
                jnp.asarray(k), 0, None, "float32").reshape(2, 128, 4, 16)
        assert np.abs(np.asarray(full) - got).max() > 0.05


# --------------------------------------------------------- pieces of a layer
def _ref_cfg(c, **over):
    """The reference reads a dict: the keys of the configuration file."""
    return dict({
        "hidden_size": c.hidden_size, "layer_types": c.layer_types,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "head_dim": c.head_dim, "rms_norm_eps": c.rms_norm_eps,
        "rope_parameters": c.rope_parameters,
        "sliding_window": c.sliding_window, "vocab_size": c.vocab_size,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_experts": c.num_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "experts_held": c.experts_held, "first_expert": c.first_expert},
        **over)


def _layer_params(rc):
    model = manifest.Manifest().model("mellum2")
    out = {}
    for name, shape, kind in model.param_specs(dict(rc, layer_types=["x"])):
        if name.startswith("l0_"):
            out[name] = 1 + _f32(*shape, scale=0.1) if kind == "norm" \
                else _f32(*shape, scale=0.3 if kind == "router" else 0.15)
    return out


@pytest.mark.parametrize("mode", ["auto", "interpret"])
@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_each_kind_of_attention_layer_matches_the_reference(kind, mode):
    """q/k norm, the layer type's positions, the layer type's mask and
    W_o, over [2, 256, 64] with a window of 32: the composition, and the
    tiled kernels in the interpreter."""
    cfg = mellum2.Mellum2Config(**SMALL)
    rc = _ref_cfg(cfg)
    params = _layer_params(rc)
    x = _f32(2, 256, 64)
    ops_registry.set_mode(mode)
    try:
        got = _part(lambda xv, c, name: mellum2._attention(xv, c, name, kind),
                    cfg, x, params)
    finally:
        ops_registry.set_mode("auto")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention(
            {k: jnp.asarray(v) for k, v in params.items()}, "l0",
            jnp.asarray(x), kind, rc, "float32"))
        other = np.asarray(ref.attention(
            {k: jnp.asarray(v) for k, v in params.items()}, "l0",
            jnp.asarray(x), ({"sliding_attention", "full_attention"}
                             - {kind}).pop(), rc, "float32"))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    assert np.abs(got - other).max() > 1e-2      # the kinds do differ


def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """One layer, uncut, as the reference computes it: 8 experts, top 2,
    no shared expert. Against it the PROGRAM's expert layer as the four
    expert-parallel ranks of a deployment hold it, 2 experts each: the
    four shares' parts of the layer's output add up to the uncut layer,
    with what every rank computes alike (attention, the residual)
    counted once."""
    whole = mellum2.Mellum2Config(**SMALL)
    rc = _ref_cfg(whole, layer_types=["sliding_attention"])
    params = _layer_params(rc)
    h = _f32(2, 128, 64)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._layer(jp, jnp.asarray(h), 0,
                                     "sliding_attention", rc, "float32"))
        x1 = ref.rms_norm(h, params["l0_attn_norm.w_0"], 1e-6)
        h1 = h + np.asarray(ref.attention(jp, "l0", x1, "sliding_attention",
                                          rc, "float32"))
        x2 = np.asarray(ref.rms_norm(h1, params["l0_ffn_norm.w_0"], 1e-6))

    def share(first):
        cfg = mellum2.Mellum2Config(**SMALL, experts_held=2,
                                    first_expert=first)
        held = dict(params)
        for j in range(3):
            held[f"l0_experts.w_{j}"] = \
                params[f"l0_experts.w_{j}"][first:first + 2]
        return _part(mellum2._moe, cfg, x2, held)

    parts = [share(first) for first in range(0, 8, 2)]
    np.testing.assert_allclose(h1 + sum(parts), want, atol=5e-5, rtol=5e-4)
    # a share alone is not the layer, and no share is empty
    assert all(np.abs(p).max() > 1e-3 for p in parts)
    assert np.abs(h1 + parts[0] - want).max() > 1e-3
    # the reference's own shares add up too, walked in blocks of experts
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.moe(jp, "l0", jnp.asarray(x2), rc, "float32"))
        halves = sum(
            np.asarray(ref.moe(
                {**jp, **{f"l0_experts.w_{j}":
                          jp[f"l0_experts.w_{j}"][first:first + 4]
                          for j in range(3)}}, "l0", jnp.asarray(x2),
                dict(rc, first_expert=first), "float32"))
            for first in (0, 4))
    np.testing.assert_allclose(halves, uncut, atol=2e-5, rtol=2e-5)


def test_the_configuration_is_the_published_one_by_default():
    full = mellum2.Mellum2Config()
    assert len(full.layer_types) == 28
    assert full.layer_types[:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert [i for i, k in enumerate(full.layer_types)
            if k == "full_attention"] == list(range(3, 28, 4))
    assert (full.hidden_size, full.head_dim, full.num_attention_heads,
            full.num_key_value_heads) == (2304, 128, 32, 4)
    assert (full.num_experts, full.num_experts_per_tok, full.experts_held,
            full.moe_intermediate_size) == (64, 8, 64, 896)
    assert full.sliding_window == 1024 and full.vocab_size == 98304
    assert full.rope_parameters == {"full_attention": FULL,
                                    "sliding_attention": SLIDING}
    assert mellum2.Mellum2Config(use_sliding_window=False).sliding_window \
        is None
    cut = mellum2.Mellum2Config(experts_held=16, first_expert=48)
    assert cut.experts_held == 16
    with pytest.raises(ValueError):
        mellum2.Mellum2Config(experts_held=16, first_expert=49)
    with pytest.raises(ValueError):
        mellum2.Mellum2Config(layer_types=["chunked_attention"])
    for variant in (dict(mlp_layer_types=["dense"] * 28),
                    dict(tie_word_embeddings=True),
                    dict(attention_bias=True)):
        with pytest.raises(NotImplementedError):
            mellum2.Mellum2Config(**variant)


# ---------------------------------------------------------- the whole model
def _tiny_cell(length=136):
    """The benchmark's own tiny configuration of the cell and its model
    file (hidden 64, 4 heads over 2 of 16, 4 of 8 experts, top 2, window
    32, one sliding and one full layer)."""
    here = os.path.dirname(os.path.abspath(__file__))
    man = manifest.Manifest()
    cfg = man.config("mellum2_12b_train_ep4")
    with open(os.path.join(here, "chipbench_tests", "tiny", "configs",
                           "mellum2_12b_train_ep4.json")) as f:
        cfg.update(json.load(f))
    traffic = {"kind": "lm_stream_batches", "rows": 2, "length": length,
               "pool": 3}
    return man.model("mellum2"), cfg, traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 36])
def test_three_steps_through_executor_run_match_the_reference(seed):
    """Loss of each step, the first gradient per leaf, the parameters'
    change per leaf after three Adam steps, float32 on both sides; T =
    136 is over four windows."""
    model, cfg, traffic = _tiny_cell()
    seen, params, batches, _ = _train(model, cfg, traffic, seed, "float32")
    want = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                 "float32", 1)
    np.testing.assert_allclose(seen["loss"], want["loss"], rtol=2e-5)
    for n, g in want["grad_norm"].items():
        assert seen["grad_norm"][n] == pytest.approx(g, rel=3e-3, abs=1e-7), n
    numbers = correct.train_numbers(seen, want)
    assert numbers["loss_gap"] < 1e-4 and numbers["grad_gap"] < 3e-3
    assert numbers["delta_gap"] < 0.02
    assert all(g > 0 for g in want["grad_norm"].values())
    assert {"l0_q_norm.w_0", "l1_router.w_0", "lm_head.w_0"} \
        <= set(want["grad_norm"])


def test_the_tiled_kernels_train_the_model_in_the_interpreter():
    """The same three steps with every Pallas kernel in the interpreter
    (T = 128: one block a head): the sliding layer's backward is the
    windowed one, the full layer's the plain one, and the numbers are the
    reference's."""
    model, cfg, traffic = _tiny_cell(128)
    before = dict(fa.STATS)
    ops_registry.set_mode("interpret")
    try:
        seen, params, batches, _ = _train(model, cfg, traffic, 3, "float32")
    finally:
        ops_registry.set_mode("auto")
    assert fa.STATS["tiled_window"] == before["tiled_window"] + 1
    assert fa.STATS["tiled_bwd_fused"] == before["tiled_bwd_fused"] + 2
    want = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                 "float32", 1)
    numbers = correct.train_numbers(seen, want)
    assert numbers["loss_gap"] < 1e-4 and numbers["grad_gap"] < 3e-3
    assert numbers["delta_gap"] < 0.02


def test_the_model_is_built_from_one_op_type_a_mechanism():
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    block = main.global_block()
    types = [op.type for op in block.ops]
    assert {"rms_norm", "rotary_embedding", "flash_attention", "moe_route",
            "moe_expert_ffn"} <= set(types)
    attn = [op for op in block.ops if op.type == "flash_attention"]
    assert [op.attrs.get("window") for op in attn] == [32, None]
    assert all(op.attrs["causal"] for op in attn)
    rope = [op for op in block.ops if op.type == "rotary_embedding"]
    assert [op.attrs.get("rope_type", "default") for op in rope] \
        == ["default", "default", "yarn", "yarn"]            # q, k a layer
    assert rope[2].attrs["factor"] == 16.0 and rope[0].attrs == {
        "theta": 500000.0}
    for op in block.ops:
        if op.type == "flash_attention":      # 4 query heads over 2
            assert block.var(op.inputs["Q"][0]).shape[2] == 4
            assert block.var(op.inputs["K"][0]).shape[2] == 2
        if op.type == "moe_expert_ffn":
            assert block.var(op.inputs["W1"][0]).shape[0] == 4
        if op.type == "moe_route":
            assert op.attrs["scoring"] == "softmax"
            assert "Bias" not in op.inputs
    names = {v.name for v in main.all_parameters()}
    assert {"embed.w_0", "lm_head.w_0", "l0_q_norm.w_0"} <= names
    assert block.var("l0_router.w_0").shape == (64, 8)
    assert set(main._device_counters) == {"moe.local_pairs",
                                          "moe.max_expert_pairs"}


# ------------------------------------------------------------- bfloat16
def test_cast_to_bf16_keeps_what_the_configuration_says_float32():
    """RMSNorm weights (the per-head ones too) and the router's weight and
    routing weights keep float32 by the slot an op reads them through;
    every other parameter is bfloat16."""
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    fluid.amp.cast_program_to_bf16(main)
    keep = set(cfg["precision"]["float32_parameters"])
    assert keep == {"norm", "router"}
    block = main.global_block()
    for name, _, kind in model.param_specs(cfg):
        assert str(block.var(name).dtype) == (
            "float32" if kind in keep else "bfloat16"), name
    for op in block.ops:
        if op.type == "moe_route":
            assert str(block.var(op.outputs["TopkW"][0]).dtype) == "float32"
        if op.type == "flash_attention":
            assert str(block.var(op.inputs["Q"][0]).dtype) == "bfloat16"
    made = model.make_params(cfg, 3, "bfloat16")
    for name, _, kind in model.param_specs(cfg):
        assert str(made[name].dtype) == (
            "float32" if kind in keep else "bfloat16"), name
    assert model.bias_names(cfg) == []


def test_bf16_steps_pass_the_limits_and_the_faults_do_not():
    """The program cast to bfloat16 against the float32 reference, by the
    numbers `correct` compares; the reference's own int8 control stands
    further off than the program does; and the planted fault in the
    reference, the window left out of the sliding layers, fails a limit:
    the comparison sees the mechanism."""
    model, cfg, traffic = _tiny_cell()
    seen, params, batches, _ = _train(model, cfg, traffic, 5, "bfloat16",
                                      bf16=True)
    want = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                 "float32", 1)
    numbers = correct.train_numbers(seen, want)
    assert correct.judge(numbers, cfg["limits"])[1], numbers
    low = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                "int8", 1)
    control = correct.train_numbers(low, want)
    assert control["grad_gap"] > 2 * numbers["grad_gap"]
    no_window = model.reference_steps(
        params, dict(cfg, sliding_window=None), batches[:3],
        cfg["optimizer"], "float32", 1)
    fault = correct.train_numbers(no_window, want)
    assert not correct.judge(fault, cfg["limits"])[1], fault
    assert fault["grad_gap"] > 10 * numbers["grad_gap"]
