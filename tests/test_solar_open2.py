"""Solar Open 2 through the Program IR, against the benchmark's plain
reference (chipbench/reference/solar_open2.py, which imports nothing of the
program): the chunked linear-attention scan against the token-by-token
recurrence (forward and every gradient, a length off the chunk, strong
decay), the small ops around it, the two mixers, the share of a tensor-
and expert-parallel deployment, the whole model's first steps through
`Executor.run`, and what `amp.cast_program_to_bf16` keeps float32.

Sizes: hidden 64, heads of 16, 16 experts top 2, T 96-200, all on the CPU.
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import solar_open2 as solar
from paddle_tpu.ops import kernels_scan as scan
from paddle_tpu.ops import registry as ops_registry
from paddle_tpu.ops.kern import registry as kreg

from chipbench import correct, manifest
from chipbench.reference import solar_open2 as ref

# an op as a program with its gradients; a model's first steps through
# Executor.run: the second cell's helpers serve any model file
from test_lfm2_moe import _op_and_grads, _train

RNG = np.random.default_rng(34)


def _f32(*shape, scale=1.0):
    return (RNG.standard_normal(shape) * scale).astype("float32")


def _ref_and_grads(fn, values, probe):
    def loss(vals):
        out = fn(vals)
        return jnp.sum(out * probe), out
    with jax.default_matmul_precision("highest"):
        (_, out), g = jax.value_and_grad(loss, has_aux=True)(
            {k: jnp.asarray(v) for k, v in values.items()})
    return np.asarray(out), {k: np.asarray(v) for k, v in g.items()}


def _check(build, fn, values, tol=2e-5):
    out, grads, probe = _op_and_grads(build, values)
    want, want_g = _ref_and_grads(fn, values, probe)
    np.testing.assert_allclose(out, want, atol=tol, rtol=tol)
    assert set(grads) == set(values)
    for n in values:
        scale = max(1.0, float(np.abs(want_g[n]).max()))
        np.testing.assert_allclose(grads[n], want_g[n], atol=tol * 10 * scale,
                                   rtol=tol * 10, err_msg=n)


def _unit(*shape):
    x = RNG.standard_normal(shape)
    return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype("float32")


def _scan_case(T, g_min, B=2, H=3, D=16):
    return {"q": _unit(B, T, H, D), "k": _unit(B, T, H, D),
            "v": _f32(B, T, H, D),
            "g": RNG.uniform(g_min, 0.0, (B, T, H, D)).astype("float32"),
            "beta": RNG.uniform(0.0, 2.0, (B, T, H)).astype("float32")}


# ------------------------------------------------------- the chunked scan
@pytest.mark.parametrize("T,g_min", [
    (128, -0.1),       # two whole chunks, a slow decay
    (200, -1.6),       # off a multiple of 64; the initialisation's range
    (150, -10.0),      # strong decay: exp(-G) over a chunk is exp(640)
    (40, -10.0),       # shorter than a chunk
], ids=["T128", "T200_off_the_chunk", "T150_strong_decay",
        "T40_strong_decay"])
def test_kda_attention_chunked_matches_the_token_by_token_recurrence(
        T, g_min):
    """The op (chunks of 64, sub-blocks of 16) against the reference's
    `lax.scan` over t: the output and the gradient of q, k, v, g and
    beta, finite whatever the decay."""
    vals = _scan_case(T, g_min)
    out, grads, probe = _op_and_grads(
        lambda v: layers.kda_attention(v["q"], v["k"], v["v"], v["g"],
                                       v["beta"]), vals)
    assert np.isfinite(out).all()
    assert all(np.isfinite(g).all() for g in grads.values())
    want, want_g = _ref_and_grads(
        lambda v: ref.delta_rule(v["q"], v["k"], v["v"], v["g"], v["beta"]),
        vals, probe)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    assert set(grads) == set(vals)
    for n in vals:
        np.testing.assert_allclose(
            grads[n], want_g[n], rtol=2e-4,
            atol=2e-4 * float(np.abs(want_g[n]).max()), err_msg=n)


def test_the_recurrence_by_hand_at_the_first_two_tokens():
    """S_1 = beta_1 k_1 v_1^T; S_2 = (I - beta_2 k_2 k_2^T) diag(e^g_2) S_1
    + beta_2 k_2 v_2^T; o_t = S_t^T q_t / sqrt(D)."""
    v = _scan_case(2, -1.0, B=1, H=1, D=4)
    q, k, val, g, beta = (np.asarray(v[n], "float64")[0, :, 0]
                          for n in ("q", "k", "v", "g", "beta"))
    S1 = beta[0] * np.outer(k[0], val[0])
    S2 = (np.eye(4) - beta[1] * np.outer(k[1], k[1])) \
        @ (np.exp(g[1])[:, None] * S1) + beta[1] * np.outer(k[1], val[1])
    want = np.stack([S1.T @ q[0], S2.T @ q[1]]) / 2.0
    for fn in (ref.delta_rule, scan.kda_recurrent, scan.kda_chunked):
        got = fn(*(jnp.asarray(v[n]) for n in ("q", "k", "v", "g", "beta")))
        np.testing.assert_allclose(np.asarray(got)[0, :, 0], want, atol=1e-6)


def test_the_program_walks_chunks_not_tokens():
    """One Fluid op; inside it one loop, over the T / 64 chunk states (and
    the solve's own over a chunk's rows), none over the T tokens."""
    T = 256
    v = _scan_case(T, -1.0, B=1, H=2)
    args = [jnp.asarray(v[n]) for n in ("q", "k", "v", "g", "beta")]
    text = str(jax.make_jaxpr(scan.kda_chunked)(*args))
    lengths = [int(x) for x in re.findall(r"length=(\d+)", text)]
    assert T // scan.CHUNK in lengths and T not in lengths
    assert max(lengths) <= max(T // scan.CHUNK, scan.CHUNK)
    # bfloat16 in, float32 inside, bfloat16 out
    out = scan.kda_chunked(*(a.astype(jnp.bfloat16) for a in args[:3]),
                           args[3], args[4])
    assert out.dtype == jnp.bfloat16


def test_the_registry_counts_the_scans_calls_and_holds_its_reference():
    """The op asks the registry every time; heads of 16 on the CPU are the
    composition's (the kernels' own gate says no: tests/test_kda_kernel.py
    holds what it takes), and the spec's example runs the kernels."""
    spec = kreg.get("kda_attention")
    assert spec.reference is scan.kda_recurrent

    def calls():
        per = kreg.STATS["by_kernel"].get("kda_attention", {})
        return per.get("accepted", 0) + per.get("rejected", 0)

    before = calls()
    v = _scan_case(70, -2.0)
    _op_and_grads(lambda x: layers.kda_attention(
        x["q"], x["k"], x["v"], x["g"], x["beta"]), v)
    assert calls() > before
    ops_registry.set_mode("interpret")
    try:
        ok, detail = kreg.parity_check(
            "kda_attention", *spec.example(np.random.RandomState(0)))
    finally:
        ops_registry.set_mode("auto")
    assert ok is True, detail


# ------------------------------------------------- the ops around the scan
def test_l2_norm_matches_the_reference():
    vals = {"x": _f32(2, 8, 4, 16)}
    _check(lambda v: layers.l2_norm(v["x"]),
           lambda v: ref.l2_norm(v["x"]), vals)
    out = np.asarray(ref.l2_norm(vals["x"]))
    np.testing.assert_allclose(np.sum(out * out, -1), 1.0, atol=1e-4)
    # a zero row stays zero: the epsilon is under the root
    assert not np.asarray(ref.l2_norm(np.zeros((1, 4), "float32"))).any()


def test_kda_gate_is_the_log_of_a_per_channel_decay():
    vals = {"x": _f32(2, 8, 3, 16),
            "d.w_0": np.log(RNG.uniform(1, 16, 3)).astype("float32"),
            "d.w_1": _f32(3, 16)}

    def want(v):
        return -jnp.exp(v["d.w_0"])[:, None] * jax.nn.softplus(
            v["x"] + v["d.w_1"])

    _check(lambda v: layers.kda_gate(v["x"], name="d"), want, vals)
    out = np.asarray(want({k: jnp.asarray(x) for k, x in vals.items()}))
    assert (out < 0).all() and out.shape == (2, 8, 3, 16)


def _params(specs, exclude=()):
    out = {}
    for name, shape, kind in specs:
        if kind == "norm":
            out[name] = 1 + _f32(*shape, scale=0.1)
        elif kind == "a_log":
            out[name] = np.log(RNG.uniform(1, 16, shape)).astype("float32")
        elif kind == "dt_bias":
            out[name] = _f32(*shape)
        elif kind == "filter":
            out[name] = RNG.uniform(-0.5, 0.5, shape).astype("float32")
        else:
            out[name] = _f32(*shape, scale=0.3 if kind == "router" else 0.15)
    return out


SMALL = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             linear_attn_config={"head_dim": 16, "num_heads": 4,
                                 "short_conv_kernel_size": 4},
             moe_intermediate_size=48, n_routed_experts=16,
             num_experts_per_tok=2)


def _ref_cfg(model_cfg, **over):
    """The reference reads a dict: the keys of the configuration file."""
    c = model_cfg
    return dict({
        "hidden_size": c.hidden_size, "layer_types": c.layer_types,
        "heads_held": c.heads_held, "kv_heads_held": c.kv_heads_held,
        "head_dim": c.head_dim, "rms_norm_eps": c.rms_norm_eps,
        "linear_attn_config": {
            "head_dim": c.linear_head_dim,
            "short_conv_kernel_size": c.short_conv_kernel_size},
        "gate_rank": c.gate_rank, "vocab_size": c.vocab_size,
        "moe_intermediate_size": c.moe_intermediate_size,
        "n_routed_experts": c.n_routed_experts,
        "n_shared_experts": c.n_shared_experts,
        "num_experts_per_tok": c.num_experts_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scaling_factor,
        "use_expert_bias": c.use_expert_bias,
        "experts_held": c.experts_held, "first_expert": c.first_expert},
        **over)


def _part(fn, cfg, x, params, name="l0"):
    """One piece of the model (`solar._gqa`, `_kda`, `_ffn`) as a program
    over the data `x`, its parameters set from `params`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data("x", shape=list(x.shape), append_batch_size=False)
        out = fn(xv, cfg, name)
        out = out[0] if isinstance(out, tuple) else out
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        declared = {v.name for v in main.all_parameters()}
        assert declared <= set(params), declared - set(params)
        for n in declared:
            scope.set(n, params[n])
        for v in main.global_block().vars.values():
            if v.name.endswith(".bias") and v.persistable:
                scope.set(v.name, params[v.name])
        return np.asarray(exe.run(main, feed={"x": x}, fetch_list=[out])[0])


def _layer_specs(cfg_dict, kind):
    """The benchmark's own parameter list for one layer of `kind`."""
    model = manifest.Manifest().model("solar_open2")
    specs = model.param_specs(dict(cfg_dict, layer_types=[kind]))
    return [s for s in specs if s[0].startswith("l0_")]


@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_each_mixer_matches_the_reference(kind):
    cfg = solar.SolarOpen2Config(**SMALL)
    rc = _ref_cfg(cfg)
    params = _params(_layer_specs(rc, kind))
    x = _f32(2, 96, 64)
    fn, want = (solar._gqa, ref.gqa_mixer) if kind == "gqa" \
        else (solar._kda, ref.kda_mixer)
    got = _part(fn, cfg, x, params)
    with jax.default_matmul_precision("highest"):
        w = np.asarray(want({k: jnp.asarray(v) for k, v in params.items()},
                            "l0", jnp.asarray(x), rc, "float32"))
    np.testing.assert_allclose(got, w, atol=2e-5, rtol=2e-4)


# ------------------------------------------------------------ THE SHARE TEST
def _head_share(params, kind, s, n_shares, cfg):
    """The parameters of tensor-parallel rank `s` of `n_shares`: its query
    heads' columns of the projections in, their rows of W_o, the
    key-value heads they read; what is whole on every rank stays whole."""
    D = cfg.head_dim
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    q = slice(s * H // n_shares * D, (s + 1) * H // n_shares * D)
    kv = slice(s * KV // n_shares * D, (s + 1) * KV // n_shares * D)
    heads = slice(s * H // n_shares, (s + 1) * H // n_shares)
    out = dict(params)
    if kind == "gqa":
        for n, cols in (("q", q), ("g", q), ("k", kv), ("v", kv)):
            out[f"l0_{n}.w_0"] = params[f"l0_{n}.w_0"][:, cols]
    else:
        for n in ("q", "k", "v"):
            out[f"l0_{n}.w_0"] = params[f"l0_{n}.w_0"][:, q]
            out[f"l0_{n}_conv.w_0"] = params[f"l0_{n}_conv.w_0"][q]
        for n in ("a_up", "g_up"):
            out[f"l0_{n}.w_0"] = params[f"l0_{n}.w_0"][:, q]
        out["l0_decay.w_0"] = params["l0_decay.w_0"][heads]
        out["l0_decay.w_1"] = params["l0_decay.w_1"][heads]
        out["l0_b.w_0"] = params["l0_b.w_0"][:, heads]
    out["l0_o.w_0"] = params["l0_o.w_0"][q]
    return out


@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_the_shares_of_all_ranks_add_up_to_the_uncut_layer(kind):
    """One layer, uncut, as the reference computes it: 4 query heads over 2
    key-value heads, 16 experts, a shared expert. Against it the PROGRAM's
    pieces as the ranks of a deployment hold them: the mixer as two
    tensor-parallel ranks of 2 query heads over 1 key-value head each,
    the expert layer as four expert-parallel ranks of 4 experts each.
    The shares' parts of the layer's output add up to the uncut layer
    with what every rank computes alike (the residual, the shared
    expert) counted once."""
    whole = solar.SolarOpen2Config(**SMALL)
    rc = _ref_cfg(whole, layer_types=[kind])
    params = _params(_layer_specs(rc, kind))
    params["l0_router.bias"] = _f32(16, scale=0.2)
    h = _f32(2, 96, 64)
    jp = {k: jnp.asarray(v) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._layer(jp, jnp.asarray(h), 0, kind, rc,
                                     "float32"))
        x1 = np.asarray(ref.rms_norm(h, params["l0_mixer_norm.w_0"], 1e-5))

    # the mixer: two ranks, each a model of 2 query heads over 1
    mixer = solar._gqa if kind == "gqa" else solar._kda
    mixed = 0.0
    for s in range(2):
        share = solar.SolarOpen2Config(**SMALL, heads_held=2,
                                       kv_heads_held=1, first_head=2 * s)
        mixed = mixed + _part(mixer, share, x1,
                              _head_share(params, kind, s, 2, whole))
    h1 = h + mixed                                  # the residual, once
    with jax.default_matmul_precision("highest"):
        x2 = np.asarray(ref.rms_norm(h1, params["l0_ffn_norm.w_0"], 1e-5))

    # the experts: four ranks of 4; the shared expert from one of them
    def ffn(first, shared):
        share = solar.SolarOpen2Config(
            **SMALL, experts_held=4, first_expert=first,
            n_shared_experts=shared)
        held = dict(params)
        for j in range(3):
            held[f"l0_experts.w_{j}"] = \
                params[f"l0_experts.w_{j}"][first:first + 4]
        return _part(solar._ffn, share, x2, held)

    routed = sum(ffn(first, 0) for first in range(0, 16, 4))
    shared = ffn(0, 1) - ffn(0, 0)
    got = h1 + routed + shared
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-4)
    # and a share alone is not the layer: the absent heads' part is missing
    assert np.abs(h + mixed / 2 - (want - routed - shared)).max() > 1e-3


def test_the_configuration_holds_whole_groups_of_heads():
    full = solar.SolarOpen2Config()
    assert len(full.layer_types) == 48
    assert [i for i, k in enumerate(full.layer_types) if k == "gqa"] \
        == list(range(0, 48, 4))
    assert (full.heads_held, full.kv_heads_held, full.experts_held) \
        == (64, 8, 320)
    assert (full.linear_head_dim, full.short_conv_kernel_size,
            full.gate_rank) == (128, 4, 128)
    cut = solar.SolarOpen2Config(heads_held=8, kv_heads_held=1, first_head=8,
                                 experts_held=8, first_expert=312)
    assert cut.heads_held == 8
    for bad in (dict(heads_held=8, kv_heads_held=2),
                dict(heads_held=8, kv_heads_held=1, first_head=4),
                dict(heads_held=8, kv_heads_held=1, first_head=64),
                dict(experts_held=8, first_expert=316)):
        with pytest.raises(ValueError):
            solar.SolarOpen2Config(**bad)
    with pytest.raises(NotImplementedError):
        solar.SolarOpen2Config(use_rope=True)


# ---------------------------------------------------------- the whole model
def _tiny_cell():
    """The benchmark's own tiny configuration of the cell and its model
    file (hidden 64, 4 of 8 heads of 16, 4 of 16 experts, top-2)."""
    here = os.path.dirname(os.path.abspath(__file__))
    man = manifest.Manifest()
    cfg = man.config("solar_open2_250b_train_ep40_tp8")
    with open(os.path.join(here, "chipbench_tests", "tiny", "configs",
                           "solar_open2_250b_train_ep40_tp8.json")) as f:
        cfg.update(json.load(f))
    traffic = {"kind": "lm_stream_batches", "rows": 2, "length": 136,
               "pool": 3}
    return man.model("solar_open2"), cfg, traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 34])
def test_three_steps_through_executor_run_match_the_reference(seed):
    """Loss of each step, the first gradient per leaf, the parameters'
    change per leaf after three Adam steps, float32 on both sides; T =
    136 is off the chunk."""
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
    # every leaf gets a gradient: A_log and dt_bias through the scan too
    assert all(g > 0 for g in want["grad_norm"].values())
    assert "l1_decay.w_0" in want["grad_norm"]
    assert not any(n.endswith(".bias") for n in want["grad_norm"])


def test_the_model_is_built_from_one_op_type_a_mechanism():
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    block = main.global_block()
    types = [op.type for op in block.ops]
    assert {"kda_attention", "kda_gate", "l2_norm", "short_conv", "silu",
            "rms_norm", "swiglu", "moe_route", "moe_expert_ffn",
            "flash_attention"} <= set(types)
    assert types.count("kda_attention") == 1 and types.count("l2_norm") == 2
    assert types.count("flash_attention") == 1
    assert types.count("short_conv") == 3
    for op in block.ops:
        if op.type == "short_conv":
            assert block.var(op.inputs["Filter"][0]).shape[1] == 4
        if op.type == "flash_attention":      # 4 query heads over 2
            assert block.var(op.inputs["Q"][0]).shape[2] == 4
            assert block.var(op.inputs["K"][0]).shape[2] == 2
        if op.type == "moe_expert_ffn":
            assert block.var(op.inputs["W1"][0]).shape[0] == 4
    # an untied head, a router over all 16, both counters marked
    names = {v.name for v in main.all_parameters()}
    assert {"embed.w_0", "lm_head.w_0"} <= names
    assert block.var("l0_router.w_0").shape == (64, 16)
    assert set(main._device_counters) == {"moe.local_pairs",
                                          "moe.max_expert_pairs"}


# ------------------------------------------------------------- bfloat16
def test_cast_to_bf16_keeps_what_the_configuration_says_float32():
    """RMSNorm weights, the router's weight, A_log, dt_bias and the
    log-decay g keep float32 by the slot an op reads them through; every
    other parameter is bfloat16."""
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    fluid.amp.cast_program_to_bf16(main)
    keep = set(cfg["precision"]["float32_parameters"])
    assert keep == {"norm", "router", "a_log", "dt_bias"}
    block = main.global_block()
    for name, _, kind in model.param_specs(cfg):
        assert str(block.var(name).dtype) == (
            "float32" if kind in keep else "bfloat16"), name
    for op in block.ops:
        if op.type == "kda_attention":
            assert str(block.var(op.inputs["G"][0]).dtype) == "float32"
            assert str(block.var(op.inputs["Q"][0]).dtype) == "bfloat16"
    made = model.make_params(cfg, 3, "bfloat16")
    for name, _, kind in model.param_specs(cfg):
        assert str(made[name].dtype) == (
            "float32" if kind in keep else "bfloat16"), name
    # the public ranges: A in [1, 16], a step in [0.001, 0.1]
    a = np.exp(np.asarray(made["l1_decay.w_0"]))
    dt = np.log1p(np.exp(np.asarray(made["l1_decay.w_1"])))
    assert (a >= 1).all() and (a <= 16).all()
    assert (dt > 0.00099).all() and (dt < 0.1001).all()


def test_bf16_steps_pass_the_limits_and_the_int8_control_reads_further():
    """The program cast to bfloat16 against the float32 reference, by the
    numbers `correct` compares; the reference's own int8 control stands
    further off than the program does."""
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


def test_the_half_batch_of_one_row_is_the_rows_first_half():
    """`entries/train.py` plants "half of the batch left out" as
    rows[0 : n // 2]; a batch of ONE row has no such rows, and the
    reference then keeps the first half of the row's positions."""
    batch = {"ids": np.arange(12).reshape(1, 12),
             "labels": np.arange(1, 13).reshape(1, 12)}
    cut = ref._half(batch, slice(0, 0))
    np.testing.assert_array_equal(cut["ids"], np.arange(6).reshape(1, 6))
    np.testing.assert_array_equal(cut["labels"], np.arange(1, 7)[None])
    two = {k: np.concatenate([v, v + 100]) for k, v in batch.items()}
    np.testing.assert_array_equal(ref._half(two, slice(0, 1))["ids"],
                                  batch["ids"])
