"""Jamba 2 through the Program IR, against the benchmark's plain reference
(chipbench/reference/jamba2.py, which imports nothing of the program): the
conv bias `short_conv` gained (and the other decoders' programs, which do
not use it, unchanged), the two mixers, the share of a tensor-parallel
deployment of four, the whole model's first steps through
`Executor.run`, and what `amp.cast_program_to_bf16` keeps float32.

Sizes: hidden 64, d_inner 128, dt_rank 8, d_state 16, a period of 4 with
attention at offset 2, T 96-100, all on the CPU.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.models import jamba2

from chipbench import correct, manifest
from chipbench.reference import jamba2 as ref
from chipbench.reference.lfm2_moe import dwconv_causal

from test_lfm2_moe import _op_and_grads, _train

RNG = np.random.default_rng(42)
CONFIG = "jamba2_3b_train_tp4"
SMALL = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
             num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=1, attn_layer_period=4, attn_layer_offset=2,
             mamba_dt_rank=8)


def _f32(*shape, scale=1.0):
    return (RNG.standard_normal(shape) * scale).astype("float32")


# ------------------------------------------------------------ short_conv
def test_short_conv_with_and_without_a_bias():
    """A bias is added after the taps where `bias_attr` asks for one (its
    gradient the sum of the output's); without one the op is the op it
    was: inputs X and Filter, no attribute but its site."""
    vals = {"x": _f32(2, 9, 8), "c.w_0": _f32(8, 4), "c.b_0": _f32(8)}

    def want(v):
        return dwconv_causal(v["x"], v["c.w_0"]) + v["c.b_0"]

    out, grads, probe = _op_and_grads(
        lambda v: layers.short_conv(v["x"], 4, name="c", bias_attr=True),
        vals)
    jv = {k: jnp.asarray(x) for k, x in vals.items()}
    with jax.default_matmul_precision("highest"):
        w_out = np.asarray(want(jv))
        wg = jax.grad(lambda v: jnp.sum(want(v) * probe))(jv)
    np.testing.assert_allclose(out, w_out, atol=1e-5, rtol=1e-5)
    for n in vals:
        np.testing.assert_allclose(grads[n], np.asarray(wg[n]), atol=1e-4,
                                   rtol=1e-4, err_msg=n)

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[9, 8])
        layers.short_conv(x, 3, name="plain")
        layers.short_conv(x, 3, name="biased", bias_attr=True)
    plain, biased = main.global_block().ops
    assert set(plain.inputs) == {"X", "Filter"}
    assert plain.attrs == {"op_namescope": "plain"}       # the site alone
    assert set(biased.inputs) == {"X", "Filter", "Bias"}
    assert biased.inputs["Bias"] == ["biased.b_0"]
    assert biased.attrs == {"op_namescope": "biased"}
    assert {p.name for p in main.all_parameters()} == {
        "plain.w_0", "biased.w_0", "biased.b_0"}


# the tiny programs of the other cells, as the parent commit builds them
# (ops, their inputs, outputs and attributes, and every variable's dtype
# after the bf16 cast): lfm2 and solar use short_conv without a bias, and
# every one goes through amp's cast
UNCHANGED = {"lfm2_24b_a2b_train_ep8": ("lfm2_moe", "1a45c2b677b7ab8f"),
             "solar_open2_250b_train_ep40_tp8": ("solar_open2",
                                                 "761403f0e7e9b50e"),
             "mellum2_12b_train_ep4": ("mellum2", "e3c90e5f3cdcc0a6"),
             "nmt_base_train_nodrop": ("nmt", "4e95d6b6eb799e41")}


@pytest.mark.parametrize("config", sorted(UNCHANGED))
def test_the_other_decoders_programs_are_unchanged(config):
    model_name, digest = UNCHANGED[config]
    man = manifest.Manifest()
    cfg = man.config(config)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "chipbench_tests", "tiny", "configs",
                           config + ".json")) as f:
        cfg.update(json.load(f))
    traffic = {"kind": "lm_stream_batches", "rows": 1, "length": 96,
               "pool": 2}
    if model_name == "nmt":
        traffic = {"kind": "train_batches", "batch_rows": 4, "src_len": 16,
                   "trg_len": 16, "pool": 2}
    main, _, _ = man.model(model_name).build(cfg, traffic, fluid)
    fluid.amp.cast_program_to_bf16(main)
    block = main.global_block()
    rows = [[op.type, sorted((k, list(v)) for k, v in op.inputs.items()),
             sorted((k, list(v)) for k, v in op.outputs.items()),
             sorted((k, repr(v)) for k, v in op.attrs.items())]
            for op in block.ops]
    rows.append(sorted((n, str(v.dtype)) for n, v in block.vars.items()))
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16] \
        == digest


# ------------------------------------------------------------ the mixers
def _ref_cfg(c, **over):
    """The reference reads a dict: the keys of the configuration file."""
    return dict({"hidden_size": c.hidden_size,
                 "num_attention_heads": c.num_attention_heads,
                 "num_key_value_heads": c.num_key_value_heads,
                 "mamba_dt_rank": c.dt_rank, "mamba_d_state": c.d_state,
                 "rms_norm_eps": c.rms_norm_eps,
                 "layer_types": c.layer_types}, **over)


def _spec_cfg(c, kind):
    """models/jamba2.py's keys for one layer of `kind`."""
    period, offset = (1, 0) if kind == "attention" else (2, 1)
    return {"hidden_size": c.hidden_size, "channels_held": c.channels_held,
            "mamba_d_state": c.d_state, "mamba_dt_rank": c.dt_rank,
            "mamba_d_conv": c.d_conv, "heads_held": c.heads_held,
            "num_key_value_heads": c.num_key_value_heads,
            "num_attention_heads": c.num_attention_heads,
            "intermediate_held": c.intermediate_held,
            "vocab_size": c.vocab_size, "attn_layer_period": period,
            "attn_layer_offset": offset, "num_hidden_layers": 1}


def _params(c, kind):
    """Seeded parameters of one layer of `kind`, by the benchmark's own
    parameter list, every kind of value signed and away from its default
    (a step dt between 0.02 and 0.4)."""
    model = manifest.Manifest().model("jamba2")
    out = {}
    for name, shape, k in model.param_specs(_spec_cfg(c, kind)):
        if not name.startswith("l0_"):
            continue
        if k == "norm":
            out[name] = 1 + _f32(*shape, scale=0.1)
        elif k == "a_log":
            out[name] = np.log(RNG.uniform(1, 16, shape)).astype("float32")
        elif k == "dt_bias":
            out[name] = RNG.uniform(-4.0, -1.0, shape).astype("float32")
        elif k in ("conv_bias", "d"):
            out[name] = _f32(*shape, scale=0.5)
        else:
            out[name] = _f32(*shape, scale=0.15)
    return out


def _part(fn, cfg, x, params, name="l0"):
    """One piece of the model (`jamba2._mamba`, `_attention`, `_mlp`) as a
    program over the data `x`, its parameters set from `params`."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = layers.data("x", shape=list(x.shape), append_batch_size=False)
        out = fn(xv, cfg, name)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        declared = {v.name for v in main.all_parameters()}
        assert declared <= set(params), declared - set(params)
        for n in declared:
            scope.set(n, params[n])
        return np.asarray(exe.run(main, feed={"x": x}, fetch_list=[out])[0])


def _jp(params):
    return {k: jnp.asarray(v) for k, v in params.items()}


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_each_mixer_matches_the_reference(kind):
    cfg = jamba2.Jamba2Config(**SMALL)
    rc = _ref_cfg(cfg)
    params = _params(cfg, kind)
    x = _f32(2, 100, 64)
    fn, want = (jamba2._mamba, ref.mamba_mixer) if kind == "mamba" \
        else (jamba2._attention, ref.attention)
    got = _part(fn, cfg, x, params)
    with jax.default_matmul_precision("highest"):
        w = np.asarray(want(_jp(params), "l0", jnp.asarray(x), rc, "float32"))
    np.testing.assert_allclose(got, w, atol=2e-5 * np.abs(w).max(),
                               rtol=2e-4)


# ------------------------------------------------------------ THE SHARE TEST
def _channel_share(params, s, n, Ch):
    """Tensor-parallel rank `s` of `n` of a mamba mixer of `Ch` inner
    channels: W_in's x and z columns, the conv's taps and bias, W_x's rows,
    W_dt's columns and bias, A_log, D and W_out's rows of its channels;
    the three norms whole."""
    c = slice(s * Ch // n, (s + 1) * Ch // n)
    out = dict(params)
    w_in = params["l0_in.w_0"]
    out["l0_in.w_0"] = np.concatenate([w_in[:, :Ch][:, c], w_in[:, Ch:][:, c]],
                                      axis=1)
    for name in ("l0_conv.w_0", "l0_conv.b_0", "l0_x.w_0", "l0_dt.b_0",
                 "l0_scan.w_0", "l0_scan.w_1", "l0_out.w_0"):
        out[name] = params[name][c]
    out["l0_dt.w_0"] = params["l0_dt.w_0"][:, c]
    return out


def test_the_shares_of_four_ranks_add_up_to_the_uncut_mamba_mixer():
    """The uncut mixer (128 inner channels) as the reference computes it,
    against four ranks of 32 channels each: every rank's part of W_x's
    product, summed (the deployment's all-reduce), handed to every rank,
    and the ranks' outputs summed (the all-reduce after W_out), give the
    uncut mixer. The PROGRAM's one-chip layer is a rank that hands itself
    its own part: the reference's share with its own partial product, and
    not the deployment's."""
    whole = jamba2.Jamba2Config(**SMALL)
    rc = _ref_cfg(whole)
    params = _params(whole, "mamba")
    x = jnp.asarray(_f32(2, 96, 64))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.mamba_mixer(_jp(params), "l0", x, rc,
                                          "float32"))
        shares = [_jp(_channel_share(params, s, 4, 128)) for s in range(4)]
        ins = [ref.mamba_in(p, "l0", x, rc, "float32") for p in shares]
        total = sum(part for _, _, part in ins)
        got = sum(ref.mamba_out(p, "l0", xc, z, total, rc, "float32")
                  for p, (xc, z, _) in zip(shares, ins))
        alone = np.asarray(ref.mamba_out(shares[1], "l0", *ins[1], rc,
                                         "float32"))
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=2e-5 * np.abs(want).max(), rtol=2e-4)
    one_chip = jamba2.Jamba2Config(**SMALL, channels_held=32,
                                   first_channel=32)
    program = _part(jamba2._mamba, one_chip, np.asarray(x),
                    _channel_share(params, 1, 4, 128))
    np.testing.assert_allclose(program, alone,
                               atol=2e-5 * np.abs(alone).max(), rtol=2e-4)
    with jax.default_matmul_precision("highest"):
        exchanged = np.asarray(ref.mamba_out(shares[1], "l0", *ins[1][:2],
                                             total, rc, "float32"))
    assert np.abs(program - exchanged).max() > 1e-2 * np.abs(want).max()


def test_the_shares_of_four_ranks_add_up_to_the_uncut_attention_layer():
    """The attention layer uncut (4 query heads over 1 key-value head, an
    MLP of 96) against four ranks of the PROGRAM's pieces: one query head
    each over the key-value head every rank holds whole, and a quarter of
    the MLP's width; the ranks' outputs add up to the uncut layer with
    the residual counted once."""
    whole = jamba2.Jamba2Config(**SMALL)
    rc = _ref_cfg(whole)
    params = _params(whole, "attention")
    h = _f32(2, 96, 64)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._layer(_jp(params), jnp.asarray(h), 0,
                                     "attention", rc, "float32"))
        x1 = np.asarray(ref.rms_norm(h, params["l0_mixer_norm.w_0"], 1e-6))
    mixed = 0.0
    for s in range(4):
        share = dict(params, **{
            "l0_q.w_0": params["l0_q.w_0"][:, 16 * s:16 * (s + 1)],
            "l0_o.w_0": params["l0_o.w_0"][16 * s:16 * (s + 1)]})
        cfg = jamba2.Jamba2Config(**SMALL, heads_held=1, first_head=s)
        mixed = mixed + _part(jamba2._attention, cfg, x1, share)
    h1 = h + mixed
    with jax.default_matmul_precision("highest"):
        x2 = np.asarray(ref.rms_norm(h1, params["l0_mlp_norm.w_0"], 1e-6))
    mlp = 0.0
    for s in range(4):
        cols = slice(24 * s, 24 * (s + 1))
        share = dict(params, **{
            "l0_mlp_gate.w_0": params["l0_mlp_gate.w_0"][:, cols],
            "l0_mlp_up.w_0": params["l0_mlp_up.w_0"][:, cols],
            "l0_mlp_down.w_0": params["l0_mlp_down.w_0"][cols]})
        cfg = jamba2.Jamba2Config(**SMALL, intermediate_held=24)
        mlp = mlp + _part(jamba2._mlp, cfg, x2, share)
    np.testing.assert_allclose(h1 + mlp, want, atol=5e-5, rtol=5e-4)


def test_the_configuration_is_the_published_one_by_default():
    full = jamba2.Jamba2Config()
    assert len(full.layer_types) == 28
    assert [i for i, k in enumerate(full.layer_types) if k == "attention"] \
        == [7, 21]
    assert (full.d_inner, full.d_state, full.d_conv, full.dt_rank) == (
        5120, 16, 4, 160)
    assert (full.channels_held, full.heads_held, full.intermediate_held,
            full.head_dim) == (5120, 20, 8192, 128)
    cut = jamba2.Jamba2Config(num_hidden_layers=14, channels_held=1280,
                              first_channel=3840, heads_held=5, first_head=15,
                              intermediate_held=2048)
    assert cut.layer_types.count("mamba") == 13
    for bad in (dict(channels_held=1280, first_channel=4000),
                dict(heads_held=5, first_head=16),
                dict(intermediate_held=9000)):
        with pytest.raises(ValueError):
            jamba2.Jamba2Config(**bad)
    with pytest.raises(NotImplementedError):
        jamba2.Jamba2Config(num_experts=16)


# ---------------------------------------------------------- the whole model
def _tiny_cell():
    """The benchmark's own tiny configuration of the cell and its model
    file (hidden 64, 64 of 128 inner channels, 2 of 4 heads, 48 of 96)."""
    here = os.path.dirname(os.path.abspath(__file__))
    man = manifest.Manifest()
    cfg = man.config(CONFIG)
    with open(os.path.join(here, "chipbench_tests", "tiny", "configs",
                           CONFIG + ".json")) as f:
        cfg.update(json.load(f))
    traffic = {"kind": "lm_stream_batches", "rows": 2, "length": 100,
               "pool": 3}
    return man.model("jamba2"), cfg, traffic


@pytest.mark.parametrize("seed", [1, 2**31 + 42])
def test_three_steps_through_executor_run_match_the_reference(seed):
    """Loss of each step, the first gradient per leaf, the parameters'
    change per leaf after three Adam steps, float32 on both sides."""
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
    # every leaf gets a gradient: A_log, D, b_dt and the conv bias too
    assert all(g > 0 for g in want["grad_norm"].values())
    assert {"l0_scan.w_0", "l0_scan.w_1", "l0_dt.b_0", "l0_conv.b_0"} \
        <= set(want["grad_norm"])


def test_the_model_is_built_from_one_op_type_a_mechanism():
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    block = main.global_block()
    types = [op.type for op in block.ops]
    assert {"selective_scan", "short_conv", "softplus", "rms_norm",
            "swiglu", "flash_attention", "mul", "matmul"} <= set(types)
    assert types.count("selective_scan") == types.count("short_conv") == 3
    assert types.count("softplus") == 3 and types.count("flash_attention") == 1
    sites = sorted(op.attrs.get("op_namescope", "").rsplit("/", 1)[-1]
                   for op in block.ops if op.type in ("mul", "matmul"))
    want = ["lm_head"]
    for i in (0, 1, 3):
        want += [f"l{i}_{s}" for s in ("in", "x", "dt", "out")]
    want += [f"l2_{s}" for s in "qkvo"]
    want += [f"l{i}_mlp_{s}" for i in range(4)
             for s in ("gate", "up", "down")]
    assert sites[:len(set(sites))] == sorted(set(sites)) \
        and sorted(set(sites)) == sorted(want)
    for op in block.ops:
        if op.type == "short_conv":
            assert block.var(op.inputs["Filter"][0]).shape == (64, 4)
            assert block.var(op.inputs["Bias"][0]).shape == (64,)
        if op.type == "selective_scan":
            assert block.var(op.inputs["ALog"][0]).shape == (64, 16)
            assert block.var(op.inputs["X"][0]).shape[-1] == 64
        if op.type == "flash_attention":      # 2 query heads over 1
            assert block.var(op.inputs["Q"][0]).shape[2] == 2
            assert block.var(op.inputs["K"][0]).shape[2] == 1
    names = {v.name for v in main.all_parameters()}
    assert "embed.w_0" in names and not any("lm_head" in n for n in names)


# ------------------------------------------------------------- bfloat16
def test_cast_to_bf16_keeps_what_the_configuration_says_float32():
    """RMSNorm weights, b_dt, A_log and D keep float32 by the slot an op
    reads them through (b_dt through the softplus of the scan's dt), and
    so does dt; every other parameter is bfloat16."""
    model, cfg, traffic = _tiny_cell()
    main, _, _ = model.build(cfg, traffic, fluid)
    fluid.amp.cast_program_to_bf16(main)
    keep = set(cfg["precision"]["float32_parameters"])
    assert keep == {"norm", "dt_bias", "a_log", "d"}
    block = main.global_block()
    for name, _, kind in model.param_specs(cfg):
        assert str(block.var(name).dtype) == (
            "float32" if kind in keep else "bfloat16"), name
    for op in block.ops:
        if op.type == "selective_scan":
            assert str(block.var(op.inputs["Dt"][0]).dtype) == "float32"
            assert str(block.var(op.inputs["X"][0]).dtype) == "bfloat16"
    made = model.make_params(cfg, 3, "bfloat16")
    for name, _, kind in model.param_specs(cfg):
        assert str(made[name].dtype) == (
            "float32" if kind in keep else "bfloat16"), name
    # the Mamba initialisation: A = 1 .. 16, D = 1, a step in [0.001, 0.1]
    np.testing.assert_allclose(np.exp(np.asarray(made["l0_scan.w_0"]))[5],
                               np.arange(1, 17), rtol=1e-6)
    assert (np.asarray(made["l0_scan.w_1"]) == 1).all()
    dt = np.log1p(np.exp(np.asarray(made["l0_dt.b_0"])))
    assert (dt > 0.00099).all() and (dt < 0.1001).all()


def test_bf16_steps_pass_the_limits_and_the_faults_do_not():
    """The program cast to bfloat16 against the float32 reference, by the
    numbers `correct` compares; the int8 control stands further off than
    the program does, and the planted fault -- the scan's state dropped
    every 16 tokens -- fails a limit."""
    model, cfg, traffic = _tiny_cell()
    seen, params, batches, _ = _train(model, cfg, traffic, 5, "bfloat16",
                                      bf16=True)
    want = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                 "float32", 1)
    numbers = correct.train_numbers(seen, want)
    assert correct.judge(numbers, cfg["limits"])[1], numbers
    low = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                "int8", 1)
    assert correct.train_numbers(low, want)["grad_gap"] \
        > 2 * numbers["grad_gap"]
    dropped = model.reference_steps(params, dict(cfg, state_reset=16),
                                    batches[:3], cfg["optimizer"],
                                    "float32", 1)
    fault = correct.train_numbers(dropped, want)
    assert not correct.judge(fault, cfg["limits"])[1], fault
