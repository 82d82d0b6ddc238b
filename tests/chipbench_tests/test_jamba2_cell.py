"""The benchmark's side of `jamba2_train_1chip`, all on the CPU (and, as
test_chipbench.py, holding BENCHMARK.json to no count and no tail): the
configuration file against the published config, the appended entries, the
cell in the tiny root (contract line, traced rehearsal, a broken timed
path, `--control 1` through `reference/jamba2.py`, the parent commit), and
hand counts of `step_flops` and of the scan kernels' `kernel_work`.

The contract line is also checked by test_chipbench.py's tests that are
parametrised over `chipbench_tiny.cells()`: the cell brings its tiny
configuration under `tiny/` and shares `solar`'s tiny traffic file.
"""
import io
import json
import os
import re
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import counts, manifest  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL, CONFIG, TRAFFIC = ("jamba2_train_1chip", "jamba2_3b_train_tp4",
                         "train_b1_t8192")

# ai21labs/AI21-Jamba2-3B config.json, the keys that say something of its
# shape (the catalog row of the model-configs guide)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
REDUCED = ["num_hidden_layers", "channels_held", "heads_held",
           "intermediate_held", "vocab_size"]


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(list(argv), root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("jamba2"))


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest().validate()


@pytest.fixture(scope="module")
def model(man):
    return man.model("jamba2")


# ------------------------------------------- the configuration and entries
def test_every_published_width_is_unchanged_and_each_cut_is_listed(man):
    cfg = man.config(CONFIG)
    entry = man.configs[CONFIG]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # the shares: a quarter of every width a tensor-parallel rank divides
    assert (cfg["channels_held"], cfg["first_channel"]) == (1280, 0)
    assert cfg["published"]["channels_held"] \
        == PUBLISHED["mamba_expand"] * PUBLISHED["hidden_size"]
    assert (cfg["heads_held"], cfg["first_head"]) == (5, 0)
    assert cfg["published"]["heads_held"] == PUBLISHED["num_attention_heads"]
    assert cfg["intermediate_held"] * 4 == PUBLISHED["intermediate_size"] \
        == cfg["published"]["intermediate_held"]
    assert cfg["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert set(REDUCED) == set(cfg["reduced_why"])
    assert all(len(why) > 40 for why in cfg["reduced_why"].values())
    # one whole period: attention at layer 7 of 0-13
    types = man.model("jamba2").layer_types(cfg)
    assert len(types) == 14 and types.index("attention") == 7
    assert types.count("mamba") == 13
    assert "four chips share each layer" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 4
    assert (cfg["model"], cfg["driver"], cfg["entry"]) == (
        "jamba2", "train", "Executor.run")
    assert cfg["precision"]["control"] == "int8"
    # every share this chip holds is a cut and listed: the depth, the
    # three tensor-parallel shares, the vocabulary
    assert set(REDUCED) == {"num_hidden_layers", "vocab_size"} | {
        k for k in cfg if k.endswith("_held")}


def test_the_held_parameters_are_counted_as_the_issue_counts_them(man, model):
    """A mamba layer 26.04M, the attention layer 19.67M, the embedding
    41.9M: 400.2M parameters, 4.80 GB at 12 bytes each."""
    specs = model.param_specs(man.config(CONFIG))
    size = {n: int(np.prod(s)) for n, s, _ in specs}

    def layer(i):
        return sum(v for n, v in size.items() if n.startswith(f"l{i}_"))

    assert layer(0) / 1e6 == pytest.approx(26.04, abs=0.01)
    assert layer(7) / 1e6 == pytest.approx(19.67, abs=0.01)
    assert size["embed.w_0"] == 16384 * 2560
    total = sum(size.values())
    assert total / 1e6 == pytest.approx(400.2, abs=0.1)
    assert total * 12 / 1e9 == pytest.approx(4.80, abs=0.01)


def test_the_limits_stand_between_their_two_readings(man):
    cfg = man.config(CONFIG)
    assert set(cfg["limits"]) == {"loss_gap", "grad_gap", "delta_gap"}
    for name, limit in cfg["limits"].items():
        lower, upper = cfg["limits_readings"][name]
        assert 0 < lower < limit < upper, name


def test_the_appended_entries_list_the_new_cell_alone(man):
    cell = man.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [m["name"] for m in man.cell_end_to_end(CELL)] == [
        "train_tokens_per_s", "setup_s"]
    assert CELL in man.end_to_end["train_tokens_per_s"]["workloads"]
    tagged = [m for m in man.doc["per_layer"] if m["name"].endswith(".jamba2")]
    assert [m["name"] for m in man.cell_per_layer(CELL)] \
        == [m["name"] for m in tagged]
    wanted = {"train_device_step_ms", "train_mfu", "device_idle_share",
              "peak_hbm_bytes", "compiles_in_window"}
    wanted |= {f"train_phase_ms_per_step.{p}"
               for p in ("forward", "backward", "optimizer")}
    wanted |= {f"train_op_ms_per_step.{op}" for op in (
        "selective_scan", "short_conv", "mul", "matmul", "rms_norm",
        "flash_attention", "unscoped")}
    wanted |= {"selective_scan_fwd_roofline", "selective_scan_bwd_roofline"}
    wanted |= {"exec_compile_s.backend", "exec_gap_ms_per_step.fetch_readback",
               "exec_gap_ms_per_step.feed_put"}
    assert {m["name"] for m in tagged} == {w + ".jamba2" for w in wanted}
    for m in tagged:
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].split(".")[0].endswith("_roofline"):
            assert (m["unit"], m["better"], m["source"], m["layer"]) == (
                "%", "higher", "device_trace", "kernels")
    # the layers are PERF.md section 3's, letter for letter
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    rows = set(re.findall(r"^\| ([^|]+?) \|", perf, re.M))
    assert {m["layer"] for m in tagged} <= rows
    # every roofline entry has its count in the model's kernel_work
    work = man.model("jamba2").kernel_work(man.config(CONFIG),
                                          man.traffic(TRAFFIC))
    assert set(work) == {"selective_scan_fwd", "selective_scan_bwd"}
    for other in ("nmt_train_1chip", "solar_train_1chip"):
        assert not {m["name"] for m in tagged} \
            & {m["name"] for m in man.cell_per_layer(other)}
    for group in ("configs", "workloads"):
        for x in man.doc[group]:
            assert 1 <= len(x["why"]) <= 200, x["name"]


# --------------------------------------------------- the cell, tiny, on CPU
def test_the_cell_prints_the_contract_line(root):
    rc, res = _run(root, "--workload", CELL, "--seed", str(2**31 + 42),
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] >= 1


def test_the_traced_rehearsal_reports_the_programs_counts_only(root):
    rc, res = _run(root, "--workload", CELL, "--seed", "42", "--seconds",
                   "1", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["device"]["busy_s"] == 0.0
    got = set(res["metrics"])
    man = manifest.Manifest(root)
    device = {m["name"] for m in man.cell_per_layer(CELL)
              if m["source"] == "device_trace"}
    assert not device & got and "train_mfu.jamba2" not in got
    assert got == {"compiles_in_window.jamba2"}
    assert res["metrics"]["compiles_in_window.jamba2"]["value"] == 0


@pytest.mark.parametrize("fault", ["scan_left_out", "state_unchanged"])
def test_a_broken_timed_path_comes_out_not_correct(root, monkeypatch, fault):
    if fault == "state_unchanged":
        Trainer = manifest.Manifest(root).driver("train").Trainer
        monkeypatch.setattr(Trainer, "step", lambda self, feed: 4.85)
    else:
        # the op returns D x alone: the scan's state is never read
        from paddle_tpu.ops import kernels_scan as scan

        def d_only(x, dt, A_log, B, C, D):
            return (x * D).astype(x.dtype)
        monkeypatch.setattr(scan, "selective_scan_recurrent", d_only)
    rc, res = _run(root, "--workload", CELL, "--seed", "43", "--seconds",
                   "1", "--trace", "0")
    assert rc == 0 and res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_and_faults_read_through_the_new_reference(root):
    from chipbench import correct
    rc, res = _run(root, "--workload", CELL, "--seed", "44", "--seconds",
                   "1", "--trace", "0", "--control", "1")
    assert rc == 0 and res["correct"] is True
    limits = manifest.Manifest(root).config(CONFIG)["limits"]
    control = res["control"]
    assert set(control) == {"int8", "state_unchanged", "half_batch"}
    for name in control:
        assert not correct.judge(control[name], limits)[1], name
    assert control["state_unchanged"]["delta_gap"] == pytest.approx(1.0)
    assert control["int8"]["grad_gap"] > res["checks"]["grad_gap"]["value"]


def test_the_parent_commit_fails_the_cell_at_once(root, monkeypatch):
    """A program without the model (the parent, with this PR's benchmark
    files laid over it) fails in `build`, before anything is compiled."""
    import paddle_tpu.models
    monkeypatch.delattr(paddle_tpu.models, "jamba2", raising=False)
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.jamba2", None)
    with pytest.raises(ImportError):
        _run(root, "--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0")


def test_weights_follow_the_seed_and_the_stated_precision(man, model):
    cfg = dict(man.config(CONFIG))
    cfg.update(tiny._tiny("configs", CONFIG))
    p = model.make_params(cfg, 2**31 + 1, "bfloat16")
    q = model.make_params(cfg, 2**31 + 1, "bfloat16")
    r = model.make_params(cfg, 2, "bfloat16")
    specs = model.param_specs(cfg)
    assert set(p) == {n for n, _, _ in specs} and not model.bias_names(cfg)
    keep = set(cfg["precision"]["float32_parameters"])
    for name, shape, kind in specs:
        assert p[name].shape == tuple(shape)
        assert str(p[name].dtype) == ("float32" if kind in keep
                                      else "bfloat16"), name
        np.testing.assert_array_equal(np.asarray(p[name], "float32"),
                                      np.asarray(q[name], "float32"))
    for name in ("embed.w_0", "l0_dt.w_0", "l0_dt.b_0"):
        assert not np.array_equal(np.asarray(p[name], "float32"),
                                  np.asarray(r[name], "float32"))
    lim = cfg["mamba_dt_rank"] ** -0.5
    assert np.abs(np.asarray(p["l0_dt.w_0"], "float32")).max() <= lim
    assert not np.asarray(p["l0_conv.b_0"], "float32").any()


# ------------------------------------------------------------- the counts
def test_step_flops_against_a_hand_count(man, model):
    """The published widths at the cell's shape, piece by piece by hand
    (MFLOP a token forward, as ISSUE 42 reckons them)."""
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, T, Ch, N, R, F, D = 2560, 8192, 1280, 16, 160, 2048, 128
    mamba_proj = 2 * H * 2 * Ch + 2 * Ch * (R + 2 * N) + 2 * R * Ch \
        + 2 * Ch * H                                   # in, x, dt, out
    scan = 6 * Ch * N + 3 * Ch
    attn_proj = 2 * H * (5 + 1 + 1) * D + 2 * 5 * D * H   # q k v, o
    scores = 2 * 2 * T * 5 * D // 2                       # causal half
    mlp = 3 * 2 * H * F
    head = 2 * H * 16384
    forward = 13 * (mamba_proj + scan) + attn_proj + scores + 14 * mlp + head
    assert model.forward_flops_per_token(cfg, T) == forward
    assert round(13 * mamba_proj / 1e6) == 267
    assert round(head / 1e6, 1) == 83.9 and round(14 * mlp / 1e6) == 440
    assert round(forward / 1e6) == 812
    assert model.step_flops(cfg, t) == 3 * T * forward
    assert round(model.step_flops(cfg, t) / 1e12, 2) == 19.95
    assert 13 * mamba_proj / forward == pytest.approx(0.33, abs=0.005)
    assert head / forward == pytest.approx(0.103, abs=0.001)


def test_scan_kernel_work_against_a_hand_count(man, model):
    """selective_scan_fwd: 6 FLOP a (token, channel, state) and 3 a
    (token, channel); x, y, B, C in bf16, dt float32, A_log and D once.
    The backward twice the FLOPs and its own bytes. Both floors are
    HBM's: 0.10 and 0.18 ms a layer."""
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    T, Ch, N = 8192, 1280, 16
    flops = 6 * T * Ch * N + 3 * T * Ch
    fwd_bytes = T * Ch * (2 + 4 + 2) + 2 * T * N * 2 + 4 * (Ch * N + Ch)
    bwd_bytes = T * Ch * (2 + 4 + 2 + 2 + 4) + 4 * T * N * 2 \
        + 2 * 4 * (Ch * N + Ch)
    work = model.kernel_work(cfg, t)
    assert work == {"selective_scan_fwd": [(flops, fwd_bytes, 13)],
                    "selective_scan_bwd": [(2 * flops, bwd_bytes, 13)]}
    one = {k: [(f, b, 1)] for k, [(f, b, _)] in work.items()}
    fwd = counts.floor_seconds(one["selective_scan_fwd"], "TPU v5 lite")
    bwd = counts.floor_seconds(one["selective_scan_bwd"], "TPU v5 lite")
    assert fwd == pytest.approx(fwd_bytes / 819e9)
    assert 1e3 * fwd == pytest.approx(0.10, abs=0.005)
    assert 1e3 * bwd == pytest.approx(0.18, abs=0.005)
