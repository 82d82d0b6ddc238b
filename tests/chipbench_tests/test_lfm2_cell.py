"""The benchmark's side of `lfm2_train_1chip` (PR 30), all on the CPU (and,
as test_chipbench.py, holding BENCHMARK.json to no count and no tail):
the configuration file against the published config, the appended entries,
the cell in the tiny root (contract line, traced rehearsal, planted faults,
`--control 1` through `reference/lfm2_moe.py`), and hand counts of
`step_flops` and of every `kernel_work` entry at a small size.

The contract line is also checked by test_chipbench.py's tests that are
parametrised over `chipbench_tiny.cells()`: the cell brings its tiny
configuration and traffic files under `tiny/`, so it is one of them
(conftest.py says what that took).
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

from chipbench import correct, counts, manifest  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL, CONFIG, TRAFFIC = ("lfm2_train_1chip", "lfm2_24b_a2b_train_ep8",
                         "train_b2_t8192")

# LiquidAI/LFM2-24B-A2B config.json, the keys that say something of its
# shape (the catalog row of the model-configs guide)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "experts_held", "vocab_size"]


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(list(argv), root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("lfm2"))


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest().validate()


@pytest.fixture(scope="module")
def model(man):
    return man.model("lfm2_moe")


# ------------------------------------------- the configuration and entries
def test_every_published_width_is_unchanged_and_each_cut_is_listed(man):
    cfg = man.config(CONFIG)
    entry = man.configs[CONFIG]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert all(len(why) > 40 for why in cfg["reduced_why"].values())
    # the cut: one leading dense layer and one whole period
    assert cfg["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                  "conv"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["num_dense_layers"] == 1
    # the share of eight chips: the router keeps its width and its top-4
    assert cfg["num_experts"] == 64 and cfg["num_experts_per_tok"] == 4
    assert cfg["experts_held"] == 8 and cfg["first_expert"] == 0
    assert cfg["vocab_size"] == 65536 // 8
    assert "eight chips share each layer" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 4
    assert (cfg["model"], cfg["driver"], cfg["entry"]) == (
        "lfm2_moe", "train", "Executor.run")
    assert cfg["precision"]["control"] == "int8"
    # the floors of a model_config cut (model-configs guide, section 4)
    assert len(cfg["layer_types"]) - cfg["num_dense_layers"] >= 4
    assert cfg["experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


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
    tagged = [m for m in man.doc["per_layer"] if ".lfm2" in m["name"]]
    assert [m["name"] for m in man.cell_per_layer(CELL)] \
        == [m["name"] for m in tagged]
    names = {m["name"] for m in tagged}
    for m in tagged:
        assert m["name"].endswith(".lfm2") and m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # they follow everything PR 29 left (a later PR appends after them:
    # no test holds the tail)
    order = [m["name"] for m in man.doc["per_layer"]]
    assert order.index(tagged[0]["name"]) > order.index(
        "flash_attention_short_bwd_roofline")
    wanted = {
        "device_idle_share", "peak_hbm_bytes", "compiles_in_window",
        "exec_compile_s.backend",
        "exec_gap_ms_per_step.fetch_readback", "exec_gap_ms_per_step.feed_put",
        "train_device_step_ms", "train_mfu",
        "moe_local_pairs_per_step", "moe_load_max_over_mean"}
    wanted |= {f"train_phase_ms_per_step.{p}"
               for p in ("forward", "backward", "optimizer")}
    wanted |= {f"train_op_ms_per_step.{op}" for op in (
        "mul", "flash_attention", "short_conv", "rms_norm",
        "rotary_embedding", "moe_route", "moe_expert_ffn", "unscoped")}
    # a kernel's time follows from its share and `kernel_work`, so the
    # share stands alone; the backward is the one kernel that runs
    wanted |={f"{k}_roofline" for k in (
        "flash_attention_fwd", "flash_attention_bwd", "moe_gmm_swiglu",
        "moe_gmm", "moe_swiglu_bwd", "moe_tgmm")}
    assert {w + ".lfm2" for w in wanted} <= names
    assert not {n for n in names if n.startswith("kernel_ms_per_step.")}
    for m in tagged:
        if m["name"].split(".")[0].endswith("_roofline"):
            assert (m["unit"], m["better"], m["source"], m["layer"]) == (
                "%", "higher", "device_trace", "kernels")
        if m["name"].startswith(("moe_local", "moe_load")):
            assert m["source"] == "program_counter"
    # the layers are PERF.md section 3's, letter for letter
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    rows = set(re.findall(r"^\| ([^|]+?) \|", perf, re.M))
    assert {m["layer"] for m in tagged} <= rows
    # what the first cell reports is what it reported
    assert not names & {m["name"]
                        for m in man.cell_per_layer("nmt_train_1chip")}
    # every roofline entry has its count in the model's kernel_work
    work = man.model("lfm2_moe").kernel_work(man.config(CONFIG),
                                            man.traffic(TRAFFIC))
    assert {n.split(".")[0][:-len("_roofline")] for n in names
            if n.split(".")[0].endswith("_roofline")} == set(work)


def test_the_whys_fit_their_200_characters(man):
    for group in ("configs", "workloads"):
        for x in man.doc[group]:
            assert 1 <= len(x["why"]) <= 200, x["name"]
    assert len(json.dumps(man.doc)) < 64 * 1024


# --------------------------------------------------- the cell, tiny, on CPU
def test_the_cell_prints_the_contract_line(root):
    rc, res = _run(root, "--workload", CELL, "--seed", str(2**31 + 30),
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] >= 1


def test_the_first_cell_still_runs_beside_it(root):
    rc, res = _run(root, "--workload", "nmt_train_1chip", "--seed", "30",
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True
    manifest.Manifest(root).validate()
    assert tiny.cells()[:2] == ["nmt_train_1chip", CELL]


def test_the_traced_rehearsal_reports_the_programs_counts_only(root):
    from paddle_tpu import telemetry
    before = telemetry.snapshot().get("moe.steps", 0)
    rc, res = _run(root, "--workload", CELL, "--seed", "30", "--seconds",
                   "1", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["device"]["busy_s"] == 0.0
    got = set(res["metrics"])
    man = manifest.Manifest(root)
    device = {m["name"] for m in man.cell_per_layer(CELL)
              if m["source"] == "device_trace"}
    assert not device & got and "train_mfu.lfm2" not in got
    assert {"compiles_in_window.lfm2", "moe_local_pairs_per_step.lfm2",
            "moe_load_max_over_mean.lfm2"} <= got
    assert res["metrics"]["compiles_in_window.lfm2"]["value"] == 0
    # tiny: 4 x 32 tokens, top-2 of 8 with 2 held, two expert layers:
    # 128 pairs a step at an even load; the fullest of 2 is 1 to 2 x the mean
    pairs = res["metrics"]["moe_local_pairs_per_step.lfm2"]["value"]
    assert 0 < pairs <= 4 * 32 * 2 * 2
    assert 1.0 <= res["metrics"]["moe_load_max_over_mean.lfm2"]["value"] <= 2
    # every step of the run was counted: the checked, the warm, the window's
    steps = telemetry.snapshot()["moe.steps"] - before
    assert steps == 3 + 1 + res["attempted"]


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_a_broken_timed_path_comes_out_not_correct(root, monkeypatch, fault):
    Trainer = manifest.Manifest(root).driver("train").Trainer
    real = Trainer.step
    if fault == "state_unchanged":
        monkeypatch.setattr(Trainer, "step", lambda self, feed: 4.8)
    else:
        def step(self, feed):
            return real(self, {k: v[:len(v) // 2] for k, v in feed.items()})
        monkeypatch.setattr(Trainer, "step", step)
    rc, res = _run(root, "--workload", CELL, "--seed", "31", "--seconds",
                   "1", "--trace", "0")
    assert rc == 0 and res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_and_faults_read_through_the_new_reference(root):
    rc, res = _run(root, "--workload", CELL, "--seed", "37", "--seconds",
                   "1", "--trace", "0", "--control", "1")
    assert rc == 0 and res["correct"] is True
    limits = manifest.Manifest(root).config(CONFIG)["limits"]
    control = res["control"]
    assert set(control) == {"int8", "state_unchanged", "half_batch"}
    for fault in ("state_unchanged", "half_batch"):
        assert not correct.judge(control[fault], limits)[1], fault
    assert control["state_unchanged"]["delta_gap"] == pytest.approx(1.0)
    assert control["state_unchanged"]["grad_gap"] < 1e-6
    # int8 stands further from float32 than the program's bfloat16 does
    assert control["int8"]["grad_gap"] > res["checks"]["grad_gap"]["value"]


def test_the_parent_commit_fails_the_cell_at_once(root, monkeypatch):
    """A program without the model (the parent, with this PR's benchmark
    files laid over it) fails in `build`, before anything is compiled."""
    import paddle_tpu.models
    monkeypatch.delattr(paddle_tpu.models, "lfm2_moe")
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.lfm2_moe", None)
    with pytest.raises(ImportError):
        _run(root, "--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0")


def test_the_readers_find_nothing_where_the_program_counts_nothing(
        man, monkeypatch):
    from paddle_tpu import telemetry
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"other": 1})
    facts = {"cfg": man.config(CONFIG)}
    for name in ("moe_local_pairs_per_step.lfm2",
                 "moe_load_max_over_mean.lfm2"):
        assert man.reader(name).read(facts, name) is None
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "moe.local_pairs": 96000, "moe.max_expert_pairs": 18000,
        "moe.steps": 3})
    assert man.reader("moe_local_pairs_per_step.lfm2").read(
        facts, "moe_local_pairs_per_step.lfm2") == 32000
    assert man.reader("moe_load_max_over_mean.lfm2").read(
        facts, "moe_load_max_over_mean.lfm2") == pytest.approx(1.5)


# ----------------------------------------------- the traffic and the weights
def test_batches_are_cut_from_one_stream_and_follow_the_seed(man, model):
    cfg = dict(man.config(CONFIG), vocab_size=512)
    t = {"kind": "lm_stream_batches", "rows": 2, "length": 64, "pool": 3}
    a = model.make_batches(t, cfg, 2**31 + 5)
    b = model.make_batches(t, cfg, 2**31 + 5)
    c = model.make_batches(t, cfg, 6)
    assert len(a) == 3 and set(a[0]) == {"ids", "labels"}
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["ids"], y["ids"])
    assert not np.array_equal(a[0]["ids"], c[0]["ids"])
    stream = np.concatenate([x["ids"].reshape(-1) for x in a])
    nxt = np.concatenate([x["labels"].reshape(-1) for x in a])
    # no padding, no document mask: the label is the stream's next id,
    # across rows and batches too
    np.testing.assert_array_equal(stream[1:], nxt[:-1])
    assert stream.min() >= 0 and stream.max() < 512
    assert a[0]["ids"].shape == (2, 64) and a[0]["ids"].dtype == np.int64
    assert len(np.unique(stream)) > 100
    assert model.tokens_per_step(t) == 128
    with pytest.raises(ValueError):
        model.make_batches(dict(t, kind="train_batches"), cfg, 1)


def test_weights_follow_the_seed_and_the_stated_precision(man, model):
    cfg = dict(man.config(CONFIG))
    cfg.update(tiny._tiny("configs", CONFIG))
    p = model.make_params(cfg, 2**31 + 1, "bfloat16")
    q = model.make_params(cfg, 2**31 + 1, "bfloat16")
    r = model.make_params(cfg, 2, "bfloat16")
    specs = model.param_specs(cfg)
    assert set(p) == {n for n, _, _ in specs} | set(model.bias_names(cfg))
    keep = set(cfg["precision"]["float32_parameters"])
    for name, shape, kind in specs:
        assert p[name].shape == tuple(shape)
        assert str(p[name].dtype) == ("float32" if kind in keep
                                      else "bfloat16"), name
        np.testing.assert_array_equal(np.asarray(p[name], "float32"),
                                      np.asarray(q[name], "float32"))
    assert not np.array_equal(np.asarray(p["embed.w_0"], "float32"),
                              np.asarray(r["embed.w_0"], "float32"))
    for name in model.bias_names(cfg):
        assert p[name].shape == (cfg["num_experts"],)
        assert str(p[name].dtype) == "float32" and np.asarray(p[name]).any()
    assert model.bias_names(dict(cfg, use_expert_bias=False)) == []


# ------------------------------------------------------------- the counts
def test_step_flops_against_a_hand_count(man, model):
    """The published widths at the cell's shape, layer by layer by hand
    (MFLOP a token forward, as ISSUE 30 reckons them)."""
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, T = 2048, 8192
    conv = 2 * H * (3 * H) + 2 * H * H                     # in, out
    attn = 2 * H * (32 + 8 + 8) * 64 + 2 * H * H           # q k v, o
    scores = 2 * 2 * T * 32 * 64 // 2                      # causal half
    dense = 3 * 2 * H * 11776
    router = 2 * H * 64
    experts = 0.5 * 3 * 2 * H * 1536      # 4 x 8 / 64 pairs a token
    head = 2 * H * 8192
    forward = (conv + dense) + (attn + scores + router + experts) \
        + 3 * (conv + router + experts) + head
    assert model.forward_flops_per_token(cfg, T) == forward
    assert round(forward / 1e6) == 406
    assert round(dense / 1e6) == 145 and round(4 * conv / 1e6) == 134
    assert int((attn + scores) / 1e6) == 54 and round(scores / 1e6) == 34
    assert round(4 * experts / 1e6) == 38 and round(head / 1e6) == 34
    assert model.step_flops(cfg, t) == 3 * 2 * T * forward
    assert model.tokens_per_step(t) == 16384
    # a small size too, by the same rule
    small = dict(cfg, hidden_size=64, intermediate_size=128,
                 moe_intermediate_size=48, num_attention_heads=4,
                 num_key_value_heads=2, num_experts=8, experts_held=2,
                 num_experts_per_tok=2, vocab_size=128,
                 layer_types=["conv", "full_attention"], num_dense_layers=1)
    want = (2 * 64 * 192 + 2 * 64 * 64) + 6 * 64 * 128 \
        + 2 * 64 * (4 + 2 + 2) * 16 + 2 * 64 * 64 + 4 * 32 * 64 // 2 \
        + 2 * 64 * 8 + 0.5 * 6 * 64 * 48 + 2 * 64 * 128
    assert model.forward_flops_per_token(small, 32) == want
    assert model.step_flops(small, {"rows": 4, "length": 32}) \
        == 3 * 128 * want


def test_attention_kernel_work_against_a_hand_count(man, model):
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    B, T, H, KV, D = 2, 8192, 32, 8, 64
    product = 2 * B * H * T * T * D // 2
    q, kv = B * T * H * D * 2, B * T * KV * D * 2          # bfloat16
    work = model.kernel_work(cfg, t)
    assert work["flash_attention_fwd"] == [
        (2 * product, q + 2 * kv + q, 1)]                  # q k v -> out
    assert work["flash_attention_bwd"] == [               # five products
        (5 * product, (q + 2 * kv + q + q) + (q + 2 * kv), 1)]
    # 8 key-value heads' bytes, not 32: the floor is compute's, 2.8 ms
    floor = counts.floor_seconds(work["flash_attention_fwd"], "TPU v5 lite")
    assert floor == pytest.approx(2 * product / 197e12)
    assert 1e3 * floor == pytest.approx(2.79, abs=0.01)
    floor = counts.floor_seconds(work["flash_attention_bwd"], "TPU v5 lite")
    assert floor == pytest.approx(5 * product / 197e12)
    assert 1e3 * floor == pytest.approx(6.98, abs=0.01)
    # the dq of a key-value head's 4 query heads stays resident, so the
    # one backward kernel runs at this shape, not `_dq` and `_dkv`
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._bwd_resident_bytes(4, T, D, 2) <= fa.FUSED_BWD_VMEM


def test_expert_kernel_work_against_a_hand_count(man, model, monkeypatch):
    from paddle_tpu import telemetry
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, F, E = 2048, 1536, 8
    # the program's own count, where it has one: 30000 pairs a step over
    # four expert layers
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "moe.local_pairs": 90000, "moe.steps": 3, "moe.max_expert_pairs": 1})
    P = 30000 / 4
    assert model.local_pairs_per_layer(cfg, t) == P
    mm = 2 * H * F * P
    rows_h, rows_f, mat = P * H * 2, P * F * 2, E * H * F * 2
    work = model.kernel_work(cfg, t)
    assert work["moe_gmm_swiglu"] == [(2 * mm, rows_h + rows_f + 2 * mat, 4)]
    assert work["moe_gmm"] == [
        (mm, rows_f + rows_h + mat, 4), (mm, rows_h + rows_f + mat, 4),
        (2 * mm, 2 * rows_f + rows_h + 2 * mat, 4)]
    assert work["moe_tgmm"] == [(mm, rows_h + rows_f + mat, 4)] * 3
    flops, nbytes, calls = work["moe_swiglu_bwd"][0]
    assert (flops, calls) == (2 * mm, 4)
    assert nbytes == rows_h + 4 * rows_f + 2 * mat + 2 * P * 128 * 4
    # forward 6 H F a pair, backward 12 H F and the gate's second pass
    fwd = work["moe_gmm_swiglu"][0][0] + work["moe_gmm"][0][0]
    assert fwd == 6 * H * F * P
    bwd = sum(c[0] for c in work["moe_gmm"][1:]) \
        + sum(c[0] for c in work["moe_tgmm"])
    assert bwd == 12 * H * F * P
    # no count in the program (a parent commit): the expected load
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert model.local_pairs_per_layer(cfg, t) == 16384 * 4 * 8 / 64
    assert model.kernel_work(cfg, t)["moe_gmm_swiglu"][0][0] \
        == 2 * 2 * H * F * 8192
