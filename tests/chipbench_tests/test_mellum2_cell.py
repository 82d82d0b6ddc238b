"""The benchmark's side of `mellum2_train_1chip` (PR 36), all on the CPU (and,
as test_chipbench.py, holding BENCHMARK.json to no count and no tail): the
configuration file against the published config, the appended entries, the
cell in the tiny root (contract line, traced rehearsal, planted faults,
`--control 1` through `reference/mellum2.py`), and hand counts of
`step_flops` and of every `kernel_work` entry.

The contract line is also checked by test_chipbench.py's tests that are
parametrised over `chipbench_tiny.cells()`: the cell brings its tiny
configuration file under `tiny/` (the traffic's is there), so it is one of
them.
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

CELL, CONFIG, TRAFFIC = ("mellum2_train_1chip", "mellum2_12b_train_ep4",
                         "train_b1_t8192")
TAG = ".mellum2"

# JetBrains/Mellum2-12B-A2.5B-Instruct config.json, the keys that say
# something of its shape (the catalog row of the model-configs guide)
_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": _PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "experts_held", "vocab_size"]


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(list(argv), root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("mellum2"))


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest().validate()


@pytest.fixture(scope="module")
def model(man):
    return man.model("mellum2")


# ------------------------------------------- the configuration and entries
def test_every_published_width_is_unchanged_and_each_cut_is_listed(man):
    cfg = man.config(CONFIG)
    entry = man.configs[CONFIG]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] != value, key
            assert key in cfg["published"], key
        else:
            assert cfg[key] == value, key
    assert cfg["published"]["num_hidden_layers"] == 28
    assert cfg["published"]["vocab_size"] == 98304
    assert cfg["published"]["experts_held"] == PUBLISHED["num_experts"]
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert all(len(why) > 40 for why in cfg["reduced_why"].values())
    # the cut: one whole period, layers 0-3 as published
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:4] == _PERIOD
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 4
    # the share of four chips: every width as published, the router's too
    assert (cfg["experts_held"], cfg["first_expert"]) == (16, 0)
    assert cfg["vocab_size"] == 98304 // 4
    assert "four chips share each layer" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 6
    assert (cfg["model"], cfg["driver"], cfg["entry"]) == (
        "mellum2", "train", "Executor.run")
    assert cfg["precision"]["control"] == "int8"
    # no width among the cuts, and the floors of a model_config cut
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in REDUCED)
    assert len(cfg["layer_types"]) >= 4 and cfg["experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_held_parameters_are_counted_as_the_issue_counts_them(man, model):
    """595.1M parameters, 7.14 GB at 12 bytes each."""
    specs = model.param_specs(man.config(CONFIG))
    size = {n: int(np.prod(s)) for n, s, _ in specs}

    def layer(i, pattern=""):
        return sum(v for n, v in size.items()
                   if n.startswith(f"l{i}_") and re.search(pattern, n))

    assert round(layer(0, r"_(q|k|v|o)\.w_0") / 1e6, 2) == 21.23
    assert round(layer(0, "router") / 1e6, 2) == 0.15
    assert round(layer(0, "experts") / 16 / 1e6, 2) == 6.19
    assert layer(0) == layer(3)            # the layers differ in no shape
    assert layer(0) / 1e6 == pytest.approx(120.5, abs=0.05)
    assert size["embed.w_0"] == size["lm_head.w_0"] == 24576 * 2304
    total = sum(size.values())
    assert total / 1e6 == pytest.approx(595.1, abs=0.1)
    assert total * 12 / 1e9 == pytest.approx(7.14, abs=0.01)


def test_the_limits_stand_between_their_two_readings(man):
    cfg = man.config(CONFIG)
    assert set(cfg["limits"]) == {"loss_gap", "grad_gap", "delta_gap"}
    for name, limit in cfg["limits"].items():
        lower, upper = cfg["limits_readings"][name]
        assert 0 < 2 * lower < limit < upper / 2, name     # room on both sides


def test_the_builders_runs_stand_on_the_right_side_of_the_limits(man):
    """Every reading of `chipbench/runs/mellum2_train_1chip.jsonl` (made
    on the chip while the limits were being found) against the limits as
    they stand: each of the program's runs passes all three, each control
    fails at least one, and `limits_readings` are the file's own."""
    limits = man.config(CONFIG)["limits"]
    readings = man.config(CONFIG)["limits_readings"]
    path = os.path.join(os.path.dirname(manifest.__file__), "runs",
                        f"{CELL}.jsonl")
    runs = [json.loads(line)["result"] for line in open(path)]
    assert len(runs) >= 10
    worst = {name: 0.0 for name in limits}
    for res in runs:
        for name, check in res["checks"].items():
            assert check["value"] < limits[name], (name, check)
            worst[name] = max(worst[name], check["value"])
        for fault, read in res.get("control", {}).items():
            assert any(read[n] >= limits[n] for n in limits), (fault, read)
    # the upper reading of each: the least that this control read
    upper_of = {"loss_gap": "state_unchanged", "grad_gap": "int8",
                "delta_gap": "state_unchanged"}
    for name, (lower, upper) in readings.items():
        assert lower == pytest.approx(worst[name], rel=0.02), name
        assert upper == pytest.approx(
            min(res["control"][upper_of[name]][name] for res in runs
                if "control" in res), rel=0.02), name


def test_the_appended_entries_list_the_new_cell_alone(man):
    cell = man.cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [m["name"] for m in man.cell_end_to_end(CELL)] == [
        "train_tokens_per_s", "setup_s"]
    assert man.end_to_end["train_tokens_per_s"]["workloads"][:3] == [
        "nmt_train_1chip", "lfm2_train_1chip", "solar_train_1chip"]
    assert CELL in man.end_to_end["train_tokens_per_s"]["workloads"]
    tagged = [m for m in man.doc["per_layer"] if TAG in m["name"]]
    assert [m["name"] for m in man.cell_per_layer(CELL)] \
        == [m["name"] for m in tagged]
    names = {m["name"] for m in tagged}
    for m in tagged:
        assert m["name"].endswith(TAG) and m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # they follow everything PR 34 left
    order = [m["name"] for m in man.doc["per_layer"]]
    assert order.index(tagged[0]["name"]) > order.index(
        "moe_combine_roofline.solar")
    # a benchmark holds 128 per-layer metrics at most; the cell reports 22
    # (a kernel's time follows from its share of the roofline, so no
    # kernel of it has a time entry)
    assert len(man.doc["per_layer"]) <= 128 and len(tagged) == 22
    wanted = {
        "device_idle_share", "peak_hbm_bytes", "compiles_in_window",
        "train_device_step_ms", "train_mfu",
        "moe_local_pairs_per_step", "moe_load_max_over_mean"}
    wanted |= {f"train_op_ms_per_step.{op}" for op in (
        "flash_attention", "rotary_embedding", "moe_route",
        "moe_expert_ffn", "mul", "unscoped")}
    kernels = ("flash_attention_win_fwd", "flash_attention_win_bwd",
               "flash_attention_fwd", "flash_attention_bwd",
               "moe_gmm_swiglu", "moe_gmm", "moe_swiglu_bwd", "moe_tgmm",
               "moe_combine")
    wanted |= {f"{k}_roofline" for k in kernels}
    assert {w + TAG for w in wanted} == names
    for m in tagged:
        if m["name"].split(".")[0].endswith("_roofline"):
            assert (m["unit"], m["better"], m["source"], m["layer"]) == (
                "%", "higher", "device_trace", "kernels")
        if m["name"].startswith(("moe_local", "moe_load")):
            assert m["source"] == "program_counter"
        assert m["moves"] == "train_tokens_per_s"
    # the layers are PERF.md section 3's, letter for letter
    with open(os.path.join(tiny.REPO, "PERF.md")) as f:
        perf = f.read()
    rows = set(re.findall(r"^\| ([^|]+?) \|", perf, re.M))
    assert {m["layer"] for m in tagged} <= rows
    # what the other cells report is what they reported
    for other in ("nmt_train_1chip", "lfm2_train_1chip",
                  "solar_train_1chip"):
        assert not names & {m["name"] for m in man.cell_per_layer(other)}
    # every roofline entry has its count in the model's kernel_work
    work = man.model("mellum2").kernel_work(man.config(CONFIG),
                                           man.traffic(TRAFFIC))
    assert set(work) == set(kernels)


def test_the_whys_fit_their_200_characters(man):
    for group in ("configs", "workloads"):
        for x in man.doc[group]:
            assert 1 <= len(x["why"]) <= 200, x["name"]
    assert len(json.dumps(man.doc, indent=1)) < 64 * 1024
    t = man.traffic(TRAFFIC)
    assert (t["kind"], t["rows"], t["length"], t["pool"], t["warm_steps"],
            t["trace_s"], t["reference_block_rows"]) == (
        "lm_stream_batches", 1, 8192, 8, 2, 5, 1)


# --------------------------------------------------- the cell, tiny, on CPU
def test_the_cell_prints_the_contract_line(root):
    rc, res = _run(root, "--workload", CELL, "--seed", str(2**31 + 36),
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] >= 1


def test_the_other_cells_still_run_beside_it(root):
    rc, res = _run(root, "--workload", "solar_train_1chip", "--seed", "36",
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True
    manifest.Manifest(root).validate()
    assert tiny.cells()[:4] == ["nmt_train_1chip", "lfm2_train_1chip",
                                "solar_train_1chip", CELL]


def test_the_traced_rehearsal_reports_the_programs_counts_only(root):
    from paddle_tpu import telemetry
    before = telemetry.snapshot().get("moe.steps", 0)
    rc, res = _run(root, "--workload", CELL, "--seed", "36", "--seconds",
                   "1", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["device"]["busy_s"] == 0.0
    got = set(res["metrics"])
    man = manifest.Manifest(root)
    device = {m["name"] for m in man.cell_per_layer(CELL)
              if m["source"] == "device_trace"}
    assert not device & got and "train_mfu" + TAG not in got
    assert {"compiles_in_window" + TAG, "moe_local_pairs_per_step" + TAG,
            "moe_load_max_over_mean" + TAG} <= got
    assert res["metrics"]["compiles_in_window" + TAG]["value"] == 0
    # tiny: 1 x 96 tokens, top-2 of 8 with 4 held, two expert layers: 192
    # pairs a step at an even load; the fullest of 4 is 1 to 4 x the mean
    pairs = res["metrics"]["moe_local_pairs_per_step" + TAG]["value"]
    assert 0 < pairs <= 96 * 2 * 2
    assert 1.0 <= res["metrics"]["moe_load_max_over_mean" + TAG]["value"] <= 4
    steps = telemetry.snapshot()["moe.steps"] - before
    assert steps == 3 + 1 + res["attempted"]


@pytest.mark.parametrize("fault", ["half_row", "state_unchanged",
                                   "no_window"])
def test_a_broken_timed_path_comes_out_not_correct(root, monkeypatch, fault):
    Trainer = manifest.Manifest(root).driver("train").Trainer
    real = Trainer.step
    if fault == "state_unchanged":
        monkeypatch.setattr(Trainer, "step", lambda self, feed: 4.85)
    elif fault == "half_row":
        # the program trains on the second half of the row only (a row
        # cannot be halved in length: the program's feeds are 96 wide)
        def step(self, feed):
            cut = {k: np.concatenate([v[:, 48:], v[:, 48:]], 1)
                   for k, v in feed.items()}
            return real(self, cut)
        monkeypatch.setattr(Trainer, "step", step)
    else:
        # the program's sliding layers lose their window: every op of the
        # one attention type runs as a full causal layer
        from paddle_tpu import layers
        windowed = layers.flash_attention
        monkeypatch.setattr(
            layers, "flash_attention",
            lambda *a, window=None, **kw: windowed(*a, **kw))
    rc, res = _run(root, "--workload", CELL, "--seed", "35", "--seconds",
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
    # one row: its half is the first half of its positions, finite numbers
    assert all(np.isfinite(v) for v in control["half_batch"].values())
    assert all(np.isfinite(v) for v in control["int8"].values())


def test_the_parent_commit_fails_the_cell_at_once(root, monkeypatch):
    """A program without the model (the parent, with this PR's benchmark
    files laid over it) fails in `build`, before anything is compiled."""
    import paddle_tpu.models
    monkeypatch.delattr(paddle_tpu.models, "mellum2")
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.mellum2", None)
    with pytest.raises(ImportError):
        _run(root, "--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0")


# ----------------------------------------------- the traffic and the weights
def test_weights_follow_the_seed_and_the_stated_precision(man, model):
    cfg = dict(man.config(CONFIG))
    cfg.update(tiny._tiny("configs", CONFIG))
    p = model.make_params(cfg, 2**31 + 1, "bfloat16")
    q = model.make_params(cfg, 2**31 + 1, "bfloat16")
    r = model.make_params(cfg, 2, "bfloat16")
    specs = model.param_specs(cfg)
    assert set(p) == {n for n, _, _ in specs} and model.bias_names(cfg) == []
    keep = set(cfg["precision"]["float32_parameters"])
    for name, shape, kind in specs:
        assert p[name].shape == tuple(shape)
        assert str(p[name].dtype) == ("float32" if kind in keep
                                      else "bfloat16"), name
        np.testing.assert_array_equal(np.asarray(p[name], "float32"),
                                      np.asarray(q[name], "float32"))
    assert not np.array_equal(np.asarray(p["lm_head.w_0"], "float32"),
                              np.asarray(r["lm_head.w_0"], "float32"))
    assert not np.array_equal(np.asarray(p["embed.w_0"], "float32"),
                              np.asarray(p["lm_head.w_0"], "float32").T)
    assert model.tokens_per_step(man.traffic(TRAFFIC)) == 8192
    batches = model.make_batches(
        {"kind": "lm_stream_batches", "rows": 1, "length": 64, "pool": 2},
        dict(cfg, vocab_size=512), 2**31 + 5)
    assert batches[0]["ids"].shape == (1, 64) and batches[1]["ids"].max() < 512


# ------------------------------------------------------------- the counts
def test_step_flops_against_a_hand_count(man, model):
    """The published widths at the cell's shape, piece by piece by hand
    (MFLOP a token forward, as ISSUE 36 reckons them)."""
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, T, D, F, W = 2304, 8192, 128, 896, 1024
    proj = 2 * H * (32 + 4 + 4) * D + 2 * (32 * D) * H        # q k v, o
    full = 2 * 2 * 32 * D * (T * T // 2) / T                  # causal half
    band = W * (W + 1) // 2 + (T - W) * W                     # score elements
    sliding = 2 * 2 * 32 * D * band / T
    router = 2 * H * 64
    routed = 2 * 3 * 2 * H * F             # 8 x 16 / 64 = 2 pairs a token
    head = 2 * H * 24576
    forward = 4 * proj + full + 3 * sliding + 4 * (router + routed) + head
    assert model.forward_flops_per_token(cfg, T) == forward
    assert round(4 * proj / 1e6) == 170 and round(head / 1e6) == 113
    assert round((full + 3 * sliding) / 1e6) == 114
    assert round(3 * sliding / 1e6) == 47 and round(4 * routed / 1e6) == 99
    assert round(4 * router / 1e6) == 1 and round(forward / 1e6) == 498
    assert model.step_flops(cfg, t) == 3 * T * forward
    assert round(model.step_flops(cfg, t) / 1e12, 1) == 12.2
    # the band alone: 1024 x 1025 / 2 + 7168 x 1024 score elements a head
    # and row, a little under a quarter of the causal half
    assert band == 1024 * 1025 // 2 + 7168 * 1024
    assert band / (T * T // 2) == pytest.approx(0.2344, abs=1e-4)
    # a small size too, by the same rule; a window over the length is none
    small = dict(cfg, hidden_size=64, moe_intermediate_size=48, head_dim=16,
                 num_attention_heads=4, num_key_value_heads=2,
                 num_experts=8, experts_held=4, num_experts_per_tok=2,
                 vocab_size=128, sliding_window=32,
                 layer_types=["sliding_attention", "full_attention"])
    want = 2 * (2 * 64 * (4 + 2 + 2) * 16 + 2 * 64 * 64) \
        + 4 * 4 * 16 * (96 * 96 // 2) / 96 \
        + 4 * 4 * 16 * (32 * 33 // 2 + 64 * 32) / 96 \
        + 2 * (2 * 64 * 8 + 1 * 6 * 64 * 48) + 2 * 64 * 128
    assert model.forward_flops_per_token(small, 96) == want
    assert model.step_flops(small, {"rows": 1, "length": 96}) == 3 * 96 * want
    assert model.forward_flops_per_token(dict(small, sliding_window=96), 96) \
        == model.forward_flops_per_token(dict(small, sliding_window=None), 96)


def test_attention_kernel_work_against_a_hand_count(man, model):
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    B, T, H, KV, D, W = 1, 8192, 32, 4, 128, 1024
    q, kv = B * T * H * D * 2, B * T * KV * D * 2          # bfloat16
    work = model.kernel_work(cfg, t)
    # the full layer: the causal half, one call a step
    product = 2 * B * H * D * (T * T // 2)
    assert work["flash_attention_fwd"] == [
        (2 * product, q + 2 * kv + q, 1)]                  # q k v -> out
    assert work["flash_attention_bwd"] == [               # five products
        (5 * product, (q + 2 * kv + q + q) + (q + 2 * kv), 1)]
    # the sliding layers: the band alone, three calls a step, the same
    # arrays in and out
    band = 2 * B * H * D * (W * (W + 1) // 2 + (T - W) * W)
    assert work["flash_attention_win_fwd"] == [
        (2 * band, q + 2 * kv + q, 3)]
    assert work["flash_attention_win_bwd"] == [
        (5 * band, (q + 2 * kv + q + q) + (q + 2 * kv), 3)]
    # the floors are compute's: 2.79 ms the full layer's forward, 0.65 ms
    # a sliding layer's
    floor = counts.floor_seconds(work["flash_attention_fwd"], "TPU v5 lite")
    assert floor == pytest.approx(2 * product / 197e12)
    assert 1e3 * floor == pytest.approx(2.79, abs=0.01)
    win = counts.floor_seconds(work["flash_attention_win_fwd"], "TPU v5 lite")
    assert 1e3 * win / 3 == pytest.approx(0.654, abs=0.002)
    # the dq of a key-value head's 8 query heads fills FUSED_BWD_VMEM to
    # the byte, as solar's: the one backward kernel runs at this shape
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._bwd_resident_bytes(8, T, D, 2) == fa.FUSED_BWD_VMEM
    # a tiny length under the window: every layer's kernels are the plain
    # ones, two entries under one name
    small = dict(cfg, layer_types=["sliding_attention", "full_attention"])
    names = model.kernel_work(small, {"rows": 1, "length": 512})
    assert "flash_attention_win_fwd" not in names
    assert len(names["flash_attention_fwd"]) == 2


def test_expert_kernel_work_against_a_hand_count(man, model, monkeypatch):
    from paddle_tpu import telemetry
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, F, E, N, k = 2304, 896, 16, 8192, 8
    # the program's own count, where it has one: 60000 pairs a step over
    # four expert layers
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "moe.local_pairs": 180000, "moe.steps": 3,
        "moe.max_expert_pairs": 1})
    P = 60000 / 4
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
    out = N * H * 2
    assert work["moe_combine"] == [
        (2 * P * H, rows_h + out + N * k * (4 + 4), 4),
        (P * H, rows_h + out + N * k * 4, 4)]
    fwd = work["moe_gmm_swiglu"][0][0] + work["moe_gmm"][0][0]
    assert fwd == 6 * H * F * P
    # no count in the program (a parent commit): the expected load, 2
    # pairs a token, 1024 pairs an expert
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    work = model.kernel_work(cfg, t)
    assert work["moe_gmm"][0][0] == 2 * H * F * (N * 8 * 16 / 64)
    assert N * 8 * 16 / 64 / E == 1024
