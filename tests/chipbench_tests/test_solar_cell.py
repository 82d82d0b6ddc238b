"""The benchmark's side of `solar_train_1chip` (PR 34), all on the CPU (and,
as test_chipbench.py, holding BENCHMARK.json to no count and no tail): the
configuration file against the published config, the appended entries, the
cell in the tiny root (contract line, traced rehearsal, planted faults,
`--control 1` through `reference/solar_open2.py`), and hand counts of
`step_flops` and of every `kernel_work` entry.

The contract line is also checked by test_chipbench.py's tests that are
parametrised over `chipbench_tiny.cells()`: the cell brings its tiny
configuration and traffic files under `tiny/`, so it is one of them.
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

CELL, CONFIG, TRAFFIC = ("solar_train_1chip",
                         "solar_open2_250b_train_ep40_tp8", "train_b1_t8192")

# upstage/Solar-Open2-250B config.json, the keys that say something of its
# shape (the catalog row of the model-configs guide)
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = ["num_hidden_layers", "layer_types", "heads_held", "kv_heads_held",
           "experts_held", "vocab_size"]


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(list(argv), root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("solar"))


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest().validate()


@pytest.fixture(scope="module")
def model(man):
    return man.model("solar_open2")


# ------------------------------------------- the configuration and entries
def test_every_published_width_is_unchanged_and_each_cut_is_listed(man):
    cfg = man.config(CONFIG)
    entry = man.configs[CONFIG]
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # the keys this file adds for the share are cuts too
    assert cfg["published"]["heads_held"] == PUBLISHED["num_attention_heads"]
    assert cfg["published"]["kv_heads_held"] \
        == PUBLISHED["num_key_value_heads"]
    assert cfg["published"]["experts_held"] == PUBLISHED["n_routed_experts"]
    assert set(cfg["reduced_why"]) == set(REDUCED)
    assert all(len(why) > 40 for why in cfg["reduced_why"].values())
    # the cut: one whole period, layers 0-3 as published
    assert cfg["layer_types"] == ["gqa", "kda", "kda", "kda"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 4
    assert [i for i, k in enumerate(cfg["layer_types"]) if k == "gqa"] \
        == [i for i in PUBLISHED["gqa_layers"] if i < 4]
    # the share of forty chips: every width as published, the router's too
    assert (cfg["heads_held"], cfg["kv_heads_held"], cfg["first_head"]) \
        == (8, 1, 0)
    assert (cfg["experts_held"], cfg["first_expert"]) == (8, 0)
    assert cfg["vocab_size"] == 196608 // 8
    assert cfg["gate_rank"] == cfg["linear_attn_config"]["head_dim"] == 128
    assert "forty chips share each layer" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 5
    assert (cfg["model"], cfg["driver"], cfg["entry"]) == (
        "solar_open2", "train", "Executor.run")
    assert cfg["precision"]["control"] == "int8"
    # no width among the cuts, and the floors of a model_config cut
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in REDUCED)
    assert len(cfg["layer_types"]) >= 4 and cfg["experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_held_parameters_are_counted_as_the_issue_counts_them(man, model):
    """840.5M parameters, 10.09 GB at 12 bytes each."""
    specs = model.param_specs(man.config(CONFIG))
    size = {n: int(np.prod(s)) for n, s, _ in specs}

    def layer(i):
        return sum(v for n, v in size.items() if n.startswith(f"l{i}_"))

    mixer = {i: sum(v for n, v in size.items() if n.startswith(f"l{i}_")
                    and not re.search(r"_(ffn_norm|router|experts|shared)",
                                      n)) for i in range(4)}
    assert round(mixer[0] / 1e6, 1) == 13.6       # gqa
    assert round(mixer[1] / 1e6, 1) == 18.1       # kda
    assert layer(0) / 1e6 == pytest.approx(156.5, abs=0.1)
    assert layer(1) / 1e6 == pytest.approx(160.9, abs=0.15)
    assert size["embed.w_0"] == size["lm_head.w_0"] == 24576 * 4096
    total = sum(size.values())
    assert total / 1e6 == pytest.approx(840.5, abs=0.5)
    assert total * 12 / 1e9 == pytest.approx(10.09, abs=0.01)


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
    assert man.end_to_end["train_tokens_per_s"]["workloads"][:2] == [
        "nmt_train_1chip", "lfm2_train_1chip"]
    assert CELL in man.end_to_end["train_tokens_per_s"]["workloads"]
    tagged = [m for m in man.doc["per_layer"] if ".solar" in m["name"]]
    assert [m["name"] for m in man.cell_per_layer(CELL)] \
        == [m["name"] for m in tagged]
    names = {m["name"] for m in tagged}
    for m in tagged:
        assert m["name"].endswith(".solar") and m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # they follow everything PR 30 left
    order = [m["name"] for m in man.doc["per_layer"]]
    assert order.index(tagged[0]["name"]) > order.index(
        "moe_tgmm_roofline.lfm2")
    wanted = {
        "device_idle_share", "peak_hbm_bytes", "compiles_in_window",
        "exec_compile_s.backend",
        "exec_gap_ms_per_step.fetch_readback", "exec_gap_ms_per_step.feed_put",
        "train_device_step_ms", "train_mfu",
        "moe_local_pairs_per_step", "moe_load_max_over_mean"}
    wanted |= {f"train_phase_ms_per_step.{p}"
               for p in ("forward", "backward", "optimizer")}
    wanted |= {f"train_op_ms_per_step.{op}" for op in (
        "kda_attention", "flash_attention", "short_conv", "l2_norm",
        "rms_norm", "moe_route", "moe_expert_ffn", "mul", "unscoped")}
    # a kernel's time follows from its share and `kernel_work`, so the
    # share stands alone
    wanted |= {f"{k}_roofline" for k in (
        "flash_attention_fwd", "flash_attention_bwd", "moe_gmm_swiglu",
        "moe_gmm", "moe_swiglu_bwd", "moe_tgmm", "moe_combine")}
    assert {w + ".solar" for w in wanted} <= names
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
    # what the other cells report is what they reported
    for other in ("nmt_train_1chip", "lfm2_train_1chip"):
        assert not names & {m["name"] for m in man.cell_per_layer(other)}
    # every roofline entry has its count in the model's kernel_work
    work = man.model("solar_open2").kernel_work(man.config(CONFIG),
                                               man.traffic(TRAFFIC))
    assert {n.split(".")[0][:-len("_roofline")] for n in names
            if n.split(".")[0].endswith("_roofline")} <= set(work)


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
    rc, res = _run(root, "--workload", CELL, "--seed", str(2**31 + 34),
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] >= 1


def test_the_other_cells_still_run_beside_it(root):
    rc, res = _run(root, "--workload", "lfm2_train_1chip", "--seed", "34",
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True
    manifest.Manifest(root).validate()
    assert tiny.cells()[:3] == ["nmt_train_1chip", "lfm2_train_1chip", CELL]


def test_the_traced_rehearsal_reports_the_programs_counts_only(root):
    from paddle_tpu import telemetry
    before = telemetry.snapshot().get("moe.steps", 0)
    rc, res = _run(root, "--workload", CELL, "--seed", "34", "--seconds",
                   "1", "--trace", "1")
    assert rc == 0 and res["correct"] is True
    assert res["device"]["busy_s"] == 0.0
    got = set(res["metrics"])
    man = manifest.Manifest(root)
    device = {m["name"] for m in man.cell_per_layer(CELL)
              if m["source"] == "device_trace"}
    assert not device & got and "train_mfu.solar" not in got
    assert {"compiles_in_window.solar", "moe_local_pairs_per_step.solar",
            "moe_load_max_over_mean.solar"} <= got
    assert res["metrics"]["compiles_in_window.solar"]["value"] == 0
    # tiny: 1 x 96 tokens, top-2 of 16 with 4 held, two expert layers: 96
    # pairs a step at an even load; the fullest of 4 is 1 to 4 x the mean
    pairs = res["metrics"]["moe_local_pairs_per_step.solar"]["value"]
    assert 0 < pairs <= 96 * 2 * 2
    assert 1.0 <= res["metrics"]["moe_load_max_over_mean.solar"]["value"] <= 4
    steps = telemetry.snapshot()["moe.steps"] - before
    assert steps == 3 + 1 + res["attempted"]


@pytest.mark.parametrize("fault", ["half_row", "state_unchanged"])
def test_a_broken_timed_path_comes_out_not_correct(root, monkeypatch, fault):
    Trainer = manifest.Manifest(root).driver("train").Trainer
    real = Trainer.step
    if fault == "state_unchanged":
        monkeypatch.setattr(Trainer, "step", lambda self, feed: 4.85)
    else:
        # the program trains on the second half of the row only (a row
        # cannot be halved in length: the program's feeds are 96 wide)
        def step(self, feed):
            cut = {k: np.concatenate([v[:, 48:], v[:, 48:]], 1)
                   for k, v in feed.items()}
            return real(self, cut)
        monkeypatch.setattr(Trainer, "step", step)
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
    # int8 stands further from float32 than the program's bfloat16 does
    assert control["int8"]["grad_gap"] > res["checks"]["grad_gap"]["value"]


def test_the_parent_commit_fails_the_cell_at_once(root, monkeypatch):
    """A program without the model (the parent, with this PR's benchmark
    files laid over it) fails in `build`, before anything is compiled."""
    import paddle_tpu.models
    monkeypatch.delattr(paddle_tpu.models, "solar_open2")
    monkeypatch.setitem(sys.modules, "paddle_tpu.models.solar_open2", None)
    with pytest.raises(ImportError):
        _run(root, "--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0")


# ----------------------------------------------- the traffic and the weights
def test_one_row_batches_are_cut_from_one_stream(man, model):
    cfg = dict(man.config(CONFIG), vocab_size=512)
    t = {"kind": "lm_stream_batches", "rows": 1, "length": 64, "pool": 3}
    a = model.make_batches(t, cfg, 2**31 + 5)
    b = model.make_batches(t, cfg, 2**31 + 5)
    assert len(a) == 3 and a[0]["ids"].shape == (1, 64)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["ids"], y["ids"])
    stream = np.concatenate([x["ids"].reshape(-1) for x in a])
    nxt = np.concatenate([x["labels"].reshape(-1) for x in a])
    np.testing.assert_array_equal(stream[1:], nxt[:-1])
    assert stream.min() >= 0 and stream.max() < 512
    assert model.tokens_per_step(man.traffic(TRAFFIC)) == 8192


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
    assert not np.array_equal(np.asarray(p["lm_head.w_0"], "float32"),
                              np.asarray(r["lm_head.w_0"], "float32"))
    assert not np.array_equal(np.asarray(p["embed.w_0"], "float32"),
                              np.asarray(p["lm_head.w_0"], "float32").T)
    for name in model.bias_names(cfg):
        assert p[name].shape == (cfg["n_routed_experts"],)
        assert str(p[name].dtype) == "float32" and np.asarray(p[name]).any()
    assert len(model.bias_names(cfg)) == len(cfg["layer_types"])


# ------------------------------------------------------------- the counts
def test_step_flops_against_a_hand_count(man, model):
    """The published widths at the cell's shape, piece by piece by hand
    (MFLOP a token forward, as ISSUE 34 reckons them)."""
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, T, D, F = 4096, 8192, 128, 1280
    gqa_proj = 2 * H * (8 + 1 + 1 + 8) * D + 2 * (8 * D) * H  # q k v g, o
    scores = 2 * 2 * T * 8 * D // 2                           # causal half
    kda_proj = 4 * 2 * H * (8 * D) \
        + 2 * (2 * H * 128 + 2 * 128 * 8 * D) + 2 * H * 8     # gates, beta
    scan = 6 * 8 * D * D                   # S k, the update, S q: 8 heads
    router = 2 * H * 320
    shared = 3 * 2 * H * F
    routed = 0.2 * 3 * 2 * H * F           # 8 x 8 / 320 pairs a token
    head = 2 * H * 24576
    forward = (gqa_proj + scores) + 3 * (kda_proj + scan) \
        + 4 * (router + shared + routed) + head
    assert model.forward_flops_per_token(cfg, T) == forward
    assert round(head / 1e6) == 201 and round(4 * shared / 1e6) == 126
    assert round((gqa_proj + 3 * kda_proj) / 1e6) == 136
    assert round(4 * routed / 1e6) == 25 and round(scores / 1e6) == 17
    assert round(4 * router / 1e6) == 10 and 3 * scan / 1e6 < 10
    assert round(forward / 1e6) == 518
    assert model.step_flops(cfg, t) == 3 * T * forward
    assert round(model.step_flops(cfg, t) / 1e12, 1) == 12.7
    # a small size too, by the same rule
    small = dict(cfg, hidden_size=64, moe_intermediate_size=48, head_dim=16,
                 heads_held=4, kv_heads_held=2, gate_rank=16,
                 linear_attn_config={"head_dim": 16,
                                     "short_conv_kernel_size": 4},
                 n_routed_experts=16, experts_held=4, num_experts_per_tok=2,
                 vocab_size=128, layer_types=["gqa", "kda"])
    want = 2 * 64 * (4 + 2 + 2 + 4) * 16 + 2 * 64 * 64 + 4 * 96 * 64 // 2 \
        + 4 * 2 * 64 * 64 + 2 * (2 * 64 * 16 + 2 * 16 * 64) + 2 * 64 * 4 \
        + 6 * 4 * 16 * 16 \
        + 2 * (2 * 64 * 16 + (0.5 + 1) * 6 * 64 * 48) + 2 * 64 * 128
    assert model.forward_flops_per_token(small, 96) == want
    assert model.step_flops(small, {"rows": 1, "length": 96}) == 3 * 96 * want


def test_attention_kernel_work_against_a_hand_count(man, model):
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    B, T, H, KV, D = 1, 8192, 8, 1, 128
    product = 2 * B * H * T * T * D // 2
    q, kv = B * T * H * D * 2, B * T * KV * D * 2          # bfloat16
    work = model.kernel_work(cfg, t)
    assert work["flash_attention_fwd"] == [
        (2 * product, q + 2 * kv + q, 1)]                  # q k v -> out
    assert work["flash_attention_bwd"] == [               # five products
        (5 * product, (q + 2 * kv + q + q) + (q + 2 * kv), 1)]
    assert work["flash_attention_dq"] == [
        (3 * product, q + 2 * kv + q + q + q, 1)]
    assert work["flash_attention_dkv"] == [
        (4 * product, q + 2 * kv + q + q + 2 * kv, 1)]
    # one key-value head's bytes: the floor is compute's, 0.7 ms forward
    floor = counts.floor_seconds(work["flash_attention_fwd"], "TPU v5 lite")
    assert floor == pytest.approx(2 * product / 197e12)
    assert 1e3 * floor == pytest.approx(0.70, abs=0.01)
    # the dq of a key-value head's 8 query heads fills FUSED_BWD_VMEM to
    # the byte, so the one backward kernel runs at this shape
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa._bwd_resident_bytes(8, T, D, 2) == fa.FUSED_BWD_VMEM


def test_expert_kernel_work_against_a_hand_count(man, model, monkeypatch):
    from paddle_tpu import telemetry
    cfg, t = man.config(CONFIG), man.traffic(TRAFFIC)
    H, F, E, N, k = 4096, 1280, 8, 8192, 8
    # the program's own count, where it has one: 6000 pairs a step over
    # four expert layers
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "moe.local_pairs": 18000, "moe.steps": 3, "moe.max_expert_pairs": 1})
    P = 6000 / 4
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
    # the way back to the tokens: the pairs' rows in, every token's row
    # out; forward with the places and the weights, backward the places
    out = N * H * 2
    assert work["moe_combine"] == [
        (2 * P * H, rows_h + out + N * k * (4 + 4), 4),
        (P * H, rows_h + out + N * k * 4, 4)]
    # forward 6 H F a pair, backward 12 H F and the gate's second pass
    fwd = work["moe_gmm_swiglu"][0][0] + work["moe_gmm"][0][0]
    assert fwd == 6 * H * F * P
    bwd = sum(c[0] for c in work["moe_gmm"][1:]) \
        + sum(c[0] for c in work["moe_tgmm"])
    assert bwd == 12 * H * F * P
    # no count in the program (a parent commit): the expected load, 205
    # pairs an expert
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert model.local_pairs_per_layer(cfg, t) == N * 8 * 8 / 320
    assert round(model.local_pairs_per_layer(cfg, t) / E) == 205
