"""Benchmark entry — prints ONE JSON line with the headline metric.

Flagship: Transformer-base train-step throughput (tokens/sec) on the
chip (ref benchmark/fluid/machine_translation.py), with MFU computed
from XLA's own cost analysis and corroborated by device-side profiler
timing. Secondary metrics (SURVEY §5): ResNet-50 images/sec, MNIST MLP
steps/sec, inference latency — all in the same JSON line.

One process: JAX is initialized once, here, and the stages run
in-process (a chip belongs to one process — there is no probe child
and no supervisor). No TPU means a non-zero exit and no result line;
the only CPU run is the one asked for with JAX_PLATFORMS=cpu, and its
line says `"platform": "cpu"` (plumbing evidence, never a device
number). A stage that raises fails the run.
"""
import json
import os
import sys
import time

import numpy as np


def _emit(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _peak_flops(device):
    """The one peak table (telemetry/attribution.py, PADDLE_TPU_PEAK_FLOPS
    override included). A TPU the table does not know is an error, not
    a default; off-TPU there is no peak and no MFU."""
    from paddle_tpu.telemetry.attribution import peak_flops
    peak = peak_flops(device)
    if peak is None and device.platform == "tpu":
        raise RuntimeError(
            f"device_kind {device.device_kind!r} has no entry in the "
            "peak table (paddle_tpu/telemetry/attribution.py); set "
            "PADDLE_TPU_PEAK_FLOPS")
    return peak


def _aot_compile(jfn, args):
    """AOT-compile once; return (callable, flops) — the compiled
    executable IS the benchmarked callable, so cost analysis costs no
    second compile."""
    compiled = jfn.lower(*args).compile()
    f = (compiled.cost_analysis() or {}).get("flops")
    return compiled, (float(f) if f and f > 0 else None)


def _median_window_time(run_window, windows):
    """Median wall time of `windows` repeats of run_window()."""
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        run_window()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _transformer_analytic_flops(cfg, B, T):
    """Analytic matmul FLOPs per train step (fwd 2MNK, bwd 4MNK → 6MNK)."""
    d, dff, L = cfg.d_model, cfg.d_inner, cfg.n_layer
    # per token per layer: qkv+o (4 d*d) + ffn (2 d*dff); encoder+decoder
    # decoder adds cross-attn qkv+o (~4 d*d more)
    enc = L * (4 * d * d + 2 * d * dff)
    dec = L * (8 * d * d + 2 * d * dff)
    attn = 2 * L * 2 * (2 * T * d)  # scores+context, enc+dec, per token
    logits = cfg.trg_vocab * d
    per_token = 2 * (enc + dec + attn + logits)
    return 6 / 2 * per_token * B * T  # 3x fwd-only for fwd+bwd


def bench_transformer(platform, batch=None, profile=True):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core.trace import build_step_fn
    from paddle_tpu.models import transformer as tfm

    on_tpu = platform == "tpu"
    B, T = (64, 128) if on_tpu else (8, 32)
    if batch:
        B = batch
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        with pt.unique_name.guard():
            cfg = tfm.TransformerConfig(
                src_vocab=8000, trg_vocab=8000, max_len=T,
                d_model=512, d_inner=2048, n_head=8, n_layer=6,
                dropout=0.1, fused_qkv=True)
            feeds, avg_cost, tok = tfm.build_program(cfg, maxlen=T)
            pt.optimizer.Adam(1e-3).minimize(avg_cost)
    # bf16 matmuls on the MXU, fp32 optimizer state (SURVEY §5 target)
    pt.amp.cast_program_to_bf16(main_p)

    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        pt.amp.cast_params_to_bf16(main_p, scope)
        persist = {v.name: scope.get(v.name)
                   for v in main_p.persistable_vars()}

    rng = np.random.RandomState(0)
    src = rng.randint(3, cfg.src_vocab, (B, T)).astype("int32")
    trg = np.concatenate([np.zeros((B, 1), "int32"),
                          (src[:, :-1] + 1) % cfg.trg_vocab], axis=1)
    feed = {"src": jnp.asarray(src),
            "src_len": jnp.full(B, T, jnp.int32),
            "trg": jnp.asarray(trg),
            "trg_len": jnp.full(B, T, jnp.int32),
            "label": jnp.asarray((src + 1) % cfg.trg_vocab, jnp.int32)}
    key = jax.random.PRNGKey(0)

    step_fn = build_step_fn(main_p, [avg_cost.name], False, None)
    jfn, flops_ca = _aot_compile(jax.jit(step_fn, donate_argnums=(0,)),
                                 (persist, feed, key))
    flops_step = flops_ca or _transformer_analytic_flops(cfg, B, T)
    fetches, persist = jfn(persist, feed, key)
    np.asarray(fetches[0])    # completion barrier: device→host readback

    n = 50 if on_tpu else 5
    state = {"persist": persist, "loss": 0.0}

    def window():
        p = state["persist"]
        for _ in range(n):
            fetches, p = jfn(p, feed, key)
        state["persist"] = p
        state["loss"] = float(np.asarray(fetches[0]))

    dt = _median_window_time(window, 3 if on_tpu else 1)
    loss = state["loss"]
    assert np.isfinite(loss), f"non-finite loss {loss}"
    tokens_per_sec = n * B * T / dt

    peak = _peak_flops(jax.devices()[0])
    mfu = (flops_step * n / dt / peak) if peak else None
    evidence = {
        "mfu_method": "xla_cost_analysis" if flops_ca
                      else "analytic_matmul",
        "flops_per_step": flops_step,
        "wall_step_ms": round(dt / n * 1e3, 2),
    }
    if on_tpu and profile:
        # device-side per-step time from the profiler trace: the
        # xplane event durations corroborate the wall clock
        from paddle_tpu.profiler import profile_step_fn

        def one_step():
            fetches, state["persist"] = jfn(state["persist"], feed,
                                            key)
            return fetches

        dev_s, fams = profile_step_fn(one_step, steps=10)
        evidence["device_step_ms"] = round(dev_s * 1e3, 2)
        evidence["device_mfu"] = round(flops_step / dev_s / peak, 4)
        top = sorted(fams.items(), key=lambda kv: -kv[1])[:5]
        evidence["device_top_ops_ms"] = {
            k: round(v * 1e3, 2) for k, v in top}
    return tokens_per_sec, mfu, loss, evidence


def bench_resnet(platform):
    """ResNet-50 train-step images/sec (ref benchmark/fluid/models/resnet.py)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core.trace import build_step_fn
    from paddle_tpu.models import resnet

    on_tpu = platform == "tpu"
    # B=128 measured +18% img/s over B=32 on v5e (better conv batching)
    B, HW = (128, 224) if on_tpu else (4, 64)
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        with pt.unique_name.guard():
            img = pt.layers.data("image", (3, HW, HW), dtype="float32")
            lbl = pt.layers.data("label", (1,), dtype="int64")
            predict = resnet.resnet(img, class_dim=1000, depth=50)
            loss = pt.layers.mean(pt.layers.cross_entropy(
                input=predict, label=lbl))
            pt.optimizer.Momentum(0.1, 0.9).minimize(loss)
    pt.amp.cast_program_to_bf16(main_p)

    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        pt.amp.cast_params_to_bf16(main_p, scope)
        persist = {v.name: scope.get(v.name)
                   for v in main_p.persistable_vars()}

    rng = np.random.RandomState(0)
    feed = {"image": jnp.asarray(rng.rand(B, 3, HW, HW).astype("float32")),
            "label": jnp.asarray(rng.randint(0, 1000, (B, 1)), jnp.int32)}
    key = jax.random.PRNGKey(0)
    step_fn = build_step_fn(main_p, [loss.name], False, None)
    jfn = jax.jit(step_fn, donate_argnums=(0,))
    fetches, persist = jfn(persist, feed, key)
    np.asarray(fetches[0])
    n = 20 if on_tpu else 2
    state = {"persist": persist, "loss": 0.0}

    def window():
        p = state["persist"]
        for _ in range(n):
            fetches, p = jfn(p, feed, key)
        state["persist"] = p
        state["loss"] = float(np.asarray(fetches[0]))

    dt = _median_window_time(window, 3 if on_tpu else 1)
    assert np.isfinite(state["loss"])
    return n * B / dt


def bench_flash_long_context(platform):
    """Long-context flash attention: causal fwd+bwd at T=32k (the
    unfused path cannot compile here — SURVEY §5 long-context story)."""
    if platform != "tpu":
        return None
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    B, H, T, D = 1, 8, 32768, 64
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(B, H, T, D).astype("float32"),
                           jnp.bfloat16) for _ in range(3)]

    def loss_fn(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))
    out = g(q, k, v)
    np.asarray(out[0][0, 0, 0])
    n = 5

    def window():
        out = g(q, k, v)
        for _ in range(n - 1):
            out = g(q, k, v)
        np.asarray(out[0][0, 0, 0])

    dt = _median_window_time(window, 3) / n
    # causal fwd+bwd matmul flops: 3 passes * 2MNK * BHT^2D / 2
    fl = 12 * B * H * T * T * D * 0.5
    peak = _peak_flops(jax.devices()[0])
    return {"flash_attn_32k_causal_ms": round(dt * 1e3, 1),
            "flash_attn_32k_mfu": round(fl / dt / peak, 4)}


def bench_inference(platform):
    """InferenceEngine latency/throughput (ref inference/api/api_impl.cc
    deploy story): transformer encoder forward and ResNet-50 forward,
    jit-cached path plus the AOT-compiled (save_compiled/load_compiled)
    path for ResNet."""
    import tempfile
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import resnet

    on_tpu = platform == "tpu"
    out = {}
    rng = np.random.RandomState(0)

    # --- ResNet-50 forward, B=32 ---
    B, HW = (32, 224) if on_tpu else (2, 64)
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        with pt.unique_name.guard():
            img = pt.layers.data("image", (3, HW, HW), dtype="float32")
            predict = resnet.resnet(img, class_dim=1000, depth=50)
    infer_p = main_p.clone(for_test=True)
    scope = pt.Scope()
    exe = pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
    eng = InferenceEngine(infer_p, ["image"], [predict], scope,
                          use_bf16=True)
    x = rng.rand(B, 3, HW, HW).astype("float32")
    eng.run({"image": x})  # compile
    n = 20 if on_tpu else 2
    dt = _median_window_time(
        lambda: [eng.run({"image": x}, return_numpy=False)
                 for _ in range(n)] and np.asarray(
            eng.run({"image": x})[0][0, :1]), 3) / (n + 1)
    out["resnet50_infer_images_per_sec"] = round(B / dt, 1)
    out["resnet50_infer_latency_ms"] = round(dt * 1e3, 2)

    # AOT roundtrip: save_compiled → load_compiled → run. TPU only:
    # exporting ResNet-50 StableHLO on CPU takes minutes and the CPU
    # number means nothing (the roundtrip itself is covered by tests)
    if not on_tpu:
        return out
    with tempfile.TemporaryDirectory() as d:
        eng.save_compiled(d, {"image": (B, 3, HW, HW)})
        pred = InferenceEngine.load_compiled(d)
        pred.run({"image": x})
        dt = _median_window_time(
            lambda: np.asarray(pred.run({"image": x})[0][0, :1]), 3)
        out["resnet50_infer_aot_latency_ms"] = round(dt * 1e3, 2)
    return out


def bench_deepfm(platform):
    """DeepFM CTR at scale (ref BASELINE config 5 + lookup_table_op.cc
    is_sparse): 8M-row embedding tables trained with lazy row-sparse
    Adam — update bandwidth O(batch), not O(vocab). Returns
    {examples/s, step ms, HBM peak} (VERDICT r3 #5)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core.trace import build_step_fn
    from paddle_tpu.models import deepfm

    on_tpu = platform == "tpu"
    B, F = (4096, 26) if on_tpu else (64, 6)
    vocab = 8_000_000 if on_tpu else 1000
    # `bench.py --deepfm-vocab-rows=N` (env BENCH_DEEPFM_VOCAB_ROWS):
    # scale the CTR vocabulary; vocabularies past single-device HBM
    # belong to the sharded engine (`bench.py --sparse`, BENCH_sparse)
    env_vocab = os.environ.get("BENCH_DEEPFM_VOCAB_ROWS")
    if env_vocab:
        vocab = int(float(env_vocab))
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        with pt.unique_name.guard():
            feeds, loss, prob = deepfm.build_program(
                num_fields=F, vocab_size=vocab, embed_dim=16)
            pt.optimizer.Adam(1e-3).minimize(loss)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        persist = {v.name: scope.get(v.name)
                   for v in main_p.persistable_vars()}
    rng = np.random.RandomState(0)
    feed = {"feat_ids": jnp.asarray(
                rng.randint(0, vocab, (B, F, 1)), jnp.int32),
            "feat_vals": jnp.asarray(rng.rand(B, F).astype("float32")),
            "label": jnp.asarray(
                rng.randint(0, 2, (B, 1)).astype("float32"))}
    key = jax.random.PRNGKey(0)
    step_fn = build_step_fn(main_p, [loss.name], False, None)
    jfn = jax.jit(step_fn, donate_argnums=(0,))
    fetches, persist = jfn(persist, feed, key)
    np.asarray(fetches[0])
    n = 20 if on_tpu else 2
    state = {"persist": persist, "loss": 0.0}

    def window():
        p = state["persist"]
        for _ in range(n):
            fetches, p = jfn(p, feed, key)
        state["persist"] = p
        state["loss"] = float(np.asarray(fetches[0]))

    dt = _median_window_time(window, 3 if on_tpu else 1)
    assert np.isfinite(state["loss"])
    ids_np = np.asarray(feed["feat_ids"]).reshape(-1)
    out = {"deepfm_examples_per_sec": round(n * B / dt, 1),
           "deepfm_step_ms": round(dt / n * 1e3, 2),
           "deepfm_vocab_rows": vocab,
           # dedup opportunity of the batch (the sharded engine's wire
           # win scales with 1 - unique_ratio); this dense-path stage
           # exchanges nothing — the engine numbers live in
           # BENCH_sparse.json (`bench.py --sparse`)
           "deepfm_unique_ratio": round(
               len(np.unique(ids_np)) / ids_np.size, 4),
           "deepfm_exchange_bytes": 0}
    stats = jax.devices()[0].memory_stats()    # None on CPU
    if stats and stats.get("peak_bytes_in_use"):
        out["deepfm_hbm_peak_gb"] = round(
            stats["peak_bytes_in_use"] / 2**30, 2)
    return out


def bench_mnist(platform):
    """MNIST MLP train steps/sec (ref benchmark/fluid/mnist.py)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.core.trace import build_step_fn
    from paddle_tpu.models import mnist as mn

    B = 128
    main_p, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup):
        with pt.unique_name.guard():
            img = pt.layers.data("image", (784,), dtype="float32")
            lbl = pt.layers.data("label", (1,), dtype="int64")
            predict = mn.mlp(img)
            loss = pt.layers.mean(pt.layers.cross_entropy(
                input=predict, label=lbl))
            pt.optimizer.Adam(1e-3).minimize(loss)
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        persist = {v.name: scope.get(v.name)
                   for v in main_p.persistable_vars()}
    rng = np.random.RandomState(0)
    feed = {"image": jnp.asarray(rng.rand(B, 784).astype("float32")),
            "label": jnp.asarray(rng.randint(0, 10, (B, 1)), jnp.int32)}
    key = jax.random.PRNGKey(0)
    step_fn = build_step_fn(main_p, [loss.name], False, None)
    jfn = jax.jit(step_fn, donate_argnums=(0,))
    fetches, persist = jfn(persist, feed, key)
    np.asarray(fetches[0])
    n = 200
    state = {"persist": persist}

    def window():
        p = state["persist"]
        for _ in range(n):
            fetches, p = jfn(p, feed, key)
        state["persist"] = p
        np.asarray(fetches[0])

    dt = _median_window_time(window, 3)
    return n / dt


def run_benchmarks(platform):
    """Run every stage (or the BENCH_ONLY subset) on the already-
    initialized backend; returns the result dict. A stage that raises
    propagates: a failed stage fails the run."""
    import jax
    result = {
        "metric": "transformer_base_train_tokens_per_sec",
        "value": 0.0,
        "unit": "tokens/sec",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }
    only = [s for s in os.environ.get("BENCH_ONLY", "").split(",") if s]
    stage_s = result.setdefault("stage_seconds", {})

    def _stage_peak():
        """Per-stage HBM watermark: the memory ledger's read-and-reset
        peak, None when PADDLE_TPU_MEMLEDGER is off (the off path
        never imports the ledger)."""
        from paddle_tpu import telemetry as _tm
        if not _tm.memledger_enabled():
            return None
        from paddle_tpu.telemetry import memledger as _ml
        return _ml.get().take_peak() or None

    def run_stage(names, fn):
        """`names`: accepted BENCH_ONLY selector tokens (first is the
        stage_seconds label); fn(platform) -> dict merged into the
        result."""
        if only and not any(n in only for n in names):
            return
        t0 = time.perf_counter()
        result.update(fn(platform) or {})
        stage_s[names[0]] = round(time.perf_counter() - t0, 1)
        pk = _stage_peak()
        if pk:
            result.setdefault("peak_hbm_bytes", {})[names[0]] = pk
        print(f"[bench] {names[0]}: {stage_s[names[0]]}s",
              file=sys.stderr, flush=True)

    def stage_transformer(platform):
        tokens_per_sec, mfu, loss, evidence = bench_transformer(platform)
        out = {"value": round(tokens_per_sec, 1),
               "loss": round(loss, 4), "evidence": evidence}
        if mfu is not None:
            out["mfu"] = round(mfu, 4)
        return out

    def stage_transformer_b256(platform):
        """Large-batch operating point (B=256): amortizes the
        non-matmul tail. Secondary record — the headline keeps the
        SURVEY B=64 config."""
        if platform != "tpu":
            return {}
        tps, mfu, loss, ev = bench_transformer(platform, batch=256,
                                               profile=False)
        return {"transformer_b256_tokens_per_sec": round(tps, 1),
                "transformer_b256_mfu": round(mfu, 4) if mfu else None,
                "transformer_b256_wall_step_ms": ev.get("wall_step_ms")}

    _stage_peak()              # drop any pre-bench watermark
    run_stage(("transformer",), stage_transformer)
    run_stage(("inference",), bench_inference)
    run_stage(("deepfm",), bench_deepfm)
    run_stage(("resnet", "resnet50"), lambda p: {
        "resnet50_images_per_sec": round(bench_resnet(p), 1)})
    run_stage(("mnist",), lambda p: {
        "mnist_mlp_steps_per_sec": round(bench_mnist(p), 1)})
    run_stage(("b256", "transformer_b256"), stage_transformer_b256)
    run_stage(("flash",), bench_flash_long_context)
    return result


_HISTORY_SCHEMA = "paddle_tpu.bench.history.v1"

# result key -> (unit, stage) for the perf-history spine: one compact
# record per completed bench stage lands in BENCH_history.jsonl, the
# rolling trajectory `tpustat --slo` regression-gates against
_HISTORY_METRICS = (
    ("value", "tokens/sec", "transformer"),
    ("mfu", "mfu", "transformer"),
    ("resnet50_infer_images_per_sec", "images/sec", "inference"),
    ("resnet50_infer_latency_ms", "ms", "inference"),
    ("deepfm_examples_per_sec", "examples/sec", "deepfm"),
    ("deepfm_step_ms", "ms", "deepfm"),
    ("resnet50_images_per_sec", "images/sec", "resnet"),
    ("mnist_mlp_steps_per_sec", "steps/sec", "mnist"),
    ("transformer_b256_tokens_per_sec", "tokens/sec", "b256"),
    ("transformer_b256_mfu", "mfu", "b256"),
    ("flash_attn_32k_causal_ms", "ms", "flash"),
)


def _git_sha():
    try:
        import subprocess
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha or None
    except Exception:
        return None


def _host_fingerprint():
    """Stable 12-hex id for the machine class a record was measured
    on. Same-fingerprint records are directly comparable; across
    fingerprints only the calibration ratio makes them commensurable."""
    import hashlib
    import platform as _pf
    probe = "|".join((_pf.system(), _pf.machine(),
                      _pf.processor() or "",
                      str(os.cpu_count() or 0)))
    return hashlib.sha1(probe.encode()).hexdigest()[:12]


_CALIB_MS = None


def _calibrate():
    """Fixed host-CPU calibration microbenchmark: best-of-5 wall time
    for 64 seeded 128x128 fp32 matmuls (~270 MFLOP per trial). The
    SAME work every run, every box, forever — so the ratio of two
    records' `calib_ms` is the relative speed of the boxes that
    produced them, and the history gate can normalize a spine that
    spans machines instead of flagging a slower box as a perf
    regression. Cached per process (one stamp per bench run)."""
    global _CALIB_MS
    if _CALIB_MS is None:
        import numpy as np
        rng = np.random.RandomState(0)
        a = rng.randn(128, 128).astype(np.float32)
        b = rng.randn(128, 128).astype(np.float32)
        for _ in range(8):
            (a @ b)                      # warm the BLAS path
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(64):
                (a @ b)
            best = min(best, time.perf_counter() - t0)
        _CALIB_MS = round(best * 1e3, 4)
    return _CALIB_MS


def _history_records(result, now=None):
    """The schema'd per-stage records for one bench result. The
    headline 'value' is renamed to its real metric name; zero values
    from never-ran stages are skipped (a bootstrap artifact must not
    drag the rolling median to 0)."""
    now = now if now is not None else time.time()
    sha = _git_sha()
    common = {"schema": _HISTORY_SCHEMA,
              "platform": result.get("platform"),
              "device_kind": result.get("device_kind"),
              "git_sha": sha, "unix_time": round(now, 1),
              # calibration spine: the fixed microbenchmark's wall
              # time plus the host class it ran on. history_gate
              # divides these out, so records from differently-sized
              # CI boxes gate against each other fairly
              "calib_ms": _calibrate(),
              "fingerprint": _host_fingerprint()}
    records = []
    for key, unit, stage in _HISTORY_METRICS:
        v = result.get(key)
        if not isinstance(v, (int, float)) or not v:
            continue
        if key == "value":
            # the headline metric describes itself; the table's
            # unit/stage are only the default (transformer) labels
            metric = result.get("metric", key)
            unit = result.get("unit", unit)
            stage = result.get("history_stage", stage)
        else:
            metric = key
        records.append(dict(common, metric=metric, value=v,
                            unit=unit, stage=stage))
    # per-stage HBM watermarks (memory-ledger runs only — the dict is
    # absent with PADDLE_TPU_MEMLEDGER off, so the spine is unchanged)
    for stage, pk in sorted((result.get("peak_hbm_bytes")
                             or {}).items()):
        if isinstance(pk, (int, float)) and pk:
            records.append(dict(common,
                                metric=f"{stage}_peak_hbm_bytes",
                                value=int(pk), unit="bytes",
                                stage=stage))
    return records


def _append_history(result, path=None):
    """Append this run's per-stage records to the history spine
    (BENCH_HISTORY_PATH overrides the default repo-root
    BENCH_history.jsonl). Best-effort: any failure returns None and
    never disturbs the bench artifacts or stdout contract."""
    try:
        records = _history_records(result)
        if not records:
            return None
        path = path or os.environ.get("BENCH_HISTORY_PATH") \
            or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_history.jsonl")
        with open(path, "a") as f:
            for rec in records:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
        return path
    except Exception:
        return None


def _write_telemetry_artifact(path=None):
    """BENCH_telemetry.json at the repo root: the full metric
    snapshot (+ span count) of the bench run when telemetry is on.
    Telemetry off (the default): returns None, writes NOTHING, and
    touches no stdout — the bench-contract final-line pins stay intact
    (tests/test_bench_contract.py)."""
    try:
        from paddle_tpu import telemetry
    except Exception:
        return None
    if not telemetry.enabled():
        return None
    snap = telemetry.snapshot()
    path = path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_telemetry.json")
    try:
        with open(path, "w") as f:
            json.dump({"schema": "paddle_tpu.bench.telemetry.v1",
                       "metrics": snap,
                       "spans": len(telemetry.iter_spans())},
                      f, indent=1, default=str)
    except OSError:
        return None
    return path


def _grad_sync_mode(steps=10, n_devices=8, mode="int8"):
    """`bench.py --grad-sync=MODE`: A/B the gradient-sync policy layer
    (parallel/gradsync.py) against fp32 sync on the data-parallel stage
    — the round-4 `--flash-bf16-softmax` pattern for ROADMAP item 2.
    Runs the MNIST-MLP DP stage over an 8-virtual-device CPU mesh (the
    policy layer is wire-format logic; trace-time byte accounting is
    identical on any backend), measures `collective.all_reduce.bytes`,
    the gradsync raw/wire counters, steps/sec, and final loss per
    policy, and prints ONE JSON line + the BENCH_gradsync.json
    artifact. The acceptance bar: int8 cuts all-reduce bytes >= 3.5x
    vs fp32."""
    import __graft_entry__ as graft
    restore = graft._force_cpu_mesh(n_devices)
    try:
        import jax
        import paddle_tpu as pt
        from paddle_tpu import layers, telemetry

        def build():
            img = layers.data("img", shape=[64])
            label = layers.data("label", shape=[1], dtype="int64")
            h = layers.fc(img, size=256, act="relu")
            h = layers.fc(h, size=128, act="relu")
            pred = layers.fc(h, size=10, act="softmax")
            loss = layers.mean(layers.cross_entropy(pred, label))
            pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
            return loss

        rng = np.random.RandomState(0)
        feed = {"img": rng.randn(64, 64).astype("float32"),
                "label": rng.randint(0, 10, (64, 1)).astype("int64")}
        policies = ["fp32"] + ([mode] if mode != "fp32" else [])
        per_policy = {}
        was_on = telemetry.enabled()
        for pol in policies:
            main_p, startup_p = pt.Program(), pt.Program()
            with pt.program_guard(main_p, startup_p):
                with pt.unique_name.guard():
                    loss = build()
            main_p.random_seed = startup_p.random_seed = 7
            scope = pt.Scope()
            telemetry.enable()
            telemetry.reset()
            try:
                with pt.scope_guard(scope):
                    exe = pt.Executor(pt.CPUPlace())
                    exe.run(startup_p)
                    pexe = pt.ParallelExecutor(
                        loss_name=loss.name, main_program=main_p,
                        scope=scope, grad_sync=pol)
                    last = float(np.asarray(pexe.run(
                        feed=feed, fetch_list=[loss])[0]))  # compile
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        last = float(np.asarray(pexe.run(
                            feed=feed, fetch_list=[loss])[0]))
                    dt = time.perf_counter() - t0
                snap = telemetry.snapshot()
            finally:
                telemetry.reset()
                if not was_on:
                    telemetry.disable()
            per_policy[pol] = {
                "all_reduce_bytes": snap.get(
                    "collective.all_reduce.bytes", 0),
                "all_reduce_count": snap.get(
                    "collective.all_reduce.count", 0),
                "gradsync_raw_bytes": snap.get("gradsync.raw_bytes", 0),
                "gradsync_wire_bytes": snap.get("gradsync.wire_bytes",
                                                0),
                "gradsync_buckets": snap.get("gradsync.buckets", 0),
                "steps_per_sec": round(steps / dt, 1),
                "final_loss": round(last, 5),
            }
        a, b = per_policy["fp32"], per_policy[policies[-1]]
        ratio = (a["all_reduce_bytes"] / b["all_reduce_bytes"]
                 if b["all_reduce_bytes"] else None)
        result = {
            "metric": "grad_sync_all_reduce_bytes_ratio",
            "value": round(ratio, 3) if ratio else 0.0,
            "unit": "x (fp32 bytes / policy bytes)",
            "platform": "cpu",
            "grad_sync_mode": mode,
            "n_devices": n_devices,
            "steps": steps,
            "per_policy": per_policy,
            "loss_abs_delta": round(
                abs(a["final_loss"] - b["final_loss"]), 5),
            "pass_3p5x": bool(ratio and ratio >= 3.5),
        }
        try:
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_gradsync.json")
            with open(path, "w") as f:
                json.dump({"schema": "paddle_tpu.bench.gradsync.v1",
                           **result}, f, indent=1)
        except OSError:
            pass
        _emit(result)
        return 0 if mode == "fp32" or result["pass_3p5x"] else 1
    finally:
        restore()


def _sparse_mode(vocab_rows=100_000_000, steps=8, n_devices=8):
    """`bench.py --sparse[=VOCAB_ROWS]`: DeepFM through the sharded
    embedding engine (parallel/sparse.py, ROADMAP item 5) on an
    8-virtual-device CPU mesh. The tables are never materialized on
    one device: startup init is stripped and each mesh member seeds
    only its vocab/N rows (engine.init_shards), so vocab_rows=1e8
    (the default — the pserver-era scale) holds ~400 MB of table per
    member instead of 3.2 GB anywhere. Ids follow a hot-set mixture
    (30% of positions from 1k hot ids — CTR-style popularity skew) so
    the unique-ids dedup has a measurable ratio. SGD keeps the 1e8
    footprint at 1x table (lazy-Adam moments would 3x it; the engine
    supports both). Prints ONE JSON line + BENCH_sparse.json with
    examples/s, the dedup ratio, and the per-step exchange bytes."""
    import __graft_entry__ as graft
    restore = graft._force_cpu_mesh(n_devices)
    try:
        import jax
        import paddle_tpu as pt
        from paddle_tpu import telemetry
        from paddle_tpu.models import deepfm
        from paddle_tpu.parallel import sparse as tpusparse

        B, F, D = 512, 26, 8
        main_p, startup = pt.Program(), pt.Program()
        with pt.program_guard(main_p, startup):
            with pt.unique_name.guard():
                feeds, loss, prob = deepfm.build_program(
                    num_fields=F, vocab_size=vocab_rows, embed_dim=D,
                    is_distributed=True)
                pt.optimizer.SGD(0.1).minimize(loss)
        main_p.random_seed = startup.random_seed = 1
        tables = tpusparse.discover_tables(main_p)
        tpusparse.strip_table_init(startup, tables)
        rng = np.random.RandomState(0)
        hot = rng.randint(0, vocab_rows, 1000)
        flat = np.where(rng.rand(B * F) < 0.3,
                        hot[rng.randint(0, 1000, B * F)],
                        rng.randint(0, vocab_rows, B * F))
        feed = {"feat_ids": flat.reshape(B, F, 1).astype("int64"),
                "feat_vals": rng.rand(B, F).astype("float32"),
                "label": rng.randint(0, 2, (B, 1)).astype("float32")}
        was_on = telemetry.enabled()
        telemetry.enable()
        telemetry.reset()
        scope = pt.Scope()
        try:
            with pt.scope_guard(scope):
                exe = pt.Executor(pt.CPUPlace())
                exe.run(startup)
                pexe = pt.ParallelExecutor(
                    loss_name=loss.name, main_program=main_p,
                    scope=scope, sparse="shard")
                t0 = time.perf_counter()
                pexe.sparse_engine.init_shards(scope, seed=1)
                init_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                l0 = float(np.asarray(pexe.run(
                    feed=feed, fetch_list=[loss])[0]))  # compile
                compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _ in range(steps):
                    last = float(np.asarray(pexe.run(
                        feed=feed, fetch_list=[loss])[0]))
                dt = time.perf_counter() - t0
                eng = pexe.sparse_engine
                shard_rows = {
                    t: eng.tables[t].local_rows for t in tables}
                stats = {t: np.asarray(
                    scope.get(tpusparse.STATS_PREFIX + t))
                    for t in tables}
            snap = telemetry.snapshot()
        finally:
            telemetry.reset()
            if not was_on:
                telemetry.disable()
        uniq = {t: round(float(s[1] / max(s[0], 1)), 4)
                for t, s in stats.items()}
        exchange = {t: int(snap.get(f"embed.{t}.exchange_bytes", 0))
                    for t in tables}
        ratio = sum(uniq.values()) / max(len(uniq), 1)
        result = {
            "metric": "sparse_deepfm_examples_per_sec",
            "value": round(steps * B / dt, 1),
            "unit": "examples/sec",
            "platform": "cpu",
            "vocab_rows": vocab_rows,
            "n_devices": n_devices,
            "embed_dim": D,
            "batch": B,
            "fields": F,
            "step_ms": round(dt / steps * 1e3, 2),
            "init_shards_s": round(init_s, 1),
            "compile_s": round(compile_s, 1),
            "unique_ratio": uniq,
            "unique_ratio_mean": round(ratio, 4),
            # trace-time wire accounting: one traced step's all-to-all
            # payload per table (ids out + rows back, both directions)
            "exchange_bytes_per_step": exchange,
            "rows_per_shard": shard_rows,
            "loss_first": round(l0, 5),
            "loss_last": round(last, 5),
            "trains": bool(np.isfinite(last) and last < l0),
        }
        try:
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_sparse.json")
            with open(path, "w") as f:
                json.dump({"schema": "paddle_tpu.bench.sparse.v1",
                           **result}, f, indent=1)
        except OSError:
            pass
        _emit(result)
        return 0 if result["trains"] else 1
    finally:
        restore()


def _async_mode(k=4, steps=40):
    """`bench.py --async-steps=K`: A/B the asynchronous step pipeline
    (tpupipe, core/pipeline_exec.py) against the synchronous executor
    hot path — the round-4 `--flash-bf16-softmax` pattern. Two stages:

    - mlp_feedbound: a feed-transfer-bound MLP (32 MB of feed per step
      against a small matmul), the workload double_buffer existed for.
      The SYNC leg is the PR-9 path exactly (per-step feed re-put,
      donating, k=0); the PIPELINED leg is this PR's full feature set
      (identity feed cache + async_steps=K + donate_state=False so
      dispatch stays async on this jax's CPU backend). Acceptance:
      >= 20% step-time reduction with bit-identical per-step losses.
    - transformer: the flagship model under the same A/B (reported,
      no bar — its step is compute-bound, the honest null case).

    Caveat recorded in the artifact: this CI image has ONE host core,
    so the window cannot overlap host work with device compute here —
    the measured win is feed-put elimination + deferred readback; on
    multi-core hosts / real TPUs the same knob adds compute overlap
    (donation + async dispatch coexist on TPU backends).
    Prints ONE JSON line + the BENCH_pipeline.json artifact."""
    import __graft_entry__ as graft
    restore = graft._force_cpu_mesh(1)
    try:
        import jax
        # the pipeline needs real async dispatch on the CPU backend to
        # measure anything (TPU backends are always async)
        jax.config.update("jax_cpu_enable_async_dispatch", True)
        import paddle_tpu as pt
        from paddle_tpu import layers, telemetry

        def hist_sum(snap, name):
            v = snap.get(name)
            return float(v.get("sum", 0.0)) if isinstance(v, dict) \
                else 0.0

        def run_leg(build_fn, feed, n, *, async_k, cache,
                    donate, seed=3):
            main_p, startup_p = pt.Program(), pt.Program()
            with pt.program_guard(main_p, startup_p):
                with pt.unique_name.guard():
                    fetch_var = build_fn()
            main_p.random_seed = startup_p.random_seed = seed
            scope = pt.Scope()
            was_on = telemetry.enabled()
            telemetry.enable()
            telemetry.reset()
            try:
                with pt.scope_guard(scope):
                    exe = pt.Executor(pt.CPUPlace())
                    exe.feed_cache = cache
                    exe.donate_state = donate
                    exe.run(startup_p)
                    exe.run(main_p, feed=feed,
                            fetch_list=[fetch_var])      # compile
                    telemetry.reset()
                    t0 = time.perf_counter()
                    outs = [exe.run(main_p, feed=feed,
                                    fetch_list=[fetch_var],
                                    async_steps=async_k or None)
                            for _ in range(n)]
                    if async_k:
                        exe.drain()
                    wall = time.perf_counter() - t0
                    losses = [np.asarray(o[0]).tobytes() for o in outs]
                    final = float(np.frombuffer(losses[-1],
                                                np.float32)[0])
                snap = telemetry.snapshot()
            finally:
                telemetry.reset()
                if not was_on:
                    telemetry.disable()
            stall_s = hist_sum(snap, "executor.pending_wait_seconds") \
                + hist_sum(snap, "executor.fetch_readback_seconds")
            return {
                "step_ms": round(wall / n * 1e3, 2),
                "wall_s": round(wall, 3),
                "final_loss": final,
                "feed_put_reused": int(
                    snap.get("executor.feed_put.reused", 0)),
                # host time spent BLOCKED on device results; the
                # overlap fraction below is 1 - stall/wall
                "stall_s": round(stall_s, 4),
                "_losses": losses,
            }

        rng = np.random.RandomState(0)
        stages = {}

        # ---- stage 1: feed-bound MLP (the acceptance stage) ----
        B, D, H = 4096, 2048, 32
        xs = rng.rand(B, D).astype("float32")
        ys = rng.rand(B, 1).astype("float32")
        # frozen batch: the identity cache only reuses buffers that
        # CANNOT be mutated (or feed_cache="trust") — mark them
        # read-only, the documented fixed-batch idiom
        xs.flags.writeable = False
        ys.flags.writeable = False

        def build_mlp():
            x = layers.data("x", shape=[D])
            y = layers.data("y", shape=[1])
            h = layers.fc(x, size=H, act="relu")
            pred = layers.fc(h, size=1)
            loss = layers.mean(layers.square_error_cost(pred, y))
            pt.optimizer.SGD(0.1).minimize(loss)
            return loss

        feed = {"x": xs, "y": ys}
        sync = run_leg(build_mlp, feed, steps,
                       async_k=0, cache=False, donate=True)
        pipe = run_leg(build_mlp, feed, steps,
                       async_k=k, cache=True, donate=False)
        ident = sync.pop("_losses") == pipe.pop("_losses")
        red = 100.0 * (1.0 - pipe["step_ms"] / sync["step_ms"])
        stages["mlp_feedbound"] = {
            "batch": B, "dim": D, "hidden": H, "steps": steps,
            "feed_mb": round((xs.nbytes + ys.nbytes) / 2**20, 1),
            "sync": sync, "pipelined": pipe,
            "step_time_reduction_pct": round(red, 1),
            "overlap_fraction": round(
                1.0 - pipe["stall_s"] / max(pipe["wall_s"], 1e-9), 4),
            "bit_identical_losses": ident,
        }

        # ---- stage 2: transformer (reported; compute-bound) ----
        from paddle_tpu.models import transformer as tfm

        def build_tfm():
            cfg = tfm.TransformerConfig(
                src_vocab=512, trg_vocab=512, max_len=32,
                d_model=128, d_inner=256, n_head=4, n_layer=2,
                dropout=0.0)
            feeds, avg_cost, tok = tfm.build_program(cfg, maxlen=32)
            pt.optimizer.Adam(1e-3).minimize(avg_cost)
            return avg_cost

        tb, tt = 8, 32
        src = rng.randint(3, 512, (tb, tt)).astype("int32")
        trg = np.concatenate([np.zeros((tb, 1), "int32"),
                              (src[:, :-1] + 1) % 512], axis=1)
        tfm_feed = {"src": src,
                    "src_len": np.full(tb, tt, "int32"),
                    "trg": trg,
                    "trg_len": np.full(tb, tt, "int32"),
                    "label": ((src + 1) % 512).astype("int32")}
        for arr in tfm_feed.values():
            arr.flags.writeable = False
        t_steps = 10
        sync_t = run_leg(build_tfm, tfm_feed, t_steps,
                         async_k=0, cache=False, donate=True)
        pipe_t = run_leg(build_tfm, tfm_feed, t_steps,
                         async_k=k, cache=True, donate=False)
        ident_t = sync_t.pop("_losses") == pipe_t.pop("_losses")
        stages["transformer"] = {
            "batch": tb, "seq": tt, "steps": t_steps,
            "sync": sync_t, "pipelined": pipe_t,
            "step_time_reduction_pct": round(
                100.0 * (1.0 - pipe_t["step_ms"] / sync_t["step_ms"]),
                1),
            "bit_identical_losses": ident_t,
        }

        ok = bool(red >= 20.0
                  and stages["mlp_feedbound"]["bit_identical_losses"])
        result = {
            "metric": "pipeline_step_time_reduction_pct",
            "value": round(red, 1),
            "unit": "% (feed-bound stage, sync vs pipelined)",
            "platform": "cpu",
            "async_steps": k,
            "host_cpus": os.cpu_count(),
            "legs": {
                "sync": "PR-9 path: per-step feed re-put, donating, "
                        "k=0",
                "pipelined": "feed identity cache + async window "
                             f"k={k} + donate_state=False (CPU async "
                             "dispatch)"},
            "single_core_note": (
                "1 host core on this image: the window cannot overlap "
                "host work with device compute here, so the measured "
                "win is feed-put elimination + deferred readback; "
                "multi-core hosts / TPUs add compute overlap on top"
            ) if (os.cpu_count() or 1) <= 1 else None,
            "stages": stages,
            "pass_20pct": ok,
        }
        try:
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_pipeline.json")
            with open(path, "w") as f:
                json.dump({"schema": "paddle_tpu.bench.pipeline.v1",
                           **result}, f, indent=1)
        except OSError:
            pass
        _emit(result)
        return 0 if ok else 1
    finally:
        restore()


def main():
    for i, arg in enumerate(sys.argv[1:], start=1):
        if arg.startswith("--deepfm-vocab-rows"):
            _, eq, v = arg.partition("=")
            val = v if eq else (sys.argv[i + 1]
                                if len(sys.argv) > i + 1 else "")
            if val:
                os.environ["BENCH_DEEPFM_VOCAB_ROWS"] = val
    for i, arg in enumerate(sys.argv[1:], start=1):
        if arg.startswith("--grad-sync"):
            _, eq, v = arg.partition("=")
            mode = v if eq else (sys.argv[i + 1]
                                 if len(sys.argv) > i + 1 else "int8")
            sys.exit(_grad_sync_mode(mode=mode or "int8"))
        if arg.startswith("--sparse"):
            _, eq, v = arg.partition("=")
            vocab = int(float(v)) if eq and v else 100_000_000
            sys.exit(_sparse_mode(vocab_rows=vocab))
        if arg.startswith("--async-steps"):
            _, eq, v = arg.partition("=")
            depth = int(v) if eq and v else 4
            sys.exit(_async_mode(k=depth))
    import jax
    dev = jax.devices()[0]          # the one backend initialization
    if dev.platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        print(f"bench.py: no TPU — JAX found platform {dev.platform!r} "
              f"({dev.device_kind}). Nothing was measured. A CPU "
              "plumbing run must be asked for with JAX_PLATFORMS=cpu.",
              file=sys.stderr)
        sys.exit(1)
    result = run_benchmarks(dev.platform)
    # artifact writes happen BEFORE the final emit: the last stdout
    # line must stay the result line no matter what the writes do
    _append_history(result)
    _write_telemetry_artifact()
    _emit(result)


if __name__ == "__main__":
    main()
