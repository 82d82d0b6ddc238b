"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, through the entry points a user calls, at
the full width of transformer-base (depth and weights unchanged, the
weights random from a seed): train steps through `fluid.Executor`,
every registered Pallas kernel against its jnp reference, a decode
server answering HTTP requests, and — when more than one chip is
visible — data-parallel steps through `ParallelExecutor`.

One process, JAX initialized once, no network, no child process. A
phase that fails raises, so the run exits non-zero and prints no
result; so does a run in which JAX finds no TPU. On success the last
line of stdout is one JSON object with exactly two keys,
`{"ok": true, "device": {"platform", "kind", "count"}}`, the device as
JAX reports it; the per-phase numbers are the `summary:` line above it.

Every time printed here is a SMOKE timing (one run, compile included
where it says so) — evidence that the path runs, never a benchmark
result. `"claim": null` at the end of the summary says so.

    python chip_smoke.py            # on a machine with a TPU
"""
import concurrent.futures
import json
import sys
import time
import urllib.request

import numpy as np

SEED = 20260926


class SmokeFailure(AssertionError):
    """A phase's pass condition did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def say(phase, msg):
    print(f"[chip_smoke] {phase}: {msg}", flush=True)


# ------------------------------------------------------------ plumbing
class CompileWatch:
    """Counts XLA compile requests and persistent-cache traffic from
    JAX's own monitoring events, so "nothing compiles after the first
    step" is observed, not inferred."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = self.cache_writes = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def _platforms(arr):
    return {d.platform for d in arr.devices()}


def train_feed(cfg, batch, seq):
    """One seeded synthetic NMT batch (the bench.py recipe)."""
    rng = np.random.RandomState(SEED)
    src = rng.randint(3, cfg.src_vocab, (batch, seq)).astype("int64")
    trg = np.concatenate([np.zeros((batch, 1), "int64"),
                          (src[:, :-1] + 1) % cfg.trg_vocab], axis=1)
    full = np.full((batch,), seq, "int64")
    return {"src": src, "src_len": full, "trg": trg, "trg_len": full,
            "label": (src + 1) % cfg.trg_vocab}


def build_train(cfg, seq):
    """bf16 transformer train program + Adam, as bench.py builds it."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            _feeds, avg_cost, _tok = tfm.build_program(cfg, maxlen=seq)
            fluid.optimizer.Adam(1e-3).minimize(avg_cost)
    main_p.random_seed = startup.random_seed = SEED
    fluid.amp.cast_program_to_bf16(main_p)
    return main_p, startup, avg_cost


def init_scope(main_p, startup, place):
    """Fresh scope: startup on `place`, params cast to the program's
    bf16 dtypes."""
    import paddle_tpu as fluid
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.amp.cast_params_to_bf16(main_p, scope)
    return scope, exe


# ---------------------------------------------------------------- train
def phase_train(cfg, batch, seq, steps, place, watch, kernels="compiled"):
    """`steps` Executor.run steps on one repeated batch. Returns
    (scope, main_program, info). `kernels`: "compiled" (chip) or
    "interpret" (CPU plumbing test) — how the fused LayerNorm must
    have been dispatched."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import layer_norm as ln

    main_p, startup, avg_cost = build_train(cfg, seq)
    scope, exe = init_scope(main_p, startup, place)
    feed = train_feed(cfg, batch, seq)
    dev = place.jax_device()
    ln_before = ln.STATS["pallas_calls"]
    c0, h0 = watch.compiles, watch.cache_hits

    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        first = float(exe.run(main_p, feed=feed,
                              fetch_list=[avg_cost])[0])
        first_s = time.perf_counter() - t0
        c1 = watch.compiles
        losses, step_s, read_s = [first], [], []
        for _ in range(steps - 1):
            t0 = time.perf_counter()
            out = exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                          return_numpy=False)
            jax.block_until_ready(out)
            t1 = time.perf_counter()
            losses.append(float(np.asarray(out[0])))
            t2 = time.perf_counter()
            step_s.append(t1 - t0)
            read_s.append(t2 - t1)

    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    check(c1 - c0 >= 1, "first step did not compile")
    check(watch.compiles == c1,
          f"{watch.compiles - c1} compile(s) after the first step")
    wrong = [v.name for v in main_p.persistable_vars()
             if _platforms(scope.get(v.name)) != {dev.platform}]
    check(not wrong, f"persistables off {dev.platform}: {wrong[:5]}")
    ln_calls = ln.STATS["pallas_calls"] - ln_before
    check(ln_calls > 0, "fused LayerNorm was never dispatched")
    check(fa.active() == (True, kernels == "interpret"),
          f"Pallas mode is {fa.active()}, wanted {kernels}")
    # block_until_ready must be a completion barrier on this runtime:
    # once it returns, reading the scalar back has nothing to wait for
    step_ms = 1e3 * float(np.median(step_s))
    read_ms = 1e3 * float(np.median(read_s))
    check(read_ms < max(0.25 * step_ms, 2.0),
          f"readback after block_until_ready took {read_ms:.2f} ms of "
          f"a {step_ms:.2f} ms step: not a barrier")
    stats = dev.memory_stats() or {}
    info = {"first_step_s": round(first_s, 2),
            "steady_step_ms": round(step_ms, 2),
            "readback_after_barrier_ms": round(read_ms, 3),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "compiles_first_step": c1 - c0,
            "cache_hits": watch.cache_hits - h0,
            "layer_norm_pallas_calls": ln_calls,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    say("train", f"B={batch} T={seq} steps={steps} on {dev}: "
                 f"first step {info['first_step_s']} s (compile, "
                 f"{info['cache_hits']} cache hit(s)), steady step "
                 f"{info['steady_step_ms']} ms [smoke timing], readback "
                 f"after barrier {info['readback_after_barrier_ms']} ms, "
                 f"loss {info['loss_first']} -> {info['loss_last']}, "
                 f"fused LayerNorm x{ln_calls} ({kernels}), peak HBM "
                 f"{info['peak_bytes_in_use'] or 'not reported'} B")
    return scope, main_p, info


# -------------------------------------------------------------- kernels
def chip_kernel_cases():
    """Each registered kernel at a shape its own hardware gate accepts
    (base width where the kernel has one): name -> (args, kwargs,
    grad_argnums); `name@tag` is one more shape of kernel `name`."""
    import jax.numpy as jnp
    rng = np.random.RandomState(SEED)

    def f32(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    S, H, Dh = 32, 8, 64
    # 8 query heads over 2 key-value heads, as the cell lfm2_train_1chip
    # groups them: the backward is the one kernel with dq resident
    flash = tuple(f32(1, heads, 4096, 64).astype(jnp.bfloat16)
                  for heads in (8, 2, 2))

    # the cell solar_train_1chip's: head 128, 8 query heads over ONE
    # key-value head at 8192, whose dq fills the one backward kernel's
    # resident buffer to the byte
    flash128 = tuple(f32(1, heads, 8192, 128).astype(jnp.bfloat16)
                     for heads in (8, 1, 1))

    def unit(*shape):
        x = f32(*shape)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(
            jnp.bfloat16)

    # that cell's scan: bf16 q, k, v, a float32 log-decay down to -1.6 a
    # step (-100 over a chunk), steps in (0, 2); float32 state inside
    kda = (unit(1, 8192, 8, 128), unit(1, 8192, 8, 128),
           f32(1, 8192, 8, 128).astype(jnp.bfloat16),
           jnp.asarray(-rng.uniform(0.001, 1.6, (1, 8192, 8, 128)),
                       jnp.float32),
           jnp.asarray(rng.uniform(0.0, 2.0, (1, 8192, 8)), jnp.float32))

    # the cell jamba2_train_1chip's selective scan: a bf16 x, a float32
    # step from the initialisation's [0.001, 0.1] (and up to 1), A_log =
    # log(1 .. 16), bf16 B and C, over 1280 channels of 8192 tokens
    ssm = (f32(1, 8192, 1280).astype(jnp.bfloat16),
           jnp.asarray(np.exp(rng.uniform(np.log(1e-3), 0.0,
                                          (1, 8192, 1280))), jnp.float32),
           jnp.asarray(np.log(np.broadcast_to(np.arange(1, 17), (1280, 16))),
                       jnp.float32),
           f32(1, 8192, 16).astype(jnp.bfloat16),
           f32(1, 8192, 16).astype(jnp.bfloat16),
           jnp.ones((1280,), jnp.float32))

    def int8(*shape):
        return jnp.asarray(rng.randint(-127, 128, size=shape), jnp.int8)

    def scales(*shape):
        return jnp.asarray(rng.uniform(0.005, 0.02, size=shape),
                           jnp.float32)

    def pos(T):
        return jnp.asarray(rng.randint(0, T, size=(S,)), jnp.int32)

    return {
        "layer_norm": ((f32(64, 256, 512).astype(jnp.bfloat16),
                        1.0 + 0.1 * f32(512), 0.1 * f32(512), 1e-5, 2),
                       {}, (0, 1, 2)),
        "flash_attention": (flash, {"causal": True}, (0, 1, 2)),
        "flash_attention@head128": (flash128, {"causal": True}, (0, 1, 2)),
        # one key-value head of the cell mellum2_train_1chip's sliding
        # layers (it has four such rows of the grid; the composition's
        # float32 scores of all 32 query heads would not fit beside their
        # gradient): a window of 1024, the band's kernels
        "flash_attention@window": (flash128,
                                   {"causal": True, "window": 1024},
                                   (0, 1, 2)),
        "kda_attention": (kda, {}, (0, 1, 2, 3, 4)),
        "selective_scan": (ssm, {}, (0, 1, 2, 3, 4, 5)),
        "lookup_pool": ((f32(512, 128),
                         jnp.asarray(rng.randint(-1, 512, size=(256, 8)),
                                     jnp.int32)),
                        {"pool": "mean"}, ()),
        "decode_attend": ((f32(S, H, Dh), f32(S, 1024, H, Dh),
                           f32(S, 1024, H, Dh), pos(1024)), {}, ()),
        "dequant_attend_int8": ((f32(S, H, Dh), int8(S, 256, H, Dh),
                                 scales(S, 256, H, 1),
                                 int8(S, 256, H, Dh),
                                 scales(S, 256, H, 1), pos(256)),
                                {}, ()),
        "int8_quant": ((f32(1024 * 256).at[:256].set(0.0),),
                       {"block_size": 256}, ()),
        # 2048 tokens, top-2 of 16 experts of which 4-7 are held here
        "moe_expert_ffn": ((f32(2048, 256).astype(jnp.bfloat16),
                            jnp.asarray(rng.randint(0, 16, size=(2048, 2)),
                                        jnp.int32),
                            jnp.asarray(rng.uniform(0.2, 0.8, (2048, 2)),
                                        jnp.float32),
                            (0.05 * f32(4, 256, 384)).astype(jnp.bfloat16),
                            (0.05 * f32(4, 256, 384)).astype(jnp.bfloat16),
                            (0.05 * f32(4, 384, 256)).astype(jnp.bfloat16)),
                           {"first_expert": 4}, ()),
    }


def example_kernel_cases():
    """The registry's own small examples (interpret-runnable)."""
    from paddle_tpu.ops import kern
    rng = np.random.RandomState(0)
    grads = {"layer_norm": (0, 1, 2), "flash_attention": (0, 1, 2),
             "kda_attention": (0, 1, 2, 3, 4),
             "selective_scan": (0, 1, 2, 3, 4, 5)}
    return {s.name: s.example(rng) + (grads.get(s.name, ()),)
            for s in kern.specs()}


def _grad_parity(spec, args, kwargs, argnums):
    """d(sum of the first output)/d(args[argnums]) through the kernel's
    custom_vjp against the same through the jnp reference, at ten
    times spec.tol (the margin tests/test_flash_attention.py and
    test_pallas_layer_norm.py give a backward) relative to each
    gradient's magnitude."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kern.registry import compare_leaves

    def head(fn):
        def f(*a):
            out = fn(*a, **kwargs)
            out = out[0] if isinstance(out, (tuple, list)) else out
            return jnp.sum(out.astype(jnp.float32))
        return jax.grad(f, argnums=argnums)

    got = head(spec.fn)(*args)
    with jax.default_matmul_precision("highest"):    # as parity_check
        ref = head(spec.reference)(*args)
    return compare_leaves(got, ref, tuple(10 * t for t in spec.tol),
                          scale_atol=True)


def phase_kernels(cases, watch):
    """registry.parity_check for every registered kernel (and the
    backward of the two that train). `ok is None` — the kernel's own
    gate turned the shape away — is a failure here."""
    from paddle_tpu.ops import kern
    from paddle_tpu.ops.pallas import flash_attention as fa

    check(sorted({c.split("@")[0] for c in cases}) == kern.names(),
          f"cases {sorted(cases)} != registered {kern.names()}")
    _use, interpret = fa.active()
    info = {}
    for name in sorted(cases):
        args, kwargs, argnums = cases[name]
        kernel = name.split("@")[0]
        c0 = watch.compiles
        t0 = time.perf_counter()
        ok, detail = kern.parity_check(kernel, args, kwargs)
        check(ok is True, f"{name}: parity {ok}: {detail}")
        line = f"fwd ok ({detail})"
        if argnums:
            ok, detail = _grad_parity(kern.get(kernel), args, kwargs,
                                      argnums)
            check(ok is True, f"{name}: grad parity: {detail}")
            line += f"; grad ok ({detail})"
        info[name] = {"seconds": round(time.perf_counter() - t0, 2),
                      "compiles": watch.compiles - c0}
        shapes = [tuple(getattr(a, "shape", ())) for a in args]
        say("kernels", f"{name} {shapes} "
                       f"{'interpret' if interpret else 'compiled'}"
                       f": {line} in {info[name]['seconds']} s")
    return info


# ---------------------------------------------------------------- serve
def phase_serve(cfg, scope, bf16, place, watch, num_slots, max_len,
                requests, logits_tol):
    """The trained scope's parameters behind DecodeEngine +
    ContinuousScheduler + ModelServer + the HTTP front end, in this
    process. `requests`: [(prompt_len, max_new_tokens)]; the first one
    is sent twice. Returns info."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving import HttpFrontend, ModelServer
    from paddle_tpu.serving.decode import (ContinuousScheduler,
                                           DecodeEngine,
                                           DecodeEngineConfig)
    platform = place.platform
    V = cfg.trg_vocab
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(3, cfg.src_vocab, (n,)).astype("int64")
               for n, _ in requests]

    engine = DecodeEngine.from_scope(
        scope, cfg, config=DecodeEngineConfig(
            num_slots=num_slots, max_len=max_len, src_max_len=max_len))

    # --- first-step logits: a decoder's prefill+step against the
    # traced inference program's forward at position 0
    rows = min(8, num_slots)
    dec = tfm.IncrementalDecoder(
        cfg, engine.decoder.params, num_slots=rows, max_len=max_len,
        src_max_len=max_len, return_logits=True)
    src = np.zeros((rows, max_len), "int64")
    src_len = np.ones((rows,), "int64")
    for j, p in enumerate(prompts[:rows]):
        src[j, :len(p)] = p
        src_len[j] = len(p)
    state = dec.write_slots(dec.init_state(), dec.prefill(src, src_len),
                            list(range(rows)))
    dec.step(state, np.zeros(rows, "int64"), np.zeros(rows, "int64"))
    got = np.asarray(dec.last_logits, np.float32)

    infer, _start = fluid.Program(), fluid.Program()
    with fluid.program_guard(infer, _start):
        with fluid.unique_name.guard():
            _names, logits_var = tfm.build_infer_program(
                cfg, maxlen=max_len)
    if bf16:
        fluid.amp.cast_program_to_bf16(infer)
    exe = fluid.Executor(place)
    ref = exe.run(infer, scope=scope, is_test=True,
                  feed={"src": src, "src_len": src_len,
                        "trg": np.zeros((rows, max_len), "int64"),
                        "trg_len": np.ones((rows,), "int64")},
                  fetch_list=[logits_var])[0]
    ref = np.asarray(ref, np.float32)[:, 0, :]
    check(got.shape == ref.shape == (rows, V),
          f"logits shapes {got.shape} vs {ref.shape}")
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          "non-finite logits")
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    check(err <= logits_tol * max(1.0, scale),
          f"first-step logits differ by {err:.4g} (logit scale "
          f"{scale:.3g}, tolerance {logits_tol} x scale)")

    # --- the server
    t0 = time.perf_counter()
    sched = ContinuousScheduler(engine, name="nmt")       # warms up
    warm_s = time.perf_counter() - t0
    want_compiles = len(engine.config.prefill_buckets) + 1
    check(engine.compile_count == want_compiles,
          f"engine built {engine.compile_count} executables, wanted "
          f"{want_compiles}")
    server = ModelServer()
    server.attach_decoder("nmt", sched)
    http = HttpFrontend(server, host="127.0.0.1", port=0).start()

    def ask(i):
        body = json.dumps({
            "inputs": {"src": prompts[i].tolist()},
            "max_new_tokens": requests[i][1]}).encode()
        req = urllib.request.Request(
            f"{http.url}/v1/models/nmt:predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            return json.loads(r.read())

    order = list(range(len(requests))) + [0]     # request 0 twice
    c0 = watch.compiles
    t0 = time.perf_counter()
    try:
        with concurrent.futures.ThreadPoolExecutor(len(order)) as pool:
            replies = list(pool.map(ask, order))
        traffic_s = time.perf_counter() - t0
        wrong = {k for k, v in sched.state.items()
                 if _platforms(v) != {platform}}
        check(not wrong, f"slot state off {platform}: {sorted(wrong)}")
    finally:
        http.stop()
        server.shutdown(drain=False, timeout=10.0)
    n_tokens = 0
    for i, rep in zip(order, replies):
        toks = rep["outputs"][0]
        budget = requests[i][1]
        check(0 < len(toks) <= budget,
              f"request {i}: {len(toks)} tokens for a budget of {budget}")
        check(all(isinstance(t, int) and 0 <= t < V for t in toks),
              f"request {i}: token outside the vocabulary: {toks}")
        n_tokens += len(toks)
    check(replies[0]["outputs"] == replies[-1]["outputs"],
          "the same prompt twice gave different tokens")
    check(engine.compile_count == want_compiles,
          f"engine compiled under traffic: {engine.compile_count} "
          f"executables, wanted {want_compiles}")
    info = {"warmup_s": round(warm_s, 2),
            "traffic_s": round(traffic_s, 2),
            "requests": len(order), "tokens": n_tokens,
            "executables": engine.compile_count,
            "jax_compiles_under_traffic": watch.compiles - c0,
            "logits_max_abs_err": round(err, 5),
            "logits_scale": round(scale, 4)}
    say("serve", f"slots={num_slots} max_len={max_len} "
                 f"{'bf16-cast' if bf16 else 'fp32'} weights from the "
                 f"train scope: warmup {info['warmup_s']} s "
                 f"({engine.compile_count} executables = "
                 f"{len(engine.config.prefill_buckets)} prefill buckets "
                 f"+ 1 step), {len(order)} HTTP requests -> {n_tokens} "
                 f"tokens in {info['traffic_s']} s [smoke timing], "
                 f"{info['jax_compiles_under_traffic']} small XLA "
                 f"compile(s) under traffic (eager slot writes), "
                 f"first-step logits vs traced program: max |diff| "
                 f"{err:.4g} at logit scale {scale:.3g} (tolerance "
                 f"{logits_tol} x scale), slot state on {platform}")
    return info


# ------------------------------------------------------------------- dp
def phase_dp(cfg, batch, seq, steps, first_loss_one_chip, loss_rtol,
             watch):
    """The same train program through ParallelExecutor over every
    local device."""
    import jax
    import paddle_tpu as fluid

    devices = jax.local_devices()
    n = len(devices)
    main_p, startup, avg_cost = build_train(cfg, seq)
    scope, _exe = init_scope(main_p, startup, None)
    pexe = fluid.ParallelExecutor(loss_name=avg_cost.name,
                                  main_program=main_p, scope=scope)
    check((pexe.platform, pexe.device_count)
          == (devices[0].platform, n),
          f"mesh is {pexe.device_count} x {pexe.platform}, process has "
          f"{n} x {devices[0].platform}")
    feed = train_feed(cfg, batch, seq)
    t0 = time.perf_counter()
    losses = [float(pexe.run(fetch_list=[avg_cost], feed=feed)[0])]
    first_s = time.perf_counter() - t0
    c1 = watch.compiles
    for _ in range(steps - 1):
        losses.append(float(pexe.run(fetch_list=[avg_cost],
                                     feed=feed)[0]))
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(watch.compiles == c1,
          f"{watch.compiles - c1} compile(s) after the first dp step")
    check(abs(losses[0] - first_loss_one_chip)
          <= loss_rtol * abs(first_loss_one_chip),
          f"dp step-0 loss {losses[0]} vs one-device "
          f"{first_loss_one_chip} (rtol {loss_rtol})")
    for k, arr in pexe.last_feeds.items():
        shard = arr.sharding.shard_shape(arr.shape)
        check(len(arr.sharding.device_set) == n
              and shard[0] * n == arr.shape[0] == batch,
              f"feed {k!r} {arr.shape} is not split {n} ways over dp: "
              f"shard {shard} on {len(arr.sharding.device_set)} devices")
    for v in main_p.persistable_vars():
        arr = scope.get(v.name)
        check({s.device for s in arr.addressable_shards} == set(devices),
              f"{v.name} is not addressable on every device")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices]
    if devices[0].platform == "tpu":
        check(all(b and b > 0 for b in in_use),
              f"bytes_in_use per chip: {in_use}")
    info = {"devices": n, "first_step_s": round(first_s, 2),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "bytes_in_use": in_use}
    say("dp", f"{n} x {devices[0].device_kind}: first step "
              f"{info['first_step_s']} s (compile), loss "
              f"{info['loss_first']} -> {info['loss_last']} (one-device "
              f"step 0: {round(first_loss_one_chip, 4)}, rtol "
              f"{loss_rtol}), every feed split {n} x {batch // n} rows, "
              f"params on all {n}, bytes_in_use {in_use}")
    return info


# ----------------------------------------------------------------- main
def result_line(dev, count):
    """The last line of stdout: exactly these keys, the device as JAX
    reports it. The smoke timings go on the summary line above it."""
    return json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": count}})


def main():
    import jax
    dev = jax.devices()[0]              # the one backend initialization
    count = len(jax.devices())
    say("device", f"jax {jax.__version__} platform={dev.platform} "
                  f"device_kind={dev.device_kind!r} count={count}")
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found platform {dev.platform!r}, "
              "not a TPU. Nothing was run.", file=sys.stderr)
        return 2

    import paddle_tpu as fluid
    from paddle_tpu import native
    from paddle_tpu.models.transformer import TransformerConfig
    from paddle_tpu.telemetry.attribution import peak_flops
    if peak_flops(dev) is None:
        print(f"chip_smoke.py: device_kind {dev.device_kind!r} is not "
              "in the peak table (paddle_tpu/telemetry/attribution.py)."
              " Nothing was run.", file=sys.stderr)
        return 2
    say("device", "compile cache at "
                  f"{jax.config.jax_compilation_cache_dir}; native "
                  "library " + ("built from the tracked sources"
                                if native.lib() is not None
                                else "unavailable (python fallbacks)"))

    cfg = TransformerConfig.base()
    cfg.fused_qkv = True                # as bench.py sets it
    place = fluid.TPUPlace(0)
    watch = CompileWatch()
    t_start = time.perf_counter()
    phases = {}
    scope, _main_p, phases["train"] = phase_train(
        cfg, batch=64, seq=cfg.max_len, steps=8, place=place,
        watch=watch)
    phases["kernels"] = phase_kernels(chip_kernel_cases(), watch)
    phases["serve"] = phase_serve(
        cfg, scope, bf16=True, place=place, watch=watch, num_slots=32,
        max_len=cfg.max_len,
        requests=[(5, 12), (17, 8), (33, 24), (64, 4), (100, 16),
                  (150, 32), (201, 6), (255, 48)],
        # bf16 activations through 12 layers on one side, fp32
        # residual stream on the other: 2^-8 per rounding, a few
        # dozen roundings deep
        logits_tol=0.05)
    if count > 1:
        phases["dp"] = phase_dp(
            cfg, batch=64, seq=cfg.max_len, steps=5,
            first_loss_one_chip=phases["train"]["loss_first"],
            # dropout masks differ (rbg streams are not partition-
            # invariant) and the fused LayerNorm is off under GSPMD
            loss_rtol=0.02, watch=watch)
    else:
        say("dp", "not run (one device visible)")
    total_s = round(time.perf_counter() - t_start, 1)
    say("done", f"all phases passed in {total_s} s: "
                f"{watch.compiles} compile requests, "
                f"{watch.cache_hits} persistent-cache hits, "
                f"{watch.cache_writes} cache writes")
    watch.close()
    say("summary", json.dumps({
        "jax": jax.__version__,
        "seconds": total_s,
        "compile_requests": watch.compiles,
        "cache_hits": watch.cache_hits,
        "phases": phases,
        "claim": None}))
    print(result_line(dev, count), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
