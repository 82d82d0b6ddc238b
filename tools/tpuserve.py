#!/usr/bin/env python
"""tpuserve — serve a save_inference_model dir with dynamic batching.

The serving counterpart of tools/tpustat.py: loads a model directory
into `paddle_tpu.serving.ModelServer` (shape-bucketed dynamic batching,
admission control, warmup) and exposes the TF-Serving-shaped HTTP API:

  POST /v1/models/<name>:predict   {"inputs": {feed: tensor}, ...}
  GET  /healthz
  GET  /metrics                    (telemetry prometheus_text)

Modes:
  serve (default)  python tools/tpuserve.py MODEL_DIR --port 8500
  --bench          closed-loop load generator against the served model:
                   reports p50/p99 latency, throughput, compile count,
                   reject rate (one JSON line with --json)
  --selftest       CI gate in the tpustat --json style: builds an mnist
                   model, serves it, fires mixed-shape concurrent
                   requests over HTTP, and exits non-zero unless
                   compile_count <= bucket count, every response matches
                   unbatched InferenceEngine.run, and overload requests
                   are rejected within their deadline. Includes the
                   decode leg (below).
  --selftest-decode
                   just the tpudecode CI gate: continuous-batching
                   decode over a tiny transformer must be token-
                   identical to one-at-a-time greedy_decode under
                   staggered arrivals/mixed lengths, the executable
                   count must stay == prefill buckets + 1, and
                   overload must shed fast.
  --bench-decode   continuous-decode closed loop at ~10x overload vs
                   the PR 3 fixed-batch greedy_decode path on the SAME
                   model: goodput (useful tokens/s), p50/p99
                   time-to-first-token and per-token latency; writes
                   the BENCH_decode.json artifact.
  --selftest-farm  the tpufarm CI gate: a 2-replica group with
                   disaggregated prefill must be token-identical to
                   greedy_decode at the group compile pin, int8
                   block-quantized KV must match fp32 tokens within
                   the parity bound (max logit delta reported), one
                   replica crashed by chaos must not drop a single
                   request, and a rolling weight update must serve
                   both versions mid-update with zero drops.
  --bench-farm     replica-group serving across the farm axes (1 vs 2
                   replicas, fp32 vs int8 KV, pooled vs disaggregated
                   prefill): slots/device and goodput/device per
                   case; writes the BENCH_decode2.json artifact.
  --selftest-guard the tpuguard CI gate: hedged requests must cut p99
                   vs guard-off under replica_slow on 1 of 2 replicas
                   at greedy_decode token parity; a replica_flap'd
                   replica must be ejected, probed and re-admitted
                   with zero drops; request_poison must fail exactly
                   one request with the replica surviving probation;
                   brownout must shed only the lowest QoS class with
                   a Retry-After hint and recover, and the retry
                   budget must cap resubmissions with a typed error.
  --bench-guard    closed-loop p50/p99 with vs without hedging while
                   replica_slow throttles 1 of 2 replicas; writes
                   BENCH_guard.json and appends guard_* records to
                   the bench history spine (tpustat --slo).
  --selftest-scale the tpuscale CI gate: under a tpuchaos
                   traffic_spike the controller must ramp the group
                   1->N and back with zero dropped requests and ZERO
                   scale-up recompiles (shared build cache); an
                   overloaded guard must DEFER brownout while a free
                   device slice exists and shed exactly when the
                   planner reports the ceiling; an over-mem-cap grow
                   must be rejected by the meshlint pre-spawn gate.
                   Writes no file.
  --bench-scale    static 1-replica vs SLO-autoscaled group under
                   the same traffic_spike script: goodput, peak
                   replicas, extra compiles; writes the bench section
                   of BENCH_autoscale.json.

Examples:
  python tools/tpuserve.py /models/mnist --name mnist --port 8500
  python tools/tpuserve.py /models/mnist --bench --duration 5 --json
  python tools/tpuserve.py --selftest --json
  python tools/tpuserve.py --selftest-decode --json
  python tools/tpuserve.py --bench-decode --duration 5 --json
  python tools/tpuserve.py --selftest-farm --json
  python tools/tpuserve.py --bench-farm --duration 5 --json
  python tools/tpuserve.py --selftest-guard --json
  python tools/tpuserve.py --bench-guard --duration 5 --json
  python tools/tpuserve.py --selftest-scale --json
  python tools/tpuserve.py --bench-scale --json
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _post_json(url, payload, timeout=30.0):
    """(status_code, decoded_body) — errors returned, not raised."""
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            body = json.loads(e.read())
        except Exception:
            body = {"error": str(e)}
        return e.code, body


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _build_server(args, dirname, name):
    from paddle_tpu.serving import (BatchConfig, HttpFrontend,
                                    ModelServer, ServerConfig)
    buckets = tuple(int(b) for b in args.buckets.split(",")) \
        if args.buckets else None
    cfg = ServerConfig(
        batch=BatchConfig(max_batch_size=args.max_batch_size,
                          max_wait_ms=args.max_wait_ms,
                          buckets=buckets,
                          max_queue_requests=args.max_queue),
        workers=args.workers,
        default_deadline_ms=args.deadline_ms)
    server = ModelServer(cfg)
    server.load(name, dirname)
    frontend = HttpFrontend(server, host=args.host, port=args.port)
    return server, frontend


def _mixed_feeds(engine, count, max_rows, seed=0):
    """`count` random feeds with batch sizes cycling over a mixed set
    (1..max_rows), dtypes/shapes from the engine's feed specs."""
    import numpy as np
    rng = np.random.RandomState(seed)
    sizes = [1, 2, 3, max(1, max_rows // 2), max_rows,
             max(1, max_rows - 1), max(1, max_rows // 4), 2]
    specs = engine.feed_specs()
    feeds = []
    for i in range(count):
        n = sizes[i % len(sizes)]
        feed = {}
        for fname, (shape, dt) in specs.items():
            full = (n,) + tuple(d if d != -1 else 1 for d in shape[1:])
            if np.dtype(dt).kind in "iu":
                feed[fname] = rng.randint(0, 10, full).astype(dt)
            else:
                feed[fname] = rng.rand(*full).astype(dt)
        feeds.append(feed)
    return feeds


# ----------------------------------------------------------------- bench
def run_bench(args):
    from paddle_tpu import telemetry
    telemetry.enable()
    name = args.name
    server, frontend = _build_server(args, args.model_dir, name)
    frontend.start()
    engine, _ = server.registry.get(name)
    warm_sigs = engine.signature_count()
    telemetry.reset()        # scope metrics to the measured loop

    feeds = _mixed_feeds(engine, 64, args.max_batch_size)
    url = f"{frontend.url}/v1/models/{name}:predict"
    stop_t = time.monotonic() + args.duration
    lock = threading.Lock()
    lat, rejects, errors, rows_done = [], [0], [0], [0]

    def worker(wid):
        i = wid
        while time.monotonic() < stop_t:
            feed = feeds[i % len(feeds)]
            i += args.concurrency
            payload = {"inputs": {k: v.tolist() for k, v in feed.items()}}
            if args.deadline_ms:
                payload["deadline_ms"] = args.deadline_ms
            t0 = time.perf_counter()
            status, body = _post_json(url, payload)
            dt = time.perf_counter() - t0
            rows = next(iter(feed.values())).shape[0]
            with lock:
                if status == 200:
                    lat.append(dt)
                    rows_done[0] += rows
                elif status in (429, 504):
                    rejects[0] += 1
                else:
                    errors[0] += 1

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(args.concurrency)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t_start

    frontend.stop()
    server.shutdown()
    lat.sort()
    snap = telemetry.snapshot()
    total = len(lat) + rejects[0] + errors[0]
    result = {
        "mode": "bench", "model": name,
        "duration_s": round(elapsed, 3),
        "concurrency": args.concurrency,
        "requests_ok": len(lat), "rejected": rejects[0],
        "errors": errors[0],
        "reject_rate": round(rejects[0] / total, 4) if total else 0.0,
        "throughput_rps": round(len(lat) / elapsed, 2),
        "throughput_rows_per_s": round(rows_done[0] / elapsed, 1),
        "latency_p50_ms": round(1e3 * _percentile(lat, 0.50), 3)
        if lat else None,
        "latency_p99_ms": round(1e3 * _percentile(lat, 0.99), 3)
        if lat else None,
        "compile_count_warmup": warm_sigs,
        "compile_count_steady": snap.get("inference.compile_count", 0),
        "signature_count": engine.signature_count(),
        "batches": snap.get("serving.batches", 0),
        "mean_rows_per_batch": round(
            rows_done[0] / snap["serving.batches"], 2)
        if snap.get("serving.batches") else None,
    }
    if args.as_json:
        print(json.dumps(result))
    else:
        for k, v in result.items():
            print(f"  {k:<24} {v}")
    return 1 if errors[0] else 0


# -------------------------------------------------------------- selftest
def _build_mnist_dir(tmpdir):
    """Train-free mnist MLP -> save_inference_model dir."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.models import mnist as zoo
    img = layers.data("pixel", shape=[784])
    predict = zoo.mlp(img)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    pt.io.save_inference_model(tmpdir, ["pixel"], [predict], exe)
    return tmpdir


class _StallEngine:
    """Duck-typed engine whose run() stalls — overload on demand."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def feed_specs(self):
        return {"pixel": ((-1, 4), "float32")}

    def signature_count(self):
        return 0

    def run(self, feed, return_numpy=True):
        import numpy as np
        time.sleep(self.delay_s)
        return [np.zeros((next(iter(feed.values())).shape[0], 1),
                         dtype="float32")]


def run_selftest(args):
    import numpy as np
    from paddle_tpu import telemetry
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.serving import (BatchConfig, DynamicBatcher,
                                    DeadlineExceeded, HttpFrontend,
                                    ModelServer, RejectedError,
                                    ServerConfig)

    telemetry.enable()
    problems = []
    buckets = (4, 16)

    with tempfile.TemporaryDirectory() as tmpdir:
        model_dir = _build_mnist_dir(tmpdir)
        cfg = ServerConfig(
            batch=BatchConfig(max_batch_size=16, max_wait_ms=2.0,
                              buckets=buckets, max_queue_requests=256),
            workers=3)
        server = ModelServer(cfg)
        server.load("mnist", model_dir)
        engine, _ = server.registry.get("mnist")
        warm_sigs = engine.signature_count()
        if warm_sigs != len(buckets):
            problems.append(
                f"warmup compiled {warm_sigs} signatures, expected "
                f"exactly {len(buckets)} (one per bucket)")

        # mixed-shape concurrent traffic over HTTP vs unbatched reference
        ref = InferenceEngine.from_dir(model_dir)
        feeds = _mixed_feeds(engine, 48, 16, seed=7)
        expected = [ref.run(f)[0] for f in feeds]
        frontend = HttpFrontend(server, port=0).start()
        url = f"{frontend.url}/v1/models/mnist:predict"
        statuses = [None] * len(feeds)
        outputs = [None] * len(feeds)

        def fire(i):
            statuses[i], body = _post_json(url, {
                "inputs": {k: v.tolist() for k, v in feeds[i].items()},
                "deadline_ms": 30000})
            if statuses[i] == 200:
                outputs[i] = np.asarray(body["outputs"][0],
                                        dtype="float32")

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(len(feeds))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        mismatches = 0
        for i, exp in enumerate(expected):
            if statuses[i] != 200:
                problems.append(f"request {i} failed: HTTP {statuses[i]}")
            elif not np.allclose(outputs[i], exp, rtol=1e-4, atol=1e-6):
                mismatches += 1
        if mismatches:
            problems.append(f"{mismatches} responses differ from "
                            f"unbatched InferenceEngine.run")
        sigs = engine.signature_count()
        if sigs > len(buckets):
            problems.append(
                f"compile_count {sigs} exceeds bucket count "
                f"{len(buckets)} — shape bucketing is not containing "
                f"signature explosion")

        # healthz + metrics surfaces
        with urllib.request.urlopen(frontend.url + "/healthz") as r:
            if json.loads(r.read()).get("status") != "ok":
                problems.append("healthz not ok while serving")
        with urllib.request.urlopen(frontend.url + "/metrics") as r:
            metrics_text = r.read().decode()
        for needle in ("serving_batches", "inference_signature_count"):
            if needle not in metrics_text:
                problems.append(f"/metrics missing {needle}")

        # overload over HTTP: one stalled worker, bounded queue, short
        # deadlines — rejections must come back fast, not queue forever
        slow = ModelServer(ServerConfig(
            batch=BatchConfig(max_batch_size=4, max_wait_ms=0.0,
                              buckets=(4,), max_queue_requests=2),
            workers=1, warmup=False))
        slow.register("slow", _StallEngine(0.3))
        sfront = HttpFrontend(slow, port=0).start()
        surl = f"{sfront.url}/v1/models/slow:predict"
        deadline_ms = 200.0
        reject_lat, ok_n, late = [], [0], [0]

        def flood(i):
            t0 = time.perf_counter()
            status, _body = _post_json(surl, {
                "inputs": {"pixel": [[0.0] * 4]},
                "deadline_ms": deadline_ms})
            dt = time.perf_counter() - t0
            if status == 200:
                ok_n[0] += 1
            else:
                reject_lat.append(dt)
                # client-observed: deadline + generous slack for 24
                # client threads contending on the GIL; the hard bound
                # on *server-side* queueing is the flood-duration check
                if dt > deadline_ms / 1e3 + 2.0:
                    late[0] += 1

        flooders = [threading.Thread(target=flood, args=(i,))
                    for i in range(24)]
        t_flood = time.monotonic()
        for t in flooders:
            t.start()
        for t in flooders:
            t.join()
        flood_s = time.monotonic() - t_flood
        if not reject_lat:
            problems.append("overload produced zero rejections "
                            "(queue grew unboundedly?)")
        if late[0]:
            problems.append(f"{late[0]} overload rejections took "
                            f"longer than deadline+2s")
        # had the 24 requests queued unboundedly behind the 0.3s/batch
        # stalled worker they would serialize to ~7s; load shedding
        # must finish the whole flood far sooner
        if flood_s > 5.0:
            problems.append(
                f"overload flood took {flood_s:.1f}s — requests piled "
                f"up behind the stalled worker instead of being shed")
        sfront.stop()
        slow.shutdown(drain=False, timeout=5.0)

        # admission control at the batcher level, deterministically:
        # no worker attached = a permanently stalled worker
        b = DynamicBatcher(BatchConfig(max_batch_size=4, buckets=(4,),
                                       max_queue_requests=2))
        f1 = b.submit({"x": np.zeros((1, 2))}, deadline_ms=100)
        b.submit({"x": np.zeros((1, 2))})
        t0 = time.perf_counter()
        try:
            b.submit({"x": np.zeros((1, 2))})
            problems.append("queue-full submit was admitted")
        except RejectedError:
            if time.perf_counter() - t0 > 0.1:
                problems.append("queue-full rejection was not fast")
        t0 = time.perf_counter()
        try:
            f1.result()
            problems.append("stalled request returned a result")
        except DeadlineExceeded:
            if time.perf_counter() - t0 > 1.0:
                problems.append("deadline enforcement took > 1s on a "
                                "stalled worker")

        snap = telemetry.snapshot()
        frontend.stop()
        server.shutdown()

    # decode leg: continuous batching must match one-at-a-time
    # greedy_decode exactly, with a pinned executable count
    decode_info = _decode_selftest_problems(problems)

    result = {
        "mode": "selftest",
        "decode": decode_info,
        "buckets": list(buckets),
        "warmup_signatures": warm_sigs,
        "signatures_after_traffic": sigs,
        "requests": len(feeds),
        "mismatches": mismatches,
        "overload": {"sent": 24, "ok": ok_n[0],
                     "rejected": len(reject_lat),
                     "duration_s": round(flood_s, 3),
                     "max_reject_latency_s":
                     round(max(reject_lat), 3) if reject_lat else None},
        "metrics": {k: v for k, v in sorted(snap.items())
                    if not isinstance(v, dict)},
        "problems": problems,
        "ok": not problems,
    }
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        print(f"tpuserve selftest: warmup {warm_sigs} sigs for "
              f"{len(buckets)} buckets; {len(feeds)} mixed-shape "
              f"requests, {mismatches} mismatches; overload "
              f"{len(reject_lat)}/24 rejected "
              f"(max {result['overload']['max_reject_latency_s']}s)")
        for prob in problems:
            print(f"FAIL: {prob}", file=sys.stderr)
    return 2 if problems else 0


# -------------------------------------------------------------- tpudecode
def _decode_stack(seed=7, maxlen=16, vocab=64, d_model=32, n_layer=2):
    """Tiny transformer for the decode selftest/bench: infer program +
    executor with SEEDED random parameters (drawn wide enough that
    argmax tokens vary across rows/steps — a fresh default init is
    degenerate) and the same params as a plain dict for the decode
    engine. Returns (cfg, exe, infer_program, logits_var, params)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        src_vocab=vocab, trg_vocab=vocab, max_len=maxlen,
        d_model=d_model, d_inner=2 * d_model, n_head=4,
        n_layer=n_layer, dropout=0.0, label_smooth_eps=0.0)
    infer, start = fw.Program(), fw.Program()
    with pt.program_guard(infer, start):
        with pt.unique_name.guard():
            _feeds, logits = tfm.build_infer_program(cfg, maxlen=maxlen)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(start)
    rng = np.random.RandomState(seed)
    scope = pt.global_scope()
    params = {}
    for v in infer.persistable_vars():
        a = np.asarray(scope.get(v.name))
        if v.name.startswith("layer_norm") and v.name.endswith(".w_0"):
            nv = 1.0 + 0.2 * rng.randn(*a.shape)
        elif v.name.endswith(".b_0"):
            nv = 0.1 * rng.randn(*a.shape)
        else:
            nv = 0.35 * rng.randn(*a.shape)
        nv = nv.astype(a.dtype)
        scope.set(v.name, nv)
        params[v.name] = nv
    return cfg, exe, infer, logits, params


def _decode_requests(rng, count, maxlen, vocab, max_new_cap):
    """Seeded mixed-length request set [(src, src_len, max_new)...]."""
    reqs = []
    for _ in range(count):
        n = int(rng.randint(3, maxlen + 1))
        src = rng.randint(2, vocab - 2, (n,)).astype("int64")
        max_new = int(rng.randint(3, max_new_cap + 1))
        reqs.append((src, n, max_new))
    return reqs


def _decode_selftest_problems(problems):
    """The tpudecode CI leg; appends failures to `problems`, returns
    an info dict for the report."""
    import numpy as np
    from paddle_tpu.models.transformer import greedy_decode
    from paddle_tpu.serving import RejectedError, DeadlineExceeded
    from paddle_tpu.serving.decode import (ContinuousScheduler,
                                           DecodeConfig, DecodeEngine,
                                           DecodeEngineConfig)

    maxlen, slots, buckets = 16, 4, (1, 2, 4)
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    engine = DecodeEngine(cfg, params, DecodeEngineConfig(
        num_slots=slots, max_len=maxlen, prefill_buckets=buckets))
    sched = ContinuousScheduler(engine, config=DecodeConfig(bos=0),
                                warmup=True)
    warm = engine.compile_count
    if warm != len(buckets) + 1:
        problems.append(
            f"decode warmup compiled {warm} executables, expected "
            f"{len(buckets)} prefill buckets + 1 step")

    # one-at-a-time greedy_decode reference (the legacy full-program
    # path, with the in-graph argmax fetch) for a mixed-length set
    rng = np.random.RandomState(11)
    reqs = _decode_requests(rng, 8, maxlen, cfg.trg_vocab,
                            engine.max_new_tokens)
    expected = []
    for src, n, max_new in reqs:
        row = np.zeros((1, maxlen), np.int64)
        row[0, :n] = src
        ids = greedy_decode(exe, infer, logits, row,
                            np.array([n], "int64"), bos=0,
                            fetch_argmax=True)
        expected.append(ids[0, 1:1 + max_new])

    # continuous, manually driven, STAGGERED arrivals: requests join
    # the running batch mid-flight, finished ones leave early
    futures = []
    arrivals = {0: [0, 1], 2: [2, 3, 4], 5: [5], 6: [6, 7]}
    it = 0
    while len(futures) < len(reqs) or not all(
            f.done() for f in futures):
        for i in arrivals.get(it, ()):
            src, n, max_new = reqs[i]
            futures.append(sched.submit(src, src_len=n,
                                        max_new_tokens=max_new))
        sched.run_iteration()
        it += 1
        if it > 600:
            problems.append("decode selftest did not converge in "
                            "600 iterations")
            break
    mismatches = 0
    for i, f in enumerate(futures):
        if not f.done():
            continue
        got = f.result(timeout=0).tokens
        if not np.array_equal(np.asarray(got, np.int64), expected[i]):
            mismatches += 1
    if mismatches:
        problems.append(
            f"{mismatches}/{len(reqs)} continuous-decode outputs "
            f"differ from one-at-a-time greedy_decode — iteration-"
            f"level batching changed the tokens")
    steady = engine.compile_count
    if steady != warm:
        problems.append(
            f"decode compiled {steady - warm} NEW executables under "
            f"traffic (compile count must stay prefill buckets + 1)")
    if sched.pool.free_count() != slots:
        problems.append("decode slots leaked after drain")

    # overload shed: no loop thread attached == permanently stalled
    # worker; the bounded queue + deadline must both fire fast
    shed = ContinuousScheduler(
        engine, config=DecodeConfig(max_queue_requests=2),
        warmup=False)
    f1 = shed.submit(np.arange(2, 6), deadline_ms=150)
    shed.submit(np.arange(2, 6))
    t0 = time.perf_counter()
    rejected_fast = deadline_fast = False
    try:
        shed.submit(np.arange(2, 6))
    except RejectedError:
        rejected_fast = time.perf_counter() - t0 < 0.1
    if not rejected_fast:
        problems.append("decode queue-full submit was not rejected "
                        "fast")
    t0 = time.perf_counter()
    try:
        f1.result()
        problems.append("stalled decode request returned a result")
    except DeadlineExceeded:
        deadline_fast = time.perf_counter() - t0 < 1.0
    if not deadline_fast:
        problems.append("decode deadline enforcement took > 1s on a "
                        "stalled scheduler")
    return {"warmup_executables": warm,
            "steady_executables": steady,
            "prefill_buckets": list(buckets),
            "requests": len(reqs),
            "mismatches": mismatches,
            "overload": {"rejected_fast": rejected_fast,
                         "deadline_fast": deadline_fast}}


def run_selftest_decode(args):
    from paddle_tpu import telemetry
    telemetry.enable()
    problems = []
    info = _decode_selftest_problems(problems)
    result = {"mode": "selftest-decode", **info,
              "problems": problems, "ok": not problems}
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        print(f"tpuserve selftest-decode: {info['warmup_executables']} "
              f"executables for {len(info['prefill_buckets'])} prefill "
              f"buckets + 1 step; {info['requests']} staggered "
              f"requests, {info['mismatches']} mismatches")
        for prob in problems:
            print(f"FAIL: {prob}", file=sys.stderr)
    return 2 if problems else 0


def run_bench_decode(args):
    """Continuous decode vs the PR 3 fixed-batch path, same model,
    ~10x overload. Writes BENCH_decode.json next to the repo root."""
    import numpy as np
    from paddle_tpu import telemetry
    from paddle_tpu.models.transformer import greedy_decode
    from paddle_tpu.serving import RejectedError
    from paddle_tpu.serving.decode import (ContinuousScheduler,
                                           DecodeConfig, DecodeEngine,
                                           DecodeEngineConfig)
    telemetry.enable()

    maxlen, slots = args.decode_max_len, args.slots
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    engine = DecodeEngine(cfg, params, DecodeEngineConfig(
        num_slots=slots, max_len=maxlen))
    sched = ContinuousScheduler(
        engine,
        config=DecodeConfig(max_queue_requests=4 * slots),
        warmup=True).start()

    rng = np.random.RandomState(23)
    reqs = _decode_requests(rng, 256, maxlen, cfg.trg_vocab,
                            engine.max_new_tokens)

    # ---- continuous tier: closed loop at ~10x the slot count --------
    stop_t = time.monotonic() + args.duration
    lock = threading.Lock()
    done_tokens, ttfts, per_tok, rejects = [0], [], [], [0]

    def client(wid):
        i = wid
        while time.monotonic() < stop_t:
            src, n, max_new = reqs[i % len(reqs)]
            i += 10 * slots
            try:
                r = sched.submit(src, src_len=n,
                                 max_new_tokens=max_new).result(
                    timeout=max(5.0, args.duration))
            except RejectedError:
                with lock:
                    rejects[0] += 1
                time.sleep(0.002)
                continue
            except TimeoutError:
                continue
            with lock:
                done_tokens[0] += len(r.tokens)
                if r.ttft_s is not None:
                    ttfts.append(r.ttft_s)
                if len(r.tokens) > 1:
                    per_tok.append(r.decode_s / len(r.tokens))

    clients = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(10 * slots)]
    t0 = time.monotonic()
    for t in clients:
        t.start()
    for t in clients:
        t.join()
    cont_s = time.monotonic() - t0
    sched.stop(drain=False, timeout=10.0)
    ttfts.sort()
    per_tok.sort()
    continuous = {
        "duration_s": round(cont_s, 3),
        "goodput_tokens_per_s": round(done_tokens[0] / cont_s, 1),
        "completed_tokens": done_tokens[0],
        "rejected": rejects[0],
        "ttft_p50_ms": round(1e3 * _percentile(ttfts, 0.5), 2)
        if ttfts else None,
        "ttft_p99_ms": round(1e3 * _percentile(ttfts, 0.99), 2)
        if ttfts else None,
        "per_token_p50_ms": round(1e3 * _percentile(per_tok, 0.5), 2)
        if per_tok else None,
        "per_token_p99_ms": round(1e3 * _percentile(per_tok, 0.99), 2)
        if per_tok else None,
        "executables": engine.compile_count,
        "slots": slots,
    }

    # ---- PR 3 fixed-batch path: greedy_decode in rigid batches ------
    # (one [slots, T] executable re-running the whole prefix per
    # token; early finishers ride the batch to the end)
    stop_t = time.monotonic() + args.duration
    t0 = time.monotonic()
    useful = batches = 0
    i = 0
    while time.monotonic() < stop_t:
        group = [reqs[(i + j) % len(reqs)] for j in range(slots)]
        i += slots
        src = np.zeros((slots, maxlen), np.int64)
        src_len = np.zeros((slots,), np.int64)
        for j, (s, n, _mn) in enumerate(group):
            src[j, :n] = s
            src_len[j] = n
        greedy_decode(exe, infer, logits, src, src_len, bos=0,
                      fetch_argmax=True)
        useful += sum(mn for _s, _n, mn in group)
        batches += 1
    fixed_s = time.monotonic() - t0
    fixed = {
        "duration_s": round(fixed_s, 3),
        "goodput_tokens_per_s": round(useful / fixed_s, 1),
        "completed_tokens": useful,
        "batches": batches,
        "batch_rows": slots,
    }

    ratio = None
    if fixed["goodput_tokens_per_s"]:
        ratio = round(continuous["goodput_tokens_per_s"]
                      / fixed["goodput_tokens_per_s"], 2)
    result = {"mode": "bench-decode", "model": "transformer-tiny",
              "maxlen": maxlen, "overload_clients": 10 * slots,
              "continuous": continuous, "fixed_batch": fixed,
              "goodput_ratio": ratio}
    out_path = os.path.join(_REPO, "BENCH_decode.json")
    try:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
    except OSError:
        pass
    if args.as_json:
        print(json.dumps(result))
    else:
        print(f"  continuous goodput  "
              f"{continuous['goodput_tokens_per_s']} tok/s "
              f"(ttft p50 {continuous['ttft_p50_ms']} ms)")
        print(f"  fixed-batch goodput {fixed['goodput_tokens_per_s']} "
              f"tok/s")
        print(f"  ratio               {ratio}x")
    return 0


# ------------------------------------------------------------------- farm
def _farm_group(cfg, params, replicas, slots, maxlen, buckets,
                prefill_devices=0, kv_quant=None, name="farm",
                max_queue=64, retries=1, guard=None, qos_factory=None):
    from paddle_tpu.serving.decode import (DecodeConfig,
                                           DecodeEngineConfig)
    from paddle_tpu.serving.farm import FarmConfig, ReplicaGroup
    return ReplicaGroup(cfg, params, FarmConfig(
        replicas=replicas, prefill_devices=prefill_devices,
        engine=DecodeEngineConfig(num_slots=slots, max_len=maxlen,
                                  prefill_buckets=buckets,
                                  kv_quant=kv_quant),
        decode=DecodeConfig(bos=0, max_queue_requests=max_queue),
        retries=retries, guard=guard, qos_factory=qos_factory),
        name=name)


def _pump_group(group, futures, problems, label, budget=800):
    """Drive a non-started group until every future resolves; crashed
    requests are resubmitted by GroupFuture on the result() poll."""
    from paddle_tpu.resilience.chaos import ChaosFault
    results = {}
    pending = dict(enumerate(futures))
    left = budget
    while pending and left:
        left -= 1
        for i, f in list(pending.items()):
            if not f.done():
                continue
            try:
                results[i] = f.result(timeout=0)
                del pending[i]
            except TimeoutError:
                pass            # resubmitted to another replica
        if pending:
            try:
                group.run_iteration()
            except ChaosFault as e:
                # manual drive has no supervisor thread: reclaim the
                # crashed replica's slots by hand, like _loop_guarded
                rep = group.replicas[0]
                rep.scheduler._crash_recover(e)
                rep.scheduler.restarts += 1
    if pending:
        problems.append(f"farm {label}: {len(pending)} requests never "
                        f"completed in {budget} iterations")
    return results


def _farm_parity_leg(problems, cfg, exe, infer, logits, params,
                     maxlen, buckets):
    """Leg 1: a 2-replica group with disaggregated prefill must be
    token-identical to one-at-a-time greedy_decode, spread load across
    both replicas, and stay at the group-level compile pin."""
    import numpy as np
    from paddle_tpu import telemetry
    from paddle_tpu.models.transformer import greedy_decode

    slots = 4
    group = _farm_group(cfg, params, replicas=2, slots=slots,
                        maxlen=maxlen, buckets=buckets,
                        prefill_devices=1, name="selftest")
    warm = group.compile_count
    if warm != len(buckets) + 1:
        problems.append(
            f"farm warmup built {warm} executables for 2 replicas, "
            f"expected {len(buckets)} shared prefill buckets + 1 "
            f"shared step")

    rng = np.random.RandomState(11)
    reqs = _decode_requests(rng, 8, maxlen, cfg.trg_vocab,
                            group.replicas[0].engine.max_new_tokens)
    expected = []
    for src, n, max_new in reqs:
        row = np.zeros((1, maxlen), np.int64)
        row[0, :n] = src
        ids = greedy_decode(exe, infer, logits, row,
                            np.array([n], "int64"), bos=0,
                            fetch_argmax=True)
        expected.append(ids[0, 1:1 + max_new])
    futures = [group.submit(src, src_len=n, max_new_tokens=mn)
               for src, n, mn in reqs]
    results = _pump_group(group, futures, problems, "parity")
    mismatches = sum(
        1 for i, r in results.items()
        if not np.array_equal(np.asarray(r.tokens, np.int64),
                              expected[i]))
    if mismatches:
        problems.append(
            f"{mismatches}/{len(reqs)} farm-decoded outputs differ "
            f"from greedy_decode — routing or the prefill handoff "
            f"changed the tokens")
    spread = [r.scheduler.tokens_generated for r in group.replicas]
    if min(spread) == 0:
        problems.append(f"router sent every request to one replica "
                        f"(tokens per replica: {spread})")
    if group.compile_count != warm:
        problems.append(
            f"farm compiled {group.compile_count - warm} NEW "
            f"executables under traffic")
    for r in group.replicas:
        r.scheduler.pool.check()
        if r.scheduler.pool.free_count() != slots:
            problems.append(f"replica {r.index} leaked slots")
    handoffs = telemetry.counter("serving.decode.handoffs").value
    if not handoffs:
        problems.append("disaggregated prefill never handed KV "
                        "device-to-device")
    return {"compile_count": warm, "requests": len(reqs),
            "mismatches": mismatches, "tokens_per_replica": spread,
            "prefill_devices": [str(d)
                                for d in group.prefill_devices],
            "handoffs": int(handoffs)}


def _farm_int8_leg(problems, cfg, params, maxlen):
    """Leg 2: int8 block-quantized KV vs the fp32 cache on the SAME
    weights, teacher-forced so per-step logits stay comparable."""
    import jax
    import numpy as np
    from paddle_tpu.models.transformer import IncrementalDecoder

    devs = jax.devices()
    dec_f = IncrementalDecoder(cfg, params, num_slots=2,
                               max_len=maxlen, return_logits=True,
                               device=devs[0])
    dec_q = IncrementalDecoder(cfg, params, num_slots=2,
                               max_len=maxlen, return_logits=True,
                               kv_quant="int8",
                               device=devs[1 % len(devs)])
    rng = np.random.RandomState(3)
    mismatch = total = 0
    max_delta = 0.0
    for n0, n1 in ((3, 5), (7, 10), (12, maxlen - 1)):
        src = np.zeros((2, dec_f.src_max_len), np.int64)
        src[0, :n0] = rng.randint(2, cfg.src_vocab - 2, n0)
        src[1, :n1] = rng.randint(2, cfg.src_vocab - 2, n1)
        sl = np.array([n0, n1], "int64")
        st_f = dec_f.write_slots(dec_f.init_state(),
                                 dec_f.prefill(src, sl), [0, 1])
        st_q = dec_q.write_slots(dec_q.init_state(),
                                 dec_q.prefill(src, sl), [0, 1])
        ids = np.zeros(2, np.int64)
        pos = np.zeros(2, np.int64)
        for _ in range(8):
            nf = dec_f.step(st_f, ids, pos)
            lf = dec_f.last_logits[:2].copy()
            nq = dec_q.step(st_q, ids, pos)
            lq = dec_q.last_logits[:2].copy()
            max_delta = max(max_delta,
                            float(np.max(np.abs(lf - lq))))
            mismatch += int((nf[:2] != nq[:2]).sum())
            total += 2
            ids[:2] = nf[:2]        # teacher-force the fp32 choice
            pos += 1
    rate = mismatch / total
    if rate > 0.02:
        problems.append(
            f"int8 KV cache diverged: {mismatch}/{total} tokens "
            f"differ from fp32 (bound 2%); max logit delta "
            f"{max_delta:.4f}")
    fb, qb = dec_f.kv_cache_bytes(), dec_q.kv_cache_bytes()
    if qb >= fb:
        problems.append(f"int8 KV cache is not smaller: {qb} vs "
                        f"{fb} bytes")
    return {"token_mismatch_rate": round(rate, 4),
            "max_logit_delta": round(max_delta, 6),
            "kv_bytes_fp32": fb, "kv_bytes_int8": qb,
            "kv_ratio": round(qb / fb, 3)}


def _farm_chaos_leg(problems, cfg, params, maxlen, buckets):
    """Leg 3: worker_crash on replica 0 of 2 (threaded) — the group
    must serve every request anyway: router skips the dead replica,
    GroupFuture resubmits the crashed ones, no slot leaks."""
    import numpy as np
    from paddle_tpu.resilience import chaos as _chaos

    slots = 4
    group = _farm_group(cfg, params, replicas=2, slots=slots,
                        maxlen=maxlen, buckets=buckets,
                        name="chaosfarm", retries=2)
    rng = np.random.RandomState(29)
    reqs = _decode_requests(rng, 6, maxlen, cfg.trg_vocab,
                            group.replicas[0].engine.max_new_tokens)
    _chaos.configure("worker_crash:at=2,replica=0")
    try:
        futures = [group.submit(src, src_len=n, max_new_tokens=mn)
                   for src, n, mn in reqs]
        group.start()
        served = 0
        for f in futures:
            try:
                r = f.result(timeout=60.0)
                if len(r.tokens) > 0:
                    served += 1
            except Exception as e:      # noqa: BLE001 — a drop
                problems.append(f"farm chaos leg dropped a request: "
                                f"{type(e).__name__}: {e}")
    finally:
        _chaos.reset()
        group.stop(drain=True, timeout=10.0)
    restarts = [r.scheduler.restarts for r in group.replicas]
    if restarts[0] < 1:
        problems.append("chaos worker_crash replica=0 never fired "
                        f"(restarts {restarts})")
    if served != len(reqs):
        problems.append(f"one-replica-down served {served}/"
                        f"{len(reqs)} — the group dropped requests")
    for r in group.replicas:
        r.scheduler.pool.check()
    return {"requests": len(reqs), "served": served,
            "restarts": restarts}


def _farm_rolling_leg(problems, cfg, params, maxlen):
    """Leg 4: rolling weight update under live traffic — zero dropped
    requests, both versions observed serving mid-update, zero new
    compiles from the weight swap."""
    import numpy as np

    slots = 2
    group = _farm_group(cfg, params, replicas=2, slots=slots,
                        maxlen=maxlen, buckets=(1, 2),
                        name="rollfarm", max_queue=64).start()
    params2 = {k: (v + 0.05 * np.random.RandomState(99)
                   .randn(*v.shape)).astype(v.dtype)
               for k, v in params.items()}
    rng = np.random.RandomState(41)
    reqs = _decode_requests(rng, 32, maxlen, cfg.trg_vocab, 8)
    stop = threading.Event()
    lock = threading.Lock()
    completed, errors = [0], []

    def client(wid):
        i = wid
        while not stop.is_set():
            src, n, mn = reqs[i % len(reqs)]
            i += 4
            try:
                group.submit(src, src_len=n,
                             max_new_tokens=mn).result(timeout=30.0)
                with lock:
                    completed[0] += 1
            except Exception as e:      # noqa: BLE001 — a drop
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                return

    versions_seen = set()

    def watcher():
        while not stop.is_set():
            versions_seen.add(
                tuple(r.version for r in group.replicas))
            time.sleep(0.0002)

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(4)]
    threads.append(threading.Thread(target=watcher, daemon=True))
    pre_compiles = group.compile_count
    for t in threads:
        t.start()
    try:
        time.sleep(0.2)
        group.rolling_update(params=params2, drain_timeout=30.0)
        time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(10.0)
        group.stop(drain=True, timeout=10.0)
    if errors:
        problems.append(f"rolling update dropped {len(errors)} "
                        f"requests (first: {errors[0]})")
    mixed = any(len(set(v)) == 2 for v in versions_seen)
    if not mixed:
        problems.append(
            f"rolling update never served both versions at once "
            f"(version snapshots: {sorted(versions_seen)})")
    if group.version != 2 or any(r.version != 2
                                 for r in group.replicas):
        problems.append("rolling update did not land version 2 on "
                        "every replica")
    if group.compile_count != pre_compiles:
        problems.append(
            f"rolling update recompiled "
            f"({group.compile_count - pre_compiles} new executables "
            f"— the weight swap must reuse the traces)")
    return {"completed": completed[0], "dropped": len(errors),
            "mixed_versions_observed": mixed,
            "version_snapshots": sorted(versions_seen)}


def _farm_selftest_problems(problems):
    """The tpufarm CI gate: replica-group parity + compile pin, int8
    KV parity bound, one-replica-down chaos, rolling update."""
    maxlen, buckets = 16, (1, 2, 4)
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    info = {"parity": _farm_parity_leg(problems, cfg, exe, infer,
                                       logits, params, maxlen,
                                       buckets),
            "int8_kv": _farm_int8_leg(problems, cfg, params, maxlen),
            "chaos": _farm_chaos_leg(problems, cfg, params, maxlen,
                                     buckets),
            "rolling": _farm_rolling_leg(problems, cfg, params,
                                         maxlen)}
    return info


def run_selftest_farm(args):
    from paddle_tpu import telemetry
    telemetry.enable()
    problems = []
    info = _farm_selftest_problems(problems)
    result = {"mode": "selftest-farm", **info,
              "problems": problems, "ok": not problems}
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        p = info["parity"]
        q = info["int8_kv"]
        print(f"tpuserve selftest-farm: {p['compile_count']} "
              f"executables for 2 replicas, "
              f"{p['mismatches']}/{p['requests']} greedy mismatches, "
              f"int8 KV {q['kv_ratio']}x bytes "
              f"(max logit delta {q['max_logit_delta']}), chaos "
              f"served {info['chaos']['served']}/"
              f"{info['chaos']['requests']}, rolling dropped "
              f"{info['rolling']['dropped']}")
        for prob in problems:
            print(f"FAIL: {prob}", file=sys.stderr)
    return 2 if problems else 0


def run_bench_farm(args):
    """Replica-group serving across the farm axes — 1 vs 2 replicas,
    fp32 vs int8 KV, pooled vs disaggregated prefill — each as a
    closed loop at ~5x total slots. Writes BENCH_decode2.json."""
    import numpy as np
    from paddle_tpu import telemetry
    from paddle_tpu.serving import RejectedError
    telemetry.enable()

    maxlen = args.decode_max_len
    slots = args.slots
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    rng = np.random.RandomState(23)
    # short prompts: the self-attn cache (the part int8 shrinks)
    # dominates the cross caches
    src_cap = max(4, maxlen // 2)
    reqs = _decode_requests(rng, 256, src_cap, cfg.trg_vocab,
                            maxlen - 1)

    cases = [
        ("r1_fp32_pooled", 1, None, 0),
        ("r1_int8_pooled", 1, "int8", 0),
        ("r2_fp32_pooled", 2, None, 0),
        ("r2_int8_pooled", 2, "int8", 0),
        ("r2_fp32_disagg", 2, None, 1),
        ("r2_int8_disagg", 2, "int8", 1),
    ]
    out_cases = {}
    for cname, replicas, kv, pdev in cases:
        group = _farm_group(
            cfg, params, replicas=replicas, slots=slots,
            maxlen=maxlen, buckets=None, kv_quant=kv,
            prefill_devices=pdev, name=cname,
            max_queue=8 * slots * replicas).start()
        total_slots = group.num_slots
        stop_t = time.monotonic() + args.duration
        lock = threading.Lock()
        done_tokens, rejects = [0], [0]

        def client(wid, _stop=stop_t, _g=group):
            i = wid
            while time.monotonic() < _stop:
                src, n, mn = reqs[i % len(reqs)]
                i += 5 * total_slots
                try:
                    r = _g.submit(src, src_len=n,
                                  max_new_tokens=mn).result(
                        timeout=max(5.0, args.duration))
                except RejectedError:
                    with lock:
                        rejects[0] += 1
                    time.sleep(0.002)
                    continue
                except TimeoutError:
                    continue
                with lock:
                    done_tokens[0] += len(r.tokens)

        clients = [threading.Thread(target=client, args=(w,),
                                    daemon=True)
                   for w in range(5 * total_slots)]
        t0 = time.monotonic()
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        dt = time.monotonic() - t0
        group.stop(drain=False, timeout=10.0)
        # devices actually computing (each engine is pinned to one
        # decode device), not the whole owned slice
        devices = {str(r.engine.device) for r in group.replicas}
        devices |= {str(d) for d in group.prefill_devices}
        goodput = done_tokens[0] / dt
        out_cases[cname] = {
            "replicas": replicas,
            "kv_quant": kv or "fp32",
            "prefill": "disaggregated" if pdev else "pooled",
            "devices": len(devices),
            "total_slots": total_slots,
            "slots_per_device": round(total_slots / len(devices), 3),
            "goodput_tokens_per_s": round(goodput, 1),
            "goodput_per_device": round(goodput / len(devices), 1),
            "kv_cache_bytes_per_replica":
                group.replicas[0].engine.kv_cache_bytes,
            "completed_tokens": done_tokens[0],
            "rejected": rejects[0],
            "compile_count": group.compile_count,
        }
        if not args.as_json:
            c = out_cases[cname]
            print(f"  {cname:<16} {c['goodput_tokens_per_s']:>8} "
                  f"tok/s  {c['goodput_per_device']:>8} tok/s/dev  "
                  f"{c['slots_per_device']:>5} slots/dev  KV "
                  f"{c['kv_cache_bytes_per_replica']} B")

    curves = {}
    for kv in ("fp32", "int8"):
        for pf in ("pooled", "disaggregated"):
            pts = sorted(
                ({"replicas": c["replicas"],
                  "slots_per_device": c["slots_per_device"],
                  "goodput_per_device": c["goodput_per_device"]}
                 for c in out_cases.values()
                 if c["kv_quant"] == kv and c["prefill"] == pf),
                key=lambda p: p["replicas"])
            if pts:
                curves[f"{kv}_{pf}"] = pts
    result = {"mode": "bench-farm", "model": "transformer-tiny",
              "maxlen": maxlen, "slots_per_replica": slots,
              "duration_s": args.duration, "cases": out_cases,
              "curves": curves}
    out_path = os.path.join(_REPO, "BENCH_decode2.json")
    try:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
    except OSError:
        pass
    if args.as_json:
        print(json.dumps(result))
    return 0


# ------------------------------------------------------------------ guard
def _guard_latency_phase(group, reqs, expected, problems, label,
                         threads=4, timeout=30.0):
    """Closed-loop clients over a STARTED group: every request's
    latency recorded, every token sequence checked against the
    precomputed greedy_decode reference. Returns sorted latencies."""
    import numpy as np
    lock = threading.Lock()
    lats, errs, mism = [], [], [0]

    def client(wid):
        for i in range(wid, len(reqs), threads):
            src, n, mn = reqs[i]
            t0 = time.monotonic()
            try:
                r = group.submit(src, src_len=n,
                                 max_new_tokens=mn).result(
                    timeout=timeout)
            except Exception as e:  # noqa: BLE001 — a drop
                with lock:
                    errs.append(f"{type(e).__name__}: {e}")
                continue
            dt = time.monotonic() - t0
            with lock:
                lats.append(dt)
                if not np.array_equal(
                        np.asarray(r.tokens, np.int64), expected[i]):
                    mism[0] += 1

    ts = [threading.Thread(target=client, args=(w,), daemon=True)
          for w in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout + 30.0)
    if errs:
        problems.append(f"guard {label}: dropped {len(errs)}/"
                        f"{len(reqs)} requests (first: {errs[0]})")
    if mism[0]:
        problems.append(
            f"guard {label}: {mism[0]}/{len(reqs)} outputs differ "
            f"from greedy_decode — hedging/cancellation changed "
            f"the tokens")
    return sorted(lats)


def _hedge_guard_config(**over):
    """Hedging isolated: health transitions and brownout are pushed
    out of reach so any p99 win is attributable to the hedge alone."""
    from paddle_tpu.serving.guard import GuardConfig
    kw = dict(hedge_fixed_delay_s=0.05, hedge_fraction=1.0,
              hedge_burst=64.0, retry_rate=1000.0, retry_burst=1000,
              slow_factor=1e9, err_probation=2.0, enter_streak=10**6,
              queue_high=10**9)
    kw.update(over)
    return GuardConfig(**kw)


def _guard_hedge_leg(problems, cfg, exe, infer, logits, params,
                     maxlen, buckets):
    """Leg (a): replica_slow on 1 of 2 replicas — hedged requests must
    cut p99 vs the guard-off group under the SAME fault, at token
    parity with greedy_decode, with every losing leg's slot
    reclaimed."""
    import numpy as np
    from paddle_tpu.models.transformer import greedy_decode
    from paddle_tpu.resilience import chaos as _chaos

    slots = 4
    rng = np.random.RandomState(17)
    reqs = _decode_requests(rng, 24, maxlen, cfg.trg_vocab, 6)
    expected = []
    for src, n, max_new in reqs:
        row = np.zeros((1, maxlen), np.int64)
        row[0, :n] = src
        ids = greedy_decode(exe, infer, logits, row,
                            np.array([n], "int64"), bos=0,
                            fetch_argmax=True)
        expected.append(ids[0, 1:1 + max_new])

    out = {}
    for label, guard in (("off", None), ("hedged",
                                         _hedge_guard_config())):
        group = _farm_group(cfg, params, replicas=2, slots=slots,
                            maxlen=maxlen, buckets=buckets,
                            name=f"guard-{label}", retries=2,
                            guard=guard).start()
        _chaos.configure("replica_slow:ms=120,replica=0")
        try:
            lats = _guard_latency_phase(group, reqs, expected,
                                        problems, label)
        finally:
            _chaos.reset()
            group.stop(drain=True, timeout=15.0)
        for r in group.replicas:
            r.scheduler.pool.check()
            if r.scheduler.pool.free_count() != slots:
                problems.append(f"guard {label}: replica {r.index} "
                                f"leaked slots")
        case = {"requests": len(lats),
                "p50_ms": round(1000 * _percentile(lats, 0.50), 2)
                if lats else None,
                "p99_ms": round(1000 * _percentile(lats, 0.99), 2)
                if lats else None}
        if guard is not None:
            g = group.guard
            case.update(hedges=g.hedges, hedge_wins=g.hedge_wins,
                        hedge_cancelled=g.hedge_cancelled)
            if g.hedges < 1:
                problems.append("hedging never fired under "
                                "replica_slow")
            if g.hedge_wins < 1:
                problems.append("no hedge ever won the race against "
                                "the throttled primary")
        out[label] = case
    off, on = out["off"]["p99_ms"], out["hedged"]["p99_ms"]
    if off is not None and off < 200.0:
        problems.append(f"replica_slow did not bite: guard-off p99 "
                        f"{off}ms (expected a throttled tail)")
    if off is not None and on is not None and on >= 0.7 * off:
        problems.append(
            f"hedging did not cut p99: {on}ms hedged vs {off}ms "
            f"guard-off under the same replica_slow fault")
    return out


def _pump_guard(group, futs, problems, label, budget=600):
    """Drive a non-started guarded group until every future resolves,
    catching injected crashes the way the supervisor thread would.
    Returns {index: DecodeResult}; drops land in `problems`."""
    from paddle_tpu.resilience.chaos import ChaosFault
    results, pending, left = {}, dict(enumerate(futs)), budget
    while pending and left:
        left -= 1
        for i, f in list(pending.items()):
            try:
                results[i] = f.result(timeout=0)
                del pending[i]
            except TimeoutError:
                pass            # resubmitted / still decoding
            except Exception as e:  # noqa: BLE001 — a drop
                problems.append(f"guard {label} dropped a request: "
                                f"{type(e).__name__}: {e}")
                del pending[i]
        if not pending:
            break
        for r in group.replicas:
            try:
                r.scheduler.run_iteration()
            except ChaosFault as e:
                r.scheduler._crash_recover(e)
                r.scheduler.restarts += 1
    if pending:
        problems.append(f"guard {label}: {len(pending)} requests "
                        f"never completed in {budget} iterations")
    return results


def _guard_flap_leg(problems, cfg, params, maxlen, buckets):
    """Leg (b): a crash-flapping replica must be walked to EJECTED,
    probed after cooldown, and re-admitted — with zero dropped
    requests along the way. Manually driven: the flap is armed only
    once slots are bound, so the walk is deterministic."""
    import numpy as np
    from paddle_tpu.resilience import chaos as _chaos
    from paddle_tpu.resilience.chaos import ChaosFault
    from paddle_tpu.serving.guard import GuardConfig

    # trip-sensitive health for CI clocks: the first crash-failed leg
    # puts replica 0 on probation, the second consecutive one ejects
    # it (a real deployment would ride the defaults' longer streaks)
    gcfg = GuardConfig(hedge=False, slow_factor=1e9, min_samples=1,
                       enter_streak=1, probation_grace=1,
                       err_probation=0.25, err_exit=0.6,
                       probation_good=1, cooldown_s=0.25,
                       cooldown_max_s=2.0, retry_rate=200.0,
                       retry_burst=200, queue_high=10**9)
    group = _farm_group(cfg, params, replicas=2, slots=4,
                        maxlen=maxlen, buckets=buckets,
                        name="guard-flap", retries=4, guard=gcfg)
    health = group.guard.health
    rng = np.random.RandomState(19)
    reqs = _decode_requests(rng, 12, maxlen, cfg.trg_vocab, 5)

    # 4 submissions alternate r0/r1 under least-loaded scoring; admit
    # them into slots BEFORE arming the flap so the burst has legs to
    # kill (the chaos check runs before admission, so queued-only work
    # never dies with a replica)
    futs = [group.submit(src, src_len=n, max_new_tokens=mn)
            for src, n, mn in reqs[:4]]
    legs0 = sum(1 for f in futs if f.replica_index == 0)
    if legs0 < 2:
        problems.append(f"flap precondition: expected 2 legs routed "
                        f"to replica 0, got {legs0}")
    group.run_iteration()
    _chaos.configure("replica_flap:at=1,times=2,replica=0")
    try:
        r0 = group.replicas[0]
        try:
            r0.scheduler.run_iteration()
            problems.append("replica_flap never fired on the bound "
                            "slots")
        except ChaosFault as e:
            r0.scheduler._crash_recover(e)
            r0.scheduler.restarts += 1
        # polling the dead legs immediately (pure Python, well inside
        # the cooldown window) feeds the health tracker: first error
        # -> probation, second consecutive -> EJECTED; both requests
        # resubmit to replica 1 — zero drops
        for f in futs:
            try:
                f.result(timeout=0)
            except TimeoutError:
                pass
        if health.ejections < 1 or health.state(0) != "ejected":
            problems.append(
                f"flapping replica was not ejected (state "
                f"{health.state(0)!r}, ejections "
                f"{health.ejections})")
        # while ejected the router must never select replica 0
        mid = [group.submit(src, src_len=n, max_new_tokens=mn)
               for src, n, mn in reqs[4:8]]
        if any(f.replica_index == 0 for f in mid):
            problems.append("router sent traffic to an EJECTED "
                            "replica")
        _pump_guard(group, futs + mid, problems, "flap-mid",
                    budget=400)
        # cooldown passes -> HALF_OPEN; the next request IS the probe.
        # The flap still has one charge: the probe rides through a
        # respawn (the crash fires before admission, so the probe
        # survives queued), then completes as the OK sample that
        # re-admits the replica
        time.sleep(0.3)
        src, n, mn = reqs[8]
        probe = group.submit(src, src_len=n, max_new_tokens=mn)
        if probe.replica_index != 0:
            problems.append(
                f"half-open probe was not routed to the cooled-down "
                f"replica (went to {probe.replica_index})")
        if health.probes < 1:
            problems.append("probe routing did not consume probe "
                            "capacity")
        _pump_guard(group, [probe], problems, "flap-probe",
                    budget=400)
    finally:
        _chaos.reset()
    if health.readmissions < 1 or health.state(0) != "healthy":
        problems.append(
            f"probed replica was not re-admitted (state "
            f"{health.state(0)!r}, readmissions "
            f"{health.readmissions})")
    for r in group.replicas:
        r.scheduler.pool.check()
        if r.scheduler.pool.free_count() != 4:
            problems.append(f"flap leg: replica {r.index} leaked "
                            f"slots")
    return {"served": 9, "ejections": health.ejections,
            "probes": health.probes,
            "readmissions": health.readmissions,
            "replica0_restarts": group.replicas[0].scheduler.restarts,
            "final_states": [health.state(r.index)
                             for r in group.replicas]}


def _guard_poison_leg(problems, cfg, params, maxlen, buckets):
    """Leg (c): request_poison kills whichever replica steps it — the
    poisoned request must fail ALONE (typed, after its retries burn
    out), innocents ride resubmission, the blasted replicas survive
    probation without ejection, and no slot leaks."""
    import numpy as np
    from paddle_tpu.resilience import chaos as _chaos
    from paddle_tpu.serving.guard import GuardConfig

    slots = 4
    gcfg = GuardConfig(hedge=False, slow_factor=1e9, enter_streak=3,
                       probation_grace=10, err_probation=0.35,
                       retry_rate=200.0, retry_burst=200,
                       queue_high=10**9)
    group = _farm_group(cfg, params, replicas=2, slots=slots,
                        maxlen=maxlen, buckets=buckets,
                        name="guard-poison", retries=3,
                        guard=gcfg).start()
    rng = np.random.RandomState(31)
    reqs = _decode_requests(rng, 8, maxlen, cfg.trg_vocab, 5)
    poison_i = 2
    _chaos.configure(f"request_poison:at={poison_i + 1}")
    outcomes = []
    try:
        futures = [group.submit(src, src_len=n, max_new_tokens=mn)
                   for src, n, mn in reqs]
        for f in futures:
            try:
                r = f.result(timeout=30.0)
                outcomes.append(("ok", len(r.tokens)))
            except Exception as e:  # noqa: BLE001 — expected once
                outcomes.append(("err", type(e).__name__))
    finally:
        _chaos.reset()
    failed = [i for i, o in enumerate(outcomes) if o[0] == "err"]
    if failed != [poison_i]:
        problems.append(
            f"request_poison blast was not contained: requests "
            f"{failed} failed, expected exactly [{poison_i}] "
            f"(outcomes: {outcomes})")
    health = group.guard.health
    if health.ejections:
        problems.append("a single poisoned request got a replica "
                        "ejected (poison != sick replica)")
    # recovery wave: both replicas must still serve after the blast
    recovered = 0
    for src, n, mn in reqs[:4]:
        try:
            group.submit(src, src_len=n,
                         max_new_tokens=mn).result(timeout=30.0)
            recovered += 1
        except Exception as e:  # noqa: BLE001 — a drop
            problems.append(f"post-poison request dropped: "
                            f"{type(e).__name__}: {e}")
    group.stop(drain=True, timeout=15.0)
    for r in group.replicas:
        r.scheduler.pool.check()
        if r.scheduler.pool.free_count() != slots:
            problems.append(f"poison leg: replica {r.index} leaked "
                            f"slots")
    return {"outcomes": outcomes,
            "failed": failed,
            "recovered_wave": recovered,
            "restarts": [r.scheduler.restarts
                         for r in group.replicas],
            "resubmits": group.guard.resubmits,
            "final_states": [health.state(r.index)
                             for r in group.replicas]}


def _guard_brownout_leg(problems, cfg, params, maxlen, buckets):
    """Leg (d): synthetic overload — brownout sheds ONLY the lowest
    QoS class (with a Retry-After hint), clamps the survivors'
    generation length, recovers hysteretically; then a crash storm
    shows the retry budget capping resubmissions with a typed error."""
    import numpy as np
    from paddle_tpu.resilience import chaos as _chaos
    from paddle_tpu.resilience.chaos import ChaosFault
    from paddle_tpu.serving import RetryBudgetExhausted
    from paddle_tpu.serving.batcher import BrownoutShed
    from paddle_tpu.serving.decode import QosPolicy
    from paddle_tpu.serving.guard import GuardConfig

    # --- brownout: shed the batch class, clamp interactive, recover
    gcfg = GuardConfig(hedge=False, slow_factor=1e9, queue_high=6,
                       queue_low=1, dwell_s=0.05, clamp_new_tokens=3,
                       retry_after_s=2.5, retry_rate=200.0,
                       retry_burst=200, enter_streak=10**6)
    group = _farm_group(
        cfg, params, replicas=1, slots=4, maxlen=maxlen,
        buckets=buckets, name="guard-brownout", guard=gcfg,
        qos_factory=lambda: QosPolicy(
            tenants=[("interactive", 4.0), ("batch", 1.0)]))
    rng = np.random.RandomState(37)
    reqs = _decode_requests(rng, 12, maxlen, cfg.trg_vocab, 4)
    futs, shed = [], None
    for k in range(8):
        src, n, mn = reqs[k]
        try:
            futs.append(group.submit(src, src_len=n, tenant="batch",
                                     max_new_tokens=mn))
        except BrownoutShed as e:
            shed = e
    bo = group.guard.brownout
    if shed is None:
        problems.append("brownout never shed the batch class under "
                        "a flooded queue")
    elif shed.retry_after_s != 2.5:
        problems.append(f"BrownoutShed lost the Retry-After hint: "
                        f"{shed.retry_after_s}")
    if not bo.active:
        problems.append("brownout controller not active at "
                        "queue_high")
    # the paying class rides through, generation length clamped
    src, n, _ = reqs[8]
    fi = group.submit(src, src_len=n, tenant="interactive",
                      max_new_tokens=8)
    if bo.clamped < 1:
        problems.append("brownout did not clamp the interactive "
                        "class's max_new_tokens")
    futs.append(fi)
    pending = dict(enumerate(futs))
    interactive_tokens = None
    for _ in range(600):
        if not pending:
            break
        group.run_iteration()
        for i, f in list(pending.items()):
            try:
                r = f.result(timeout=0)
            except TimeoutError:
                continue
            if f is fi:
                interactive_tokens = len(r.tokens)
            del pending[i]
    if pending:
        problems.append(f"brownout leg: {len(pending)} requests "
                        f"never completed")
    if interactive_tokens is not None and interactive_tokens > 3:
        problems.append(f"clamped interactive request generated "
                        f"{interactive_tokens} tokens (clamp 3)")
    time.sleep(0.06)        # past the hysteresis dwell, queue empty
    src, n, mn = reqs[9]
    try:
        f2 = group.submit(src, src_len=n, tenant="batch",
                          max_new_tokens=mn)
    except BrownoutShed:
        f2 = None
        problems.append("brownout failed to recover: batch class "
                        "still shed on an empty queue")
    if bo.active:
        problems.append("brownout still active after recovery "
                        "conditions were met")
    if f2 is not None:
        for _ in range(200):
            group.run_iteration()
            try:
                f2.result(timeout=0)
                break
            except TimeoutError:
                continue
        else:
            problems.append("post-recovery batch request never "
                            "completed")
    brown = {"entries": bo.entries, "sheds": bo.sheds,
             "clamped": bo.clamped, "recovered": not bo.active,
             "retry_after_s": None if shed is None
             else shed.retry_after_s}

    # --- retry budget: a crash storm is capped by the token bucket,
    # not by the per-request retry count (10 here)
    group2 = _farm_group(cfg, params, replicas=3, slots=2,
                         maxlen=maxlen, buckets=buckets,
                         name="guard-storm", retries=10,
                         guard=GuardConfig(hedge=False,
                                           slow_factor=1e9,
                                           retry_rate=0.0,
                                           retry_burst=2,
                                           queue_high=10**9))
    src, n, _ = reqs[10]
    _chaos.configure("worker_crash:every=2")
    typed = None
    try:
        f = group2.submit(src, src_len=n, max_new_tokens=3)
        for _ in range(200):
            for r in group2.replicas:
                try:
                    r.scheduler.run_iteration()
                except ChaosFault as e:
                    r.scheduler._crash_recover(e)
                    r.scheduler.restarts += 1
            try:
                f.result(timeout=0)
                problems.append("crash-storm request completed — "
                                "worker_crash:every=2 never fired")
                break
            except TimeoutError:
                continue
            except RetryBudgetExhausted as e:
                typed = e
                break
            except Exception as e:  # noqa: BLE001 — wrong type
                problems.append(
                    f"retry-budget exhaustion raised "
                    f"{type(e).__name__}, expected "
                    f"RetryBudgetExhausted: {e}")
                break
    finally:
        _chaos.reset()
    if typed is None and not problems:
        problems.append("retry budget never produced a typed "
                        "RetryBudgetExhausted under the crash storm")
    g2 = group2.guard
    if g2.resubmits != 2:
        problems.append(f"retry budget (burst 2) allowed "
                        f"{g2.resubmits} resubmissions, expected "
                        f"exactly 2")
    for r in group2.replicas:
        r.scheduler.pool.check()
    return {"brownout": brown,
            "retry_budget": {"typed": typed is not None,
                             "resubmits": g2.resubmits,
                             "denied": g2.retry_budget.denied}}


def _guard_selftest_problems(problems):
    """The tpuguard CI gate: hedging under replica_slow, flap
    ejection/re-admission, poison containment, brownout + retry
    budget."""
    maxlen, buckets = 16, (1, 2, 4)
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    info = {"hedge": _guard_hedge_leg(problems, cfg, exe, infer,
                                      logits, params, maxlen,
                                      buckets),
            "flap": _guard_flap_leg(problems, cfg, params, maxlen,
                                    buckets),
            "poison": _guard_poison_leg(problems, cfg, params, maxlen,
                                        buckets),
            "overload": _guard_brownout_leg(problems, cfg, params,
                                            maxlen, buckets)}
    return info


def run_selftest_guard(args):
    from paddle_tpu import telemetry
    telemetry.enable()
    problems = []
    info = _guard_selftest_problems(problems)
    result = {"mode": "selftest-guard", **info,
              "problems": problems, "ok": not problems}
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        h = info["hedge"]
        fl = info["flap"]
        ov = info["overload"]
        print(f"tpuserve selftest-guard: hedged p99 "
              f"{h['hedged']['p99_ms']}ms vs {h['off']['p99_ms']}ms "
              f"guard-off ({h['hedged'].get('hedges', 0)} hedges, "
              f"{h['hedged'].get('hedge_wins', 0)} wins); flap "
              f"ejections={fl['ejections']} probes={fl['probes']} "
              f"readmissions={fl['readmissions']} "
              f"dropped={fl['dropped']}; poison failed "
              f"{info['poison']['failed']}; brownout sheds="
              f"{ov['brownout']['sheds']} clamped="
              f"{ov['brownout']['clamped']} recovered="
              f"{ov['brownout']['recovered']}; retry resubmits="
              f"{ov['retry_budget']['resubmits']}")
        for prob in problems:
            print(f"FAIL: {prob}", file=sys.stderr)
    return 2 if problems else 0


def run_bench_guard(args):
    """Tail-latency defense bench: closed-loop p50/p99 with and
    without hedging while replica_slow throttles 1 of 2 replicas.
    Writes BENCH_guard.json and appends guard_* records to the
    paddle_tpu.bench.history.v1 spine for the tpustat --slo gate."""
    import numpy as np
    from paddle_tpu import telemetry
    from paddle_tpu.resilience import chaos as _chaos
    telemetry.enable()

    maxlen = args.decode_max_len
    slots = args.slots
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    rng = np.random.RandomState(43)
    reqs = _decode_requests(rng, 128, max(4, maxlen // 2),
                            cfg.trg_vocab, 8)
    out_cases = {}
    for label, guard in (("guard_off", None),
                         ("guard_hedged", _hedge_guard_config())):
        group = _farm_group(cfg, params, replicas=2, slots=slots,
                            maxlen=maxlen, buckets=None, name=label,
                            retries=2, guard=guard,
                            max_queue=16 * slots).start()
        _chaos.configure("replica_slow:ms=60,replica=0")
        stop_t = time.monotonic() + args.duration
        lock = threading.Lock()
        lats, drops = [], [0]

        def client(wid, _stop=stop_t, _g=group):
            i = wid
            while time.monotonic() < _stop:
                src, n, mn = reqs[i % len(reqs)]
                i += 4 * slots
                t0 = time.monotonic()
                try:
                    _g.submit(src, src_len=n,
                              max_new_tokens=mn).result(
                        timeout=max(5.0, args.duration))
                except Exception:  # noqa: BLE001 — count, move on
                    with lock:
                        drops[0] += 1
                    continue
                with lock:
                    lats.append(time.monotonic() - t0)

        clients = [threading.Thread(target=client, args=(w,),
                                    daemon=True)
                   for w in range(4 * slots)]
        for t in clients:
            t.start()
        for t in clients:
            t.join()
        _chaos.reset()
        group.stop(drain=True, timeout=15.0)
        lats.sort()
        case = {"requests": len(lats), "dropped": drops[0],
                "p50_ms": round(1000 * _percentile(lats, 0.50), 2)
                if lats else None,
                "p99_ms": round(1000 * _percentile(lats, 0.99), 2)
                if lats else None}
        if guard is not None:
            g = group.guard
            case.update(hedges=g.hedges, hedge_wins=g.hedge_wins,
                        hedge_cancelled=g.hedge_cancelled)
        out_cases[label] = case
        if not args.as_json:
            print(f"  {label:<14} p50 {case['p50_ms']}ms  p99 "
                  f"{case['p99_ms']}ms  ({case['requests']} requests"
                  + (f", {case['hedges']} hedges"
                     if "hedges" in case else "") + ")")

    result = {"mode": "bench-guard", "model": "transformer-tiny",
              "maxlen": maxlen, "slots_per_replica": slots,
              "duration_s": args.duration,
              "fault": "replica_slow:ms=60,replica=0",
              "cases": out_cases}
    out_path = os.path.join(_REPO, "BENCH_guard.json")
    try:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
    except OSError:
        pass
    result["history_appended"] = _guard_append_history(out_cases)
    if args.as_json:
        print(json.dumps(result))
    return 0


def _guard_append_history(cases):
    """One paddle_tpu.bench.history.v1 record per headline guard
    metric, onto the same spine bench.py feeds (BENCH_HISTORY_PATH
    overrides the repo-root default) so `tpustat --slo` regression-
    gates the hedged tail like any other perf number. Best-effort:
    returns the path or None, never raises."""
    try:
        import subprocess

        from paddle_tpu.telemetry import slo
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO,
                capture_output=True, text=True,
                timeout=10).stdout.strip() or None
        except Exception:  # noqa: BLE001 — sha is optional
            sha = None
        import jax
        dev = jax.devices()[0]      # the backend the bench just ran on
        common = {"schema": slo.HISTORY_SCHEMA,
                  "platform": dev.platform,
                  "device_kind": dev.device_kind, "git_sha": sha,
                  "unix_time": round(time.time(), 1),
                  "stage": "guard"}
        recs = []
        for case, key, metric in (
                ("guard_off", "p99_ms", "guard_off_p99_ms"),
                ("guard_hedged", "p99_ms", "guard_hedged_p99_ms"),
                ("guard_hedged", "p50_ms", "guard_hedged_p50_ms")):
            v = cases.get(case, {}).get(key)
            if isinstance(v, (int, float)) and v:
                recs.append(dict(common, metric=metric, value=v,
                                 unit="ms"))
        if not recs:
            return None
        path = os.environ.get("BENCH_HISTORY_PATH") \
            or os.path.join(_REPO, "BENCH_history.jsonl")
        slo.append_history(path, recs)
        return path
    except Exception:  # noqa: BLE001 — history is best-effort
        return None


# ------------------------------------------------------------------ scale
def _scale_group(cfg, params, slots, maxlen, buckets, name,
                 guard=None, qos_factory=None, max_queue=64):
    """A 1-replica group provisioned ELASTICALLY: the seed replica
    owns device 0 only, every other local device stays free for the
    planner's ledger. (A statically-provisioned group's single slice
    spans ALL devices — the planner would see free=0 and report the
    ceiling immediately; see the scale package docstring.)"""
    import jax

    from paddle_tpu.serving.decode import (DecodeConfig,
                                           DecodeEngineConfig)
    from paddle_tpu.serving.farm import FarmConfig, ReplicaGroup
    devs = jax.devices()
    group = ReplicaGroup(cfg, params, FarmConfig(
        replicas=1, devices=devs[:1],
        engine=DecodeEngineConfig(num_slots=slots, max_len=maxlen,
                                  prefill_buckets=buckets),
        decode=DecodeConfig(bos=0, max_queue_requests=max_queue),
        guard=guard, qos_factory=qos_factory), name=name)
    return group, devs


def _scale_ramp_leg(problems, cfg, params, maxlen, buckets):
    """Leg (a): a tpuchaos traffic_spike rides the queue up — the
    controller must ramp N->M (through the shared build cache: ZERO
    new compiles), serve every real request, then drain-and-shrink
    back to N once the spike passes."""
    import numpy as np

    from paddle_tpu import telemetry as _tm
    from paddle_tpu.resilience import chaos as _chaos
    from paddle_tpu.serving.batcher import RejectedError
    from paddle_tpu.serving.scale import (ScaleController, ScalePlanner,
                                          ScalePolicy)

    group, devs = _scale_group(cfg, params, slots=2, maxlen=maxlen,
                               buckets=buckets, name="scale-ramp")
    policy = ScalePolicy(
        ["queue_per_replica > 4 -> up", "queue_depth < 1 -> down"],
        min_replicas=1, max_replicas=3,
        up_cooldown_s=0.0, down_cooldown_s=0.0,
        up_dwell=1, down_dwell=2)
    ctl = ScaleController(group, policy,
                          ScalePlanner(group, devices=devs, width=1))
    c0 = group.compile_count
    rng = np.random.RandomState(53)
    reqs = _decode_requests(rng, 12, maxlen, cfg.trg_vocab, 4)
    _chaos.configure("traffic_spike:at=3,x=5,len=6")
    futs, timeline, max_live = [], [], 1
    try:
        for k, (src, n, mn) in enumerate(reqs):
            try:
                futs.append(group.submit(src, src_len=n,
                                         max_new_tokens=mn))
            except RejectedError:
                problems.append(f"scale ramp DROPPED real request "
                                f"{k} at admission")
            d = ctl.tick()
            max_live = max(max_live, len(group.replicas))
            timeline.append({"k": k, "queued": group.queued,
                             "live": len(group.replicas),
                             "action": d.action})
    finally:
        _chaos.reset()
    compiles_up = group.compile_count - c0
    t0 = time.monotonic()
    results = _pump_group(group, futs, problems, "scale-ramp",
                          budget=2000)
    drain_s = time.monotonic() - t0
    # shadows the spike injected may still be queued: drain them so
    # the down trigger (queue_depth < 1) can see a quiet group
    for _ in range(800):
        if group.queued == 0 and all(
                r.scheduler.pool.active_count() == 0
                for r in group.replicas):
            break
        group.run_iteration()
    for _ in range(8):
        d = ctl.tick(drive=True)
        timeline.append({"k": "drain", "queued": group.queued,
                         "live": len(group.replicas),
                         "action": d.action})
        if len(group.replicas) <= policy.min_replicas:
            break
    if max_live < 2:
        problems.append(f"controller never scaled up under the "
                        f"traffic spike (max live {max_live})")
    if compiles_up != 0:
        problems.append(f"scale-up RECOMPILED: compile_count grew by "
                        f"{compiles_up} (shared build cache must make "
                        f"grows free)")
    if len(group.replicas) != policy.min_replicas:
        problems.append(f"group did not shrink back to "
                        f"{policy.min_replicas} after the spike "
                        f"(live {len(group.replicas)})")
    if len(results) != len(reqs):
        problems.append(f"scale ramp served {len(results)}/"
                        f"{len(reqs)} real requests")
    tokens = sum(len(r.tokens) for r in results.values())
    shadows = _tm.counter("serving.farm.spike_shadows").value
    if shadows < 1:
        problems.append("traffic_spike fault never injected a "
                        "shadow request")
    ctl.stop()
    group.stop()
    return {"served": len(results), "dropped": len(reqs)
            - len(results), "max_live": max_live,
            "final_live": len(group.replicas),
            "scaleup_recompiles": compiles_up,
            "spike_shadows": int(shadows),
            "goodput_tokens_per_s": round(tokens / max(drain_s, 1e-6),
                                          1),
            "drain_ms": round(drain_s * 1000.0, 2),
            "decisions": dict(ctl.decisions),
            "planner": ctl.planner.stats(),
            "timeline": timeline}


def _scale_ceiling_leg(problems, cfg, params, maxlen, buckets):
    """Leg (b): shed-only-at-ceiling. While a free device slice
    exists, an overloaded guard must DEFER brownout (the controller
    relays headroom); the moment the planner/policy report the
    ceiling, brownout engages — exactly then, exactly once."""
    import numpy as np

    from paddle_tpu.serving.batcher import BrownoutShed
    from paddle_tpu.serving.decode import QosPolicy
    from paddle_tpu.serving.guard import GuardConfig
    from paddle_tpu.serving.scale import (ScaleController, ScalePlanner,
                                          ScalePolicy)

    gcfg = GuardConfig(hedge=False, slow_factor=1e9, queue_high=4,
                       queue_low=1, dwell_s=0.01, retry_after_s=1.5,
                       retry_rate=200.0, retry_burst=200,
                       enter_streak=10**6)
    group, devs = _scale_group(
        cfg, params, slots=2, maxlen=maxlen, buckets=buckets,
        name="scale-ceiling", guard=gcfg,
        qos_factory=lambda: QosPolicy(
            tenants=[("interactive", 4.0), ("batch", 1.0)]))
    policy = ScalePolicy(["queue_depth > 4 -> up"], min_replicas=1,
                         max_replicas=2, up_cooldown_s=0.0,
                         up_dwell=1)
    ctl = ScaleController(group, policy,
                          ScalePlanner(group, devices=devs, width=1))
    bo = group.guard.brownout
    ctl.tick()                      # below the ceiling: headroom on
    if not bo.headroom:
        problems.append("controller did not relay headroom to the "
                        "guard below the ceiling")
    rng = np.random.RandomState(59)
    reqs = _decode_requests(rng, 14, maxlen, cfg.trg_vocab, 3)
    futs, early_shed = [], 0
    for k in range(7):              # flood: queue >= queue_high
        src, n, mn = reqs[k]
        try:
            futs.append(group.submit(src, src_len=n, tenant="batch",
                                     max_new_tokens=mn))
        except BrownoutShed:
            early_shed += 1
    deferred_below = bo.deferred
    if early_shed:
        problems.append(f"brownout shed {early_shed} request(s) "
                        f"while a free device slice existed")
    if bo.entries != 0:
        problems.append("brownout ENGAGED below the device ceiling "
                        "(scale-out must beat shedding)")
    if deferred_below < 1:
        problems.append("brownout entry was never deferred under "
                        "overload with headroom")
    d = ctl.tick()                  # grow 1->2; now at policy ceiling
    if d.action != "up":
        problems.append(f"overloaded controller decided "
                        f"{d.action!r}, expected 'up'")
    if not d.at_ceiling:
        problems.append("grow to max_replicas did not report the "
                        "ceiling")
    if bo.headroom:
        problems.append("headroom still on at the ceiling — brownout "
                        "deferral never lifts")
    sheds_at_ceiling = 0
    for k in range(7, 11):          # still flooded, no slices left
        src, n, mn = reqs[k]
        try:
            futs.append(group.submit(src, src_len=n, tenant="batch",
                                     max_new_tokens=mn))
        except BrownoutShed:
            sheds_at_ceiling += 1
    if bo.entries != 1:
        problems.append(f"brownout entries={bo.entries} at the "
                        f"ceiling, expected exactly 1")
    if sheds_at_ceiling < 1:
        problems.append("brownout never shed at the device ceiling")
    src, n, _ = reqs[11]            # the paying class rides through
    try:
        futs.append(group.submit(src, src_len=n, tenant="interactive",
                                 max_new_tokens=3))
    except BrownoutShed:
        problems.append("brownout shed the interactive class")
    _pump_guard(group, futs, problems, "scale-ceiling", budget=800)
    ctl.stop()
    group.stop()
    return {"deferred_below_ceiling": deferred_below,
            "entries": bo.entries, "sheds": bo.sheds,
            "sheds_at_ceiling": sheds_at_ceiling,
            "early_sheds": early_shed,
            "grew_to": len(group.replicas),
            "decisions": dict(ctl.decisions)}


def _scale_gate_leg(problems, cfg, params, maxlen, buckets):
    """Leg (c): growing re-runs the meshlint pre-spawn gate — a plan
    whose per-replica KV footprint exceeds PADDLE_TPU_DEVICE_MEM_CAP
    is REJECTED before any engine is built."""
    from paddle_tpu.serving.scale import (ScalePlanner,
                                          ScalePlanRejected)

    group, devs = _scale_group(cfg, params, slots=2, maxlen=maxlen,
                               buckets=buckets, name="scale-gate")
    planner = ScalePlanner(group, devices=devs, width=1)
    live0 = len(group.replicas)
    old = os.environ.get("PADDLE_TPU_DEVICE_MEM_CAP")
    # the cap env var is in MiB; 0.01 MiB is far below the tiny
    # model's per-replica KV floor, so the plan must be rejected
    os.environ["PADDLE_TPU_DEVICE_MEM_CAP"] = "0.01"
    rejected = None
    try:
        try:
            planner.grow(1)
            problems.append("planner grew past a 0.01 MiB device "
                            "mem cap — the verify gate did not run")
        except ScalePlanRejected as e:
            rejected = e
    finally:
        if old is None:
            os.environ.pop("PADDLE_TPU_DEVICE_MEM_CAP", None)
        else:
            os.environ["PADDLE_TPU_DEVICE_MEM_CAP"] = old
    if rejected is not None and rejected.reason != "verify":
        problems.append(f"grow rejection reason "
                        f"{rejected.reason!r}, expected 'verify'")
    if len(group.replicas) != live0:
        problems.append("a rejected grow still changed the live "
                        "replica count")
    ok = None
    try:                            # cap restored: the same plan goes
        planner.grow(1)
        ok = len(group.replicas)
    except ScalePlanRejected as e:
        problems.append(f"grow rejected with the cap restored: {e}")
    if ok is not None and ok != live0 + 1:
        problems.append(f"post-gate grow left {ok} replicas, "
                        f"expected {live0 + 1}")
    group.stop()
    return {"rejected": rejected is not None,
            "reason": None if rejected is None else rejected.reason,
            "rejections": planner.rejections,
            "live_after": len(group.replicas)}


def _scale_selftest_problems(problems):
    """The tpuscale CI gate: spike ramp with zero drops and zero
    scale-up recompiles, shed-only-at-ceiling, verify-gated grows."""
    maxlen, buckets = 16, (1, 2, 4)
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    return {"ramp": _scale_ramp_leg(problems, cfg, params, maxlen,
                                    buckets),
            "ceiling": _scale_ceiling_leg(problems, cfg, params,
                                          maxlen, buckets),
            "gate": _scale_gate_leg(problems, cfg, params, maxlen,
                                    buckets)}


def _scale_write_bench(section, payload):
    """Merge one section into BENCH_autoscale.json."""
    out_path = os.path.join(_REPO, "BENCH_autoscale.json")
    data = {}
    try:
        with open(out_path) as f:
            data = json.load(f)
    except Exception:  # noqa: BLE001 — first write / stale file
        data = {}
    data["schema"] = "paddle_tpu.bench.autoscale.v1"
    data[section] = payload
    try:
        with open(out_path, "w") as f:
            json.dump(data, f, indent=2)
    except OSError:
        return None
    return out_path


def run_selftest_scale(args):
    from paddle_tpu import telemetry
    telemetry.enable()
    problems = []
    info = _scale_selftest_problems(problems)
    result = {"mode": "selftest-scale", **info,
              "problems": problems, "ok": not problems}
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        r, c, g = info["ramp"], info["ceiling"], info["gate"]
        print(f"tpuserve selftest-scale: spike ramp 1->"
              f"{r['max_live']}->{r['final_live']} replicas, "
              f"{r['served']} served / {r['dropped']} dropped, "
              f"{r['scaleup_recompiles']} scale-up recompiles, "
              f"{r['spike_shadows']} spike shadows; ceiling "
              f"deferred={c['deferred_below_ceiling']} "
              f"entries={c['entries']} sheds={c['sheds']}; gate "
              f"rejected={g['rejected']} ({g['reason']})")
        for prob in problems:
            print(f"FAIL: {prob}", file=sys.stderr)
    return 2 if problems else 0


def run_bench_scale(args):
    """Static 1-replica vs SLO-autoscaled under the identical
    traffic_spike script: goodput, peak replicas, compiles. Manual
    drive — deterministic, honest about single-host CPU (the win is
    queueing delay absorbed, not raw FLOPs)."""
    import numpy as np

    from paddle_tpu import telemetry
    from paddle_tpu.resilience import chaos as _chaos
    from paddle_tpu.serving.batcher import RejectedError
    from paddle_tpu.serving.scale import (ScaleController, ScalePlanner,
                                          ScalePolicy)
    telemetry.enable()
    maxlen, buckets = 16, (1, 2, 4)
    cfg, exe, infer, logits, params = _decode_stack(maxlen=maxlen)
    cases = {}
    for label, autoscaled in (("static_1", False),
                              ("autoscaled", True)):
        group, devs = _scale_group(cfg, params, slots=2,
                                   maxlen=maxlen, buckets=buckets,
                                   name=f"bench-{label}",
                                   max_queue=256)
        ctl = None
        if autoscaled:
            ctl = ScaleController(
                group,
                ScalePolicy(["queue_per_replica > 4 -> up",
                             "queue_depth < 1 -> down"],
                            min_replicas=1, max_replicas=4,
                            up_cooldown_s=0.0, down_cooldown_s=0.0,
                            up_dwell=1, down_dwell=2),
                ScalePlanner(group, devices=devs, width=1))
        c0 = group.compile_count
        rng = np.random.RandomState(67)
        reqs = _decode_requests(rng, 24, maxlen, cfg.trg_vocab, 4)
        _chaos.configure("traffic_spike:at=4,x=4,len=8")
        futs, rejected, max_live = [], 0, 1
        probs = []
        t0 = time.monotonic()
        try:
            for src, n, mn in reqs:
                try:
                    futs.append(group.submit(src, src_len=n,
                                             max_new_tokens=mn))
                except RejectedError:
                    rejected += 1
                if ctl is not None:
                    ctl.tick()
                    max_live = max(max_live, len(group.replicas))
        finally:
            _chaos.reset()
        results = _pump_group(group, futs, probs, label, budget=4000)
        wall = time.monotonic() - t0
        tokens = sum(len(r.tokens) for r in results.values())
        case = {"replicas_peak": max_live,
                "served": len(results), "rejected": rejected,
                "dropped": len(probs),
                "compile_count": group.compile_count,
                "extra_compiles": group.compile_count - c0,
                "wall_s": round(wall, 3),
                "goodput_tokens_per_s": round(
                    tokens / max(wall, 1e-6), 1)}
        if ctl is not None:
            case["decisions"] = dict(ctl.decisions)
            ctl.stop()
        group.stop()
        cases[label] = case
        if not args.as_json:
            print(f"  {label:<12} {case['goodput_tokens_per_s']:>8} "
                  f"tok/s  peak {case['replicas_peak']} replicas  "
                  f"{case['extra_compiles']} extra compiles  "
                  f"{case['served']} served")
    result = {"mode": "bench-scale", "model": "transformer-tiny",
              "maxlen": maxlen,
              "fault": "traffic_spike:at=4,x=4,len=8",
              "cases": cases}
    result["artifact"] = _scale_write_bench("bench", result)
    if args.as_json:
        print(json.dumps(result))
    return 0


# ------------------------------------------------------------------ serve
def run_serve(args):
    from paddle_tpu import telemetry
    telemetry.enable()      # /metrics should always have data
    server, frontend = _build_server(args, args.model_dir, args.name)
    engine, version = server.registry.get(args.name)
    print(f"tpuserve: serving {args.name!r} v{version} from "
          f"{args.model_dir} at {frontend.url} "
          f"({engine.signature_count()} signatures warm, buckets "
          f"{server.config.batch.buckets})")
    try:
        frontend.serve_forever()
    except KeyboardInterrupt:
        print("draining...")
    finally:
        server.shutdown()
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        description="dynamic-batching model server over a "
                    "save_inference_model dir")
    p.add_argument("model_dir", nargs="?",
                   help="save_inference_model directory (not needed "
                        "with --selftest)")
    p.add_argument("--name", default="default",
                   help="model name in the /v1/models/<name> route")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8500,
                   help="0 picks an ephemeral port")
    p.add_argument("--buckets", default=None,
                   help="comma-separated batch buckets, e.g. 1,8,32 "
                        "(default: powers of two up to max batch)")
    p.add_argument("--max-batch-size", type=int, default=32)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--max-queue", type=int, default=256)
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="default per-request deadline")
    p.add_argument("--platform", default="env",
                   help="JAX_PLATFORMS to force before backend init "
                        "(default 'env': keep the environment's)")
    p.add_argument("--bench", action="store_true",
                   help="closed-loop load generator; implies no "
                        "serve-forever")
    p.add_argument("--duration", type=float, default=5.0,
                   help="--bench wall-clock seconds")
    p.add_argument("--concurrency", type=int, default=8,
                   help="--bench closed-loop client threads")
    p.add_argument("--selftest", action="store_true",
                   help="CI gate: serve mnist, mixed-shape concurrent "
                        "load, exit non-zero on compile explosion / "
                        "result mismatch / unbounded overload "
                        "(includes the decode leg)")
    p.add_argument("--selftest-decode", action="store_true",
                   dest="selftest_decode",
                   help="just the tpudecode CI gate: greedy_decode "
                        "parity under staggered arrivals, pinned "
                        "executable count, fast overload shed")
    p.add_argument("--bench-decode", action="store_true",
                   dest="bench_decode",
                   help="continuous decode vs the fixed-batch "
                        "greedy_decode path at ~10x overload; writes "
                        "BENCH_decode.json")
    p.add_argument("--selftest-farm", action="store_true",
                   dest="selftest_farm",
                   help="the tpufarm CI gate: replica-group parity + "
                        "compile pin, int8 KV parity bound, one-"
                        "replica-down chaos with zero drops, rolling "
                        "update serving both versions")
    p.add_argument("--bench-farm", action="store_true",
                   dest="bench_farm",
                   help="replica-group bench across 1 vs 2 replicas, "
                        "fp32 vs int8 KV, pooled vs disaggregated "
                        "prefill; writes BENCH_decode2.json")
    p.add_argument("--selftest-guard", action="store_true",
                   dest="selftest_guard",
                   help="the tpuguard CI gate: hedging cuts p99 "
                        "under replica_slow at token parity, a "
                        "flapping replica is ejected/probed/"
                        "re-admitted with zero drops, request_poison "
                        "fails alone, brownout sheds only the lowest "
                        "QoS class and recovers, the retry budget "
                        "caps resubmissions with a typed error")
    p.add_argument("--bench-guard", action="store_true",
                   dest="bench_guard",
                   help="p50/p99 with vs without hedging while "
                        "replica_slow throttles 1 of 2 replicas; "
                        "writes BENCH_guard.json and appends to the "
                        "bench history spine")
    p.add_argument("--selftest-scale", action="store_true",
                   dest="selftest_scale",
                   help="the tpuscale CI gate: a traffic_spike ramp "
                        "must scale 1->N->1 with zero drops and zero "
                        "scale-up recompiles, brownout must shed "
                        "ONLY at the device ceiling (deferred while "
                        "a free slice exists), and an over-cap grow "
                        "must be verify-rejected; writes no file")
    p.add_argument("--bench-scale", action="store_true",
                   dest="bench_scale",
                   help="static 1-replica vs SLO-autoscaled group "
                        "under the same traffic_spike script; merges "
                        "into BENCH_autoscale.json")
    p.add_argument("--slots", type=int, default=8,
                   help="--bench-decode slot-pool size")
    p.add_argument("--decode-max-len", type=int, default=32,
                   dest="decode_max_len",
                   help="--bench-decode sequence/cache length")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one machine-readable JSON line")
    args = p.parse_args(argv)

    if args.platform != "env":
        os.environ["JAX_PLATFORMS"] = args.platform
    if args.selftest_farm or args.bench_farm or args.selftest_guard \
            or args.bench_guard or args.selftest_scale \
            or args.bench_scale:
        # the farm slices real devices: give the CPU backend 8
        # virtual ones (must land before jax is first imported)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                (flags + " --xla_force_host_platform_device_count=8") \
                .strip()
    if args.selftest:
        return run_selftest(args)
    if args.selftest_decode:
        return run_selftest_decode(args)
    if args.bench_decode:
        return run_bench_decode(args)
    if args.selftest_farm:
        return run_selftest_farm(args)
    if args.bench_farm:
        return run_bench_farm(args)
    if args.selftest_guard:
        return run_selftest_guard(args)
    if args.bench_guard:
        return run_bench_guard(args)
    if args.selftest_scale:
        return run_selftest_scale(args)
    if args.bench_scale:
        return run_bench_scale(args)
    if not args.model_dir:
        p.error("model_dir is required unless --selftest / "
                "--selftest-decode / --bench-decode / "
                "--selftest-farm / --bench-farm / "
                "--selftest-guard / --bench-guard / "
                "--selftest-scale / --bench-scale")
    if args.bench:
        return run_bench(args)
    return run_serve(args)


if __name__ == "__main__":
    sys.exit(main())
