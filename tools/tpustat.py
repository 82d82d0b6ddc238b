#!/usr/bin/env python
"""tpustat — run a benchmark model N steps with telemetry on and print
the runtime metrics (the dynamic counterpart of tools/proglint.py).

Builds a model from benchmark/fluid/models/ exactly like
fluid_benchmark.py, runs the startup program, then runs N training
steps with `paddle_tpu.telemetry` enabled and metrics scoped to the
steady-state loop (the startup compile is excluded). Prints a metrics
table (or one JSON line with --json) and writes the merged Chrome
trace-event timeline, loadable in chrome://tracing / Perfetto.

--json validates the snapshot (counter arithmetic, histogram
consistency, trace well-formedness) and exits non-zero when the
metrics are malformed, so it doubles as a CI gate.

Fleet mode (--fleet): the multi-rank view. Reads a rank-snapshot spool
(telemetry.fleet — every multihost worker flushes rank*.snap.json
there), merges it coordinator-side, and prints per-rank step time,
collective volume, pipeline bubble %, and the straggler verdict from
one command; --trace writes the STITCHED multi-rank Chrome trace (one
pid per rank, clocks aligned on the shared barrier marker).
--fleet --selftest spawns two local single-process workers, merges
their spool, and validates the whole path — the CI gate
tests/test_fleet.py runs.

SLO mode (--slo, "tpuscope"): evaluate declarative perf rules
(telemetry.slo) against the run's snapshot — step_ms.p99 < X,
perf.mfu > Y, serving.queue_depth < Z — plus a MAD-based regression
gate of the newest BENCH_history.jsonl record per metric against its
rolling median (same robust statistics as the fleet straggler
detector). --rules takes a file (one rule per line, # comments) or an
inline ';'-separated list; --history points at an alternate spine.
--slo --selftest validates the whole layer in-process (rule parsing,
live MFU/goodput gauges on a tiny model, an injected step-time
regression that MUST be flagged) — the tier-1 CI gate.

Watch mode (--watch N, with --fleet SPOOL_DIR): re-render the fleet
table every N seconds with an MFU / goodput / step-budget header —
a live top(1) over the telemetry spool.

Examples:
  python tools/tpustat.py --model mnist --steps 20 --json
  python tools/tpustat.py --model resnet --steps 10 --prom
  python tools/tpustat.py --model mnist --platform cpu   # force CPU
  python tools/tpustat.py --fleet /run/spool --trace fleet.json
  python tools/tpustat.py --fleet --selftest --json      # CI gate
  python tools/tpustat.py --model mnist --slo --rules ci.rules
  python tools/tpustat.py --slo --selftest --json        # CI gate
  python tools/tpustat.py --fleet /run/spool --watch 5
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "benchmark", "fluid"))
sys.path.insert(0, os.path.join(_REPO, "tools"))

from proglint import ALL_MODELS, model_args  # noqa: E402


def build_model(name, args=None):
    """(main_program, startup_program, loss, feed_fn) — the proglint
    builder plus the model's synthetic feed generator, which tpustat
    needs to actually run the steps."""
    import paddle_tpu as fluid
    args = args or model_args()
    model_mod = __import__(f"models.{name}", fromlist=["get_model"])
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        with fluid.unique_name.guard():
            loss, feed_fn = model_mod.get_model(args)
            opt = fluid.optimizer.Adam(args.learning_rate) \
                if name == "machine_translation" \
                else fluid.optimizer.Momentum(args.learning_rate, 0.9)
            opt.minimize(loss)
    return main_p, startup_p, loss, feed_fn


def validate_metrics(snap, steps):
    """Structural checks over a telemetry snapshot from a `steps`-long
    cached run. Returns a list of problem strings (empty = healthy)."""
    problems = []

    def need(name):
        if name not in snap:
            problems.append(f"missing metric {name!r}")
            return None
        return snap[name]

    compiles = need("executor.compile_count")
    hits = need("executor.cache_hit_count") \
        if "executor.cache_hit_count" in snap else 0
    n_steps = need("executor.steps")
    for name, v in snap.items():
        if isinstance(v, dict):       # histogram
            bucket_total = sum(v.get("buckets", {}).values())
            if bucket_total != v.get("count"):
                problems.append(
                    f"histogram {name!r}: bucket total {bucket_total} "
                    f"!= count {v.get('count')}")
            if v.get("count", 0) < 0 or v.get("sum", 0) < 0:
                problems.append(f"histogram {name!r}: negative count/sum")
        elif isinstance(v, bool) or not isinstance(v, (int, float)):
            problems.append(f"metric {name!r}: non-numeric value {v!r}")
    if isinstance(compiles, int) and isinstance(n_steps, int):
        if n_steps != steps:
            problems.append(
                f"executor.steps {n_steps} != requested steps {steps}")
        if compiles + hits != steps:
            problems.append(
                f"compile_count {compiles} + cache_hit_count {hits} "
                f"!= steps {steps}")
        if compiles < 1:
            problems.append("no compile recorded")
    h = snap.get("executor.step_seconds")
    if isinstance(h, dict) and h.get("count") != steps:
        problems.append(
            f"executor.step_seconds count {h.get('count')} != {steps}")
    return problems


def _fmt_value(v):
    if isinstance(v, dict):
        m = f" mean={v['mean']:.4g}s max={v['max']:.4g}s" \
            if v.get("count") else ""
        return f"hist count={v['count']}{m}"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


# ------------------------------------------------------------------ fleet

def _fleet_worker(rank, spool):
    """Hidden mode: one local single-process 'rank' for the selftest —
    runs a tiny training loop with telemetry + fleet configured, records
    one instrumented collective and the pipeline bubble gauge, then
    flushes its rank snapshot to the spool. Rank 1 injects synthetic
    slow-step observations so the straggler detector has a
    deterministic culprit regardless of CI box load."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    import paddle_tpu as fluid
    from paddle_tpu import layers, telemetry
    from paddle_tpu.parallel import collective, pipeline

    telemetry.enable()
    telemetry.fleet.configure(rank=rank, world=2, spool_dir=spool)

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        with fluid.unique_name.guard():
            x = layers.data("x", shape=[8])
            y = layers.data("y", shape=[4])
            pred = layers.fc(x, size=4)
            loss = layers.mean(layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup_p, feed={}, fetch_list=[])
    telemetry.reset()               # steady state: startup compile off
    telemetry.fleet.mark_clock()    # the shared-barrier marker analog

    rng = np.random.RandomState(rank)
    for _ in range(5):
        feed = {"x": rng.randn(8, 8).astype("float32"),
                "y": rng.randn(8, 4).astype("float32")}
        exe.run(main_p, feed=feed, fetch_list=[loss])

    # one collective through the instrumented wrappers (trace-time
    # accounting; a 1-device axis is enough for the counters)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    f = jax.jit(jax.shard_map(
        lambda v: collective.all_reduce(v, axis_name="dp"),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False))
    np.asarray(f(jnp.ones((4, 8), jnp.float32)))

    # one int8 gradient sync through the gradsync policy layer, so the
    # fleet report's raw-vs-wire gauges have known per-rank values:
    # 512 f32 grads -> raw 2048 B, wire 512 B codes + 2 block scales
    # (8 B) = 520 B, ratio 2048/520
    from paddle_tpu.parallel import gradsync
    pol = gradsync.parse_policy("int8:ef=0")
    g2 = jax.jit(jax.shard_map(
        lambda v: gradsync.sync_gradients({"w": v}, {}, pol, dp=1)[0]["w"],
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    np.asarray(g2(jnp.ones((64, 8), jnp.float32)))

    # pipeline bubble gauge via the same helper PipelineTrainer uses
    pipeline.record_bubble("gpipe", n_microbatch=4, n_stages=2)

    # sharded-embedding engine gauges with known values, so the fleet
    # merge of the embed columns is pinned (parallel/sparse.py writes
    # these per table; here the selftest plays the engine's role)
    telemetry.gauge("embed.big_table.rows").set(64)
    telemetry.gauge("embed.big_table.unique_ratio").set(0.5)
    telemetry.counter("embed.big_table.exchange_bytes").inc(4096)

    # request-trace exemplar gauges with known values — two completed
    # traces, one hedge-triggered, through the REAL reqtrace publish
    # path — so the fleet traces rollup (the tpustat --watch header
    # line) is pinned end to end
    telemetry.reqtrace_enable()
    rt = telemetry.reqtrace
    rt.trace_begin(f"w{rank}-hedged")
    rt.flag(f"w{rank}-hedged", "hedge")
    rt.trace_end(f"w{rank}-hedged")
    rt.trace_begin(f"w{rank}-plain")
    rt.trace_end(f"w{rank}-plain")

    if rank == 1:
        # synthetic straggler: this "host" reports pathologically slow
        # steps, so the detector path is exercised deterministically
        h = telemetry.histogram("executor.step_seconds")
        for _ in range(10):
            h.observe(2.0)

    path = telemetry.fleet.write_rank_snapshot()
    print(json.dumps({"rank": rank, "snapshot": path, "ok": True}))
    return 0


def _validate_fleet_report(rep, collector):
    """Structural checks over a merged fleet report (CI-gate grade)."""
    problems = []
    if len(rep["ranks"]) < 1:
        problems.append("no ranks in spool")
    for r in rep["ranks"]:
        pr = rep["per_rank"].get(str(r))
        if pr is None:
            problems.append(f"rank {r} missing from per_rank")
            continue
        if pr["step_seconds_mean"] is None:
            problems.append(f"rank {r}: no step timing")
    merged = rep["merged"]
    for name, ent in merged.items():
        if ent["kind"] == "histogram":
            v = ent["value"]
            if sum(v.get("buckets", {}).values()) != v.get("count"):
                problems.append(
                    f"merged histogram {name!r}: bucket total != count")
        elif ent["kind"] == "gauge":
            if len(ent.get("per_rank", {})) == 0:
                problems.append(f"merged gauge {name!r}: no per-rank "
                                "values retained")
    strag = rep.get("straggler") or {}
    if "verdict" not in strag:
        problems.append("no straggler verdict")
    try:
        trace = json.loads(json.dumps(collector.stitched_trace()))
        pids = {e.get("pid") for e in trace["traceEvents"]
                if e.get("ph") == "X"}
        if not pids.issuperset(set(rep["ranks"])):
            problems.append(
                f"stitched trace pids {sorted(pids)} do not cover "
                f"ranks {rep['ranks']}")
        for e in trace["traceEvents"]:
            if e.get("ph") == "X" and ("ts" not in e or "dur" not in e):
                problems.append("stitched X event missing ts/dur")
                break
    except (ValueError, KeyError) as e:
        problems.append(f"stitched trace does not round-trip: {e}")
    return problems


def _print_fleet_table(rep):
    strag = rep.get("straggler") or {}
    flagged = set(strag.get("flagged") or [])
    print(f"tpufleet: {len(rep['ranks'])} ranks "
          f"(declared process_count {rep['process_count']}), "
          f"verdict: {strag.get('verdict', '?')}")
    hdr = (f"  {'rank':<5} {'host':<12} {'steps':>5} {'step_ms':>9} "
           f"{'mfu%':>6} "
           f"{'coll#':>6} {'coll_KB':>8} {'bubble%':>8} "
           f"{'gs_raw_KB':>10} {'gs_wire_KB':>11} {'gs_x':>6} "
           f"{'emb_rows':>9} {'uniq%':>6} {'exch_KB':>8} "
           f"{'hbm_MB':>8} {'peak_MB':>8}  verdict")
    print(hdr)
    for r in rep["ranks"]:
        pr = rep["per_rank"][str(r)]
        mean = pr["step_seconds_mean"]
        bubble = pr["bubble_fraction"]
        ratio = pr.get("gradsync_ratio")
        uniq = pr.get("embed_unique_ratio")
        mfu = pr.get("mfu")
        hbm = pr.get("hbm_bytes")
        hbm_pk = pr.get("hbm_peak_bytes")
        print(f"  {r:<5} {str(pr.get('hostname') or '-')[:12]:<12} "
              f"{pr['steps']:>5} "
              f"{(mean * 1e3 if mean else 0):>9.2f} "
              f"{(f'{mfu * 100:.1f}' if mfu else '-'):>6} "
              f"{pr['collective_calls']:>6} "
              f"{pr['collective_bytes'] / 1024:>8.1f} "
              f"{(bubble * 100 if bubble is not None else 0):>8.1f} "
              f"{pr.get('gradsync_raw_bytes', 0) / 1024:>10.1f} "
              f"{pr.get('gradsync_wire_bytes', 0) / 1024:>11.1f} "
              f"{(f'{ratio:.2f}' if ratio else '-'):>6} "
              f"{pr.get('embed_rows', 0):>9} "
              f"{(f'{uniq * 100:.1f}' if uniq is not None else '-'):>6} "
              f"{pr.get('embed_exchange_bytes', 0) / 1024:>8.1f} "
              f"{(f'{hbm / 1e6:.1f}' if hbm else '-'):>8} "
              f"{(f'{hbm_pk / 1e6:.1f}' if hbm_pk else '-'):>8}  "
              f"{'STRAGGLER' if r in flagged else 'ok'}")
    if rep["collectives"]:
        parts = [f"{op} x{d.get('count', 0)} "
                 f"({d.get('bytes', 0) / 1024:.1f} KB)"
                 for op, d in sorted(rep["collectives"].items())]
        print("  collectives (trace-time): " + ", ".join(parts))
    _print_replica_table(rep)
    if strag.get("hint"):
        print(f"  hint: {strag['hint']}")


# serving.replica.<i>.guard_state gauge codes (guard/health.py
# STATE_CODES) — rendered in the replica table's state column
_GUARD_STATES = {0.0: "ok", 1.0: "probation", 2.0: "EJECTED",
                 3.0: "half-open"}

# scale.last_decision gauge -> label (serving.scale DECISION_CODES)
_SCALE_DECISIONS = {0.0: "hold", 1.0: "up", 2.0: "down",
                    3.0: "ceiling", 4.0: "rejected", 5.0: "cooldown"}


def _print_replica_table(rep):
    """Serving-farm sub-table: one row per decode replica, from the
    serving.replica.<i>.* gauges (ranks serving no farm print
    nothing), plus one guard line per rank running overload defense
    (serving.guard.* rollups) and one autoscaler line per rank with a
    live ScaleController (scale.* rollups: target vs live, last
    decision + triggering rule, cooldown remaining)."""
    rows = []
    for r in rep["ranks"]:
        pr = rep["per_rank"][str(r)]
        for idx, d in sorted(
                (pr.get("serving_replicas") or {}).items(),
                key=lambda kv: int(kv[0]) if kv[0].isdigit() else 0):
            rows.append((r, idx, d))
    if not rows:
        return
    print(f"  serving replicas: {len(rows)}")
    print(f"    {'rank':<5} {'rep':>3} {'ver':>4} {'slots':>7} "
          f"{'queue':>6} {'kv_MB':>7} {'tokens':>8} {'tok/s':>8} "
          f"{'restarts':>8}  state")
    for r, idx, d in rows:
        state = "down" if not d.get("alive", 1.0) else (
            "draining" if d.get("draining") else "ok")
        if state == "ok" and "guard_state" in d:
            state = _GUARD_STATES.get(d["guard_state"], "ok")
        print(f"    {r:<5} {idx:>3} {int(d.get('version', 1)):>4} "
              f"{int(d.get('slots_in_use', 0)):>3}/"
              f"{int(d.get('num_slots', 0)):<3} "
              f"{int(d.get('queue_depth', 0)):>6} "
              f"{d.get('kv_cache_bytes', 0) / 1e6:>7.2f} "
              f"{int(d.get('tokens_total', 0)):>8} "
              f"{d.get('goodput_tps', 0.0):>8.1f} "
              f"{int(d.get('restarts', 0)):>8}  {state}")
    for r in rep["ranks"]:
        g = rep["per_rank"][str(r)].get("serving_guard") or {}
        if not g:
            continue
        p99 = g.get("p99_ms")
        print(f"    guard[rank {r}]: "
              f"{'BROWNOUT' if g.get('brownout') else 'normal'} "
              f"ejections={int(g.get('ejections', 0))} "
              f"readmissions={int(g.get('readmissions', 0))} "
              f"hedges={int(g.get('hedges', 0))} "
              f"(wins={int(g.get('hedge_wins', 0))}) "
              f"resubmits={int(g.get('resubmits', 0))} "
              f"sheds={int(g.get('brownout_sheds', 0))} "
              f"p99={f'{p99:.1f}ms' if p99 is not None else '-'}")
    for r in rep["ranks"]:
        s = rep["per_rank"][str(r)].get("serving_scale") or {}
        if not s:
            continue
        dec = _SCALE_DECISIONS.get(s.get("last_decision", 0.0),
                                   "hold")
        rule = s.get("last_rule", -1.0)
        if rule is not None and rule >= 0:
            dec = f"{dec}(rule#{int(rule)})"
        cool = s.get("cooldown_remaining_s", 0.0) or 0.0
        print(f"    scale[rank {r}]: "
              f"target={int(s.get('target_replicas', 0))} "
              f"live={int(s.get('live_replicas', 0))} "
              f"last={dec} "
              f"cooldown={cool:.1f}s "
              f"{'AT-CEILING' if s.get('at_ceiling') else 'headroom'} "
              f"free_dev={int(s.get('free_devices', 0))} "
              f"ups={int(s.get('ups', 0))} "
              f"downs={int(s.get('downs', 0))}")


def _fleet_report(spool, as_json, trace_path):
    """tpustat --fleet SPOOL_DIR: merge the rank spool and report."""
    from paddle_tpu.telemetry import fleet as tfleet
    coll = tfleet.FleetCollector()
    try:
        coll.collect(spool)
    except (OSError, ValueError) as e:
        print(f"tpustat --fleet: {e}", file=sys.stderr)
        return 2
    rep = coll.report()
    problems = _validate_fleet_report(rep, coll)
    if trace_path:
        with open(trace_path, "w") as f:
            json.dump(coll.stitched_trace(), f)
    if as_json:
        print(json.dumps(dict(rep, problems=problems,
                              ok=not problems), default=str))
    else:
        _print_fleet_table(rep)
        if trace_path:
            print(f"  stitched trace: {trace_path}")
        for prob in problems:
            print(f"MALFORMED: {prob}", file=sys.stderr)
    return 2 if problems else 0


def _fleet_selftest(as_json, trace_path):
    """tpustat --fleet --selftest: spawn 2 local worker subprocesses,
    merge their spool, validate the merged snapshot + stitched trace.
    Exit 0 iff everything is well-formed — the tier-1 CI gate."""
    import subprocess
    import tempfile
    spool = tempfile.mkdtemp(prefix="tpufleet_selftest_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("PADDLE_TPU_TELEMETRY", "PADDLE_TPU_TELEMETRY_DIR",
              "PADDLE_TPU_FLEET_RANK", "PADDLE_TPU_FLEET_WORLD",
              "PADDLE_TPU_FLEET_DIR", "XLA_FLAGS"):
        env.pop(k, None)
    me = os.path.abspath(__file__)
    problems = []
    logs, procs = [], []
    for r in (0, 1):
        log = os.path.join(spool, f"worker{r}.log")
        logs.append(log)
        with open(log, "w") as lf:
            procs.append(subprocess.Popen(
                [sys.executable, me, "--fleet-worker", str(r),
                 "--spool", spool],
                stdout=lf, stderr=subprocess.STDOUT, env=env,
                cwd=_REPO))
    for r, p in enumerate(procs):
        try:
            rc = p.wait(timeout=240)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            rc = -9
        if rc != 0:
            tail = open(logs[r]).read()[-1200:]
            problems.append(f"worker {r} rc={rc}: {tail}")

    from paddle_tpu.telemetry import fleet as tfleet
    rep, strag = {}, {}
    if not problems:
        coll = tfleet.FleetCollector()
        try:
            coll.collect(spool)
            rep = coll.report()
            strag = rep["straggler"]
            problems += _validate_fleet_report(rep, coll)
            # the selftest knows exactly what the workers did — pin it
            if rep["ranks"] != [0, 1]:
                problems.append(f"expected ranks [0, 1], got "
                                f"{rep['ranks']}")
            # per worker: one fp32 all_reduce (4x8 f32 = 128 B) plus
            # one int8 gradsync all_reduce (512 codes + 2 fp32 block
            # scales = 520 B)
            ar = rep["merged"].get("collective.all_reduce.count")
            if not ar or ar["value"] != 4:
                problems.append(
                    f"merged collective.all_reduce.count != 4: {ar}")
            ab = rep["merged"].get("collective.all_reduce.bytes")
            if not ab or ab["value"] != 2 * (128 + 520):
                problems.append(
                    f"merged collective.all_reduce.bytes != 1296: {ab}")
            # gradsync gauges must merge correctly across ranks:
            # counters sum, the per-rank compression ratio is retained
            graw = rep["merged"].get("gradsync.raw_bytes")
            if not graw or graw["value"] != 2 * 2048:
                problems.append(
                    f"merged gradsync.raw_bytes != 4096: {graw}")
            gwire = rep["merged"].get("gradsync.wire_bytes")
            if not gwire or gwire["value"] != 2 * 520:
                problems.append(
                    f"merged gradsync.wire_bytes != 1040: {gwire}")
            gratio = rep["merged"].get("gradsync.compression_ratio")
            expect_ratio = 2048 / 520
            if (not gratio or gratio["kind"] != "gauge"
                    or sorted(gratio.get("per_rank", {})) != ["0", "1"]
                    or any(abs(v - expect_ratio) > 1e-6
                           for v in gratio["per_rank"].values())):
                problems.append(
                    f"merged gradsync.compression_ratio malformed: "
                    f"{gratio}")
            for r in (0, 1):
                pr = rep["per_rank"][str(r)]
                if pr.get("gradsync_raw_bytes") != 2048 \
                        or pr.get("gradsync_wire_bytes") != 520:
                    problems.append(
                        f"rank {r} gradsync raw/wire bytes wrong: "
                        f"{pr.get('gradsync_raw_bytes')}/"
                        f"{pr.get('gradsync_wire_bytes')}")
            for r in (0, 1):
                bub = rep["per_rank"][str(r)]["bubble_fraction"]
                if bub is None or abs(bub - 0.2) > 1e-9:
                    problems.append(
                        f"rank {r} bubble_fraction != 0.2: {bub}")
            # sharded-embedding columns: per-rank rollup (rows 64,
            # unique 0.5, 4096 exchange bytes) + table detail + the
            # counter summing across ranks in the merge
            for r in (0, 1):
                pr = rep["per_rank"][str(r)]
                if pr.get("embed_rows") != 64 \
                        or pr.get("embed_unique_ratio") != 0.5 \
                        or pr.get("embed_exchange_bytes") != 4096:
                    problems.append(
                        f"rank {r} embed columns wrong: "
                        f"{pr.get('embed_rows')}/"
                        f"{pr.get('embed_unique_ratio')}/"
                        f"{pr.get('embed_exchange_bytes')}")
                det = pr.get("embed_tables", {}).get("big_table", {})
                if det.get("rows") != 64:
                    problems.append(
                        f"rank {r} embed_tables detail wrong: {det}")
            ex = rep["merged"].get("embed.big_table.exchange_bytes")
            if not ex or ex["value"] != 2 * 4096:
                problems.append(
                    f"merged embed exchange_bytes != 8192: {ex}")
            if strag.get("flagged") != [1]:
                problems.append(
                    f"straggler detector should flag rank 1, got "
                    f"{strag.get('flagged')}")
            # request-trace rollup: each worker completed 2 traces,
            # 1 hedge-triggered (serving.trace.* gauges from the
            # reqtrace publish path), and the --watch header renders
            # the fleet-wide traces line from them
            for r in (0, 1):
                t = rep["per_rank"][str(r)].get("serving_traces") or {}
                if (t.get("seen"), t.get("kept"),
                        t.get("trigger.hedge")) != (2, 1, 1):
                    problems.append(
                        f"rank {r} serving_traces wrong: {t}")
            if "traces: 2/4 kept (hedge=2)" not in _watch_header(rep):
                problems.append(
                    "watch header is missing the traces rollup line")
            st = coll.stitched_trace()
            if st["fleetAlignment"] != "marker":
                problems.append(
                    f"expected marker clock alignment, got "
                    f"{st['fleetAlignment']}")
            # idempotent re-merge: same spool again, same totals —
            # and the traces line (gauges, not counters) must not
            # double when the same rank envelopes land twice
            coll.collect(spool)
            rep2 = coll.report()
            ar2 = rep2["merged"]["collective.all_reduce.count"]
            if ar2["value"] != 4:
                problems.append(
                    f"re-merge not idempotent: count {ar2['value']}")
            if "traces: 2/4 kept (hedge=2)" not in _watch_header(rep2):
                problems.append(
                    "traces rollup not idempotent on re-merge")
            if trace_path:
                with open(trace_path, "w") as f:
                    json.dump(st, f)
        except (OSError, ValueError, KeyError) as e:
            problems.append(f"collect/report failed: "
                            f"{type(e).__name__}: {e}")

    result = {"selftest": "fleet", "spool": spool,
              "ranks": rep.get("ranks"),
              "straggler": strag.get("verdict"),
              "problems": problems, "ok": not problems}
    if as_json:
        print(json.dumps(result, default=str))
    else:
        if rep:
            _print_fleet_table(rep)
        for prob in problems:
            print(f"SELFTEST FAIL: {prob}", file=sys.stderr)
        if not problems:
            print("fleet selftest OK")
    return 2 if problems else 0


# ------------------------------------------------------------ slo / watch

def _default_history_path():
    return os.path.join(_REPO, "BENCH_history.jsonl")


def _load_rules(rules_arg):
    """--rules: a file of one rule per line (# comments) or an inline
    ';'-separated list; default ruleset otherwise."""
    from paddle_tpu.telemetry import slo
    if not rules_arg:
        return list(slo.DEFAULT_RULES)
    if os.path.exists(rules_arg):
        with open(rules_arg) as f:
            lines = f.read().splitlines()
    else:
        lines = rules_arg.split(";")
    return [ln.strip() for ln in lines
            if ln.strip() and not ln.strip().startswith("#")]


def _slo_gate(snap, rules_arg, history_path, platform=None):
    """Evaluate rules against `snap` + regression-gate the history
    spine. Returns (problems, detail_dict)."""
    from paddle_tpu.telemetry import slo
    problems = []
    rules = _load_rules(rules_arg)
    try:
        report = slo.evaluate(rules, snap=snap)
    except ValueError as e:
        return [f"bad SLO rule: {e}"], {}
    for r in report.violations:
        problems.append(f"SLO violated: {r.rule.text} "
                        f"(observed {r.observed:g})")
    history_path = history_path or _default_history_path()
    records = slo.load_history(history_path)
    gate = slo.history_gate(records, platform=platform)
    for reg in gate["regressions"]:
        problems.append(
            f"perf regression: {reg['metric']} = {reg['current']:g} "
            f"vs rolling median {reg['median']:g} "
            f"(threshold {reg['threshold']:g}, n={reg['n']})")
    detail = {"slo": report.to_dict(),
              "history": {"path": history_path,
                          "records": len(records),
                          "checked": gate["checked"],
                          "regressions": gate["regressions"]}}
    return problems, detail


def _slo_selftest(as_json, history_path):
    """tpustat --slo --selftest: validate the tpuscope layer end to end
    in-process — live MFU/goodput gauges on a tiny model, rule parsing,
    and the regression gate flagging an injected step-time regression.
    Exit 0 iff everything holds — the tier-1 CI gate."""
    import tempfile
    problems = []

    # 1) rule parsing round-trips (aliases, stats, operators)
    from paddle_tpu.telemetry import slo
    r = slo.parse_rule("step_ms.p99 < 250")
    if (r.metric, r.stat, r.scale, r.threshold) != \
            ("executor.step_seconds", "p99", 1e3, 250.0):
        problems.append(f"rule parse wrong: {r.metric}/{r.stat}/"
                        f"{r.scale}/{r.threshold}")
    r = slo.parse_rule("perf.mfu > 0.3")
    if (r.metric, r.stat) != ("perf.mfu", "value"):
        problems.append(f"dotted metric parse wrong: "
                        f"{r.metric}/{r.stat}")
    try:
        slo.parse_rule("nonsense ~ 3")
        problems.append("bad rule did not raise")
    except ValueError:
        pass

    # 2) live gauges: a tiny training loop must produce perf.mfu > 0
    # (synthetic peak: CPU has no table entry) and pass generous rules
    os.environ["PADDLE_TPU_PEAK_FLOPS"] = "1e12"
    try:
        import numpy as np
        import paddle_tpu as fluid
        from paddle_tpu import layers, telemetry
        telemetry.enable()
        main_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup_p):
            with fluid.unique_name.guard():
                x = layers.data("x", shape=[8])
                y = layers.data("y", shape=[4])
                pred = layers.fc(x, size=4)
                loss = layers.mean(
                    layers.square_error_cost(pred, y))
                fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup_p, feed={}, fetch_list=[])
        telemetry.reset()
        rng = np.random.RandomState(0)
        for _ in range(6):
            feed = {"x": rng.randn(8, 8).astype("float32"),
                    "y": rng.randn(8, 4).astype("float32")}
            exe.run(main_p, feed=feed, fetch_list=[loss])
        snap = telemetry.snapshot()
        live = slo.evaluate(list(slo.DEFAULT_RULES)
                            + ["perf.mfu > 0",
                               "perf.goodput.examples_per_s > 0",
                               "executor.steps >= 5"], snap=snap)
        if not live.ok:
            problems.append("live rules failed:\n" + str(live))
        mfu = snap.get("perf.mfu")
        if not mfu or mfu <= 0:
            problems.append(f"perf.mfu gauge not live: {mfu}")
    finally:
        os.environ.pop("PADDLE_TPU_PEAK_FLOPS", None)

    # 3) the regression gate MUST flag injected regressions and MUST
    # pass a clean series (both directions)
    if not slo.check_regression([10.0] * 8, 100.0,
                                direction="lower")["regressed"]:
        problems.append("injected step-time regression not flagged")
    if not slo.check_regression([1000.0] * 8, 100.0,
                                direction="higher")["regressed"]:
        problems.append("injected throughput regression not flagged")
    if slo.check_regression([10.0, 10.1, 9.9, 10.0, 10.2], 10.1,
                            direction="lower")["regressed"]:
        problems.append("clean step-time series falsely flagged")

    # 4) history spine: append/load round-trip + end-to-end gate over
    # a file with one injected step-time regression
    with tempfile.TemporaryDirectory(prefix="tpuslo_") as td:
        hist = os.path.join(td, "hist.jsonl")
        base = {"schema": slo.HISTORY_SCHEMA, "platform": "cpu",
                "unit": "ms", "stage": "deepfm"}
        recs = [dict(base, metric="deepfm_step_ms", value=10.0 + 0.01 * i)
                for i in range(8)]
        recs.append(dict(base, metric="deepfm_step_ms", value=100.0))
        slo.append_history(hist, recs)
        loaded = slo.load_history(hist)
        if len(loaded) != len(recs):
            problems.append(f"history round-trip lost records: "
                            f"{len(loaded)} != {len(recs)}")
        gate = slo.history_gate(loaded)
        if gate["ok"] or not any(
                g["metric"] == "deepfm_step_ms"
                for g in gate["regressions"]):
            problems.append(
                f"history gate missed the injected step-time "
                f"regression: {gate}")
        # clean spine passes
        clean = [dict(base, metric="deepfm_step_ms",
                      value=10.0 + 0.01 * i) for i in range(9)]
        if not slo.history_gate(clean)["ok"]:
            problems.append("history gate flagged a clean series")

    result = {"selftest": "slo", "problems": problems,
              "ok": not problems}
    if as_json:
        print(json.dumps(result, default=str))
    else:
        for prob in problems:
            print(f"SELFTEST FAIL: {prob}", file=sys.stderr)
        if not problems:
            print("slo selftest OK")
    return 2 if problems else 0


_BUDGET_HISTS = (
    ("feed_put", "executor.feed_put_seconds"),
    ("dispatch", "executor.step_seconds"),
    ("stall", "executor.pending_wait_seconds"),
    ("readback", "executor.fetch_readback_seconds"),
    ("check", "executor.finite_check_seconds"),
)


def _merged_value(merged, name):
    ent = merged.get(name)
    return ent.get("value") if isinstance(ent, dict) else None


def _watch_header(rep):
    """The mfu / goodput / step-budget summary lines above the fleet
    table in --watch mode."""
    from paddle_tpu.telemetry import registry
    merged = rep.get("merged", {})
    mfus = [pr["mfu"] for pr in rep.get("per_rank", {}).values()
            if pr.get("mfu")]
    goodput = [pr["goodput_examples_per_s"]
               for pr in rep.get("per_rank", {}).values()
               if pr.get("goodput_examples_per_s")]
    step_h = _merged_value(merged, "executor.step_seconds")
    p99 = registry.quantile_from_buckets(step_h, 0.99) \
        if isinstance(step_h, dict) else None
    lines = [
        "  mfu: " + (f"{sum(mfus) / len(mfus) * 100:.1f}% (mean of "
                     f"{len(mfus)} ranks)" if mfus else "n/a")
        + "   goodput: "
        + (f"{sum(goodput):.1f} examples/s" if goodput else "n/a")
        + "   step p99: "
        + (f"{p99 * 1e3:.2f} ms" if p99 else "n/a")]
    sums = []
    for label, name in _BUDGET_HISTS:
        v = _merged_value(merged, name)
        sums.append((label, float(v.get("sum", 0.0))
                     if isinstance(v, dict) else 0.0))
    total = sum(s for _, s in sums)
    if total > 0:
        width = 24
        parts = []
        for label, s in sums:
            if s <= 0:
                continue
            bar = "#" * max(1, round(s / total * width))
            parts.append(f"{label} {s / total * 100:4.1f}% {bar}")
        lines.append("  step budget: " + "  ".join(parts))
    # request-trace exemplar pressure: sum of the per-rank
    # serving.trace.* gauges (fleet rollup). Gauges, so the line is
    # stable when the same spool is merged twice.
    tr = [pr.get("serving_traces") or {}
          for pr in rep.get("per_rank", {}).values()]
    tr = [t for t in tr if t]
    if tr:
        seen = sum(int(t.get("seen", 0)) for t in tr)
        kept = sum(int(t.get("kept", 0)) for t in tr)
        mix = {}
        for t in tr:
            for k, v in t.items():
                if k.startswith("trigger."):
                    name = k[len("trigger."):]
                    mix[name] = mix.get(name, 0) + int(v)
        mixs = " ".join(f"{k}={v}" for k, v in sorted(mix.items()))
        lines.append(f"  traces: {kept}/{seen} kept"
                     + (f" ({mixs})" if mixs else ""))
    # memory-ledger rollup (PR-20): worst rank's live and peak HBM,
    # from the per-rank memledger.* / device.* gauges
    hbms = [(int(pr.get("hbm_bytes") or 0),
             int(pr.get("hbm_peak_bytes") or 0), r)
            for r, pr in rep.get("per_rank", {}).items()
            if pr.get("hbm_bytes") or pr.get("hbm_peak_bytes")]
    if hbms:
        cur, pk, worst = max(hbms, key=lambda t: t[1] or t[0])
        lines.append(f"  hbm: {cur / 1e6:.1f} MB live, "
                     f"{pk / 1e6:.1f} MB peak "
                     f"(worst rank {worst}, {len(hbms)} reporting)")
    return "\n".join(lines)


def _watch(spool, interval, iterations, as_json):
    """tpustat --fleet SPOOL --watch N: re-render the fleet view every
    N seconds. `iterations` bounds the loop (None = forever)."""
    import time as _time
    from paddle_tpu.telemetry import fleet as tfleet
    i = 0
    while True:
        coll = tfleet.FleetCollector()
        err = None
        rep = None
        try:
            coll.collect(spool)
            rep = coll.report()
        except (OSError, ValueError) as e:
            err = f"{type(e).__name__}: {e}"
        if as_json:
            out = {"iteration": i, "ok": err is None}
            if rep:
                out["ranks"] = rep["ranks"]
                out["per_rank"] = rep["per_rank"]
            if err:
                out["error"] = err
            print(json.dumps(out, default=str), flush=True)
        else:
            if sys.stdout.isatty():
                sys.stdout.write("\x1b[2J\x1b[H")   # clear + home
            print(f"tpustat --watch (every {interval:g}s, "
                  f"iteration {i})")
            if err:
                print(f"  spool not readable yet: {err}")
            else:
                print(_watch_header(rep))
                _print_fleet_table(rep)
            sys.stdout.flush()
        i += 1
        if iterations is not None and i >= iterations:
            return 0
        _time.sleep(interval)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="runtime telemetry over a benchmark model")
    p.add_argument("--model", default="mnist", choices=ALL_MODELS)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--async-steps", type=int, default=0,
                   help="run the step loop through the tpupipe async "
                        "window (Executor.run(async_steps=K)); 0 = "
                        "synchronous")
    p.add_argument("--platform", default="env",
                   help="JAX_PLATFORMS to force before backend init "
                        "(default 'env': keep the environment's)")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="Chrome trace output "
                        "(default /tmp/tpustat_<model>.trace.json)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one machine-readable JSON line; exit non-zero "
                        "on malformed metrics")
    p.add_argument("--prom", action="store_true",
                   help="also print the Prometheus text exposition")
    p.add_argument("--profile-device", action="store_true",
                   help="run three more steps under a fluid.profiler "
                        "session and print its report: device time by "
                        "Fluid op and name scope, host spans with self "
                        "time and the idle time under each")
    p.add_argument("--fleet", nargs="?", const="", default=None,
                   metavar="SPOOL_DIR",
                   help="fleet mode: merge a telemetry.fleet rank "
                        "spool and print per-rank step time, "
                        "collective volume, bubble %%, and the "
                        "straggler verdict (--trace writes the "
                        "stitched multi-rank timeline)")
    p.add_argument("--selftest", action="store_true",
                   help="with --fleet or --slo: validate the layer "
                        "end to end (CI gate)")
    p.add_argument("--slo", action="store_true",
                   help="evaluate SLO rules against the run's metrics "
                        "and regression-gate BENCH_history.jsonl; "
                        "exit 2 on violation (tpuscope)")
    p.add_argument("--rules", default=None,
                   help="SLO rules: a file (one per line, # comments) "
                        "or an inline ';'-separated list; default: "
                        "telemetry.slo.DEFAULT_RULES")
    p.add_argument("--history", default=None, metavar="PATH",
                   help="perf-history spine for the --slo regression "
                        "gate (default <repo>/BENCH_history.jsonl)")
    p.add_argument("--watch", type=float, default=None, metavar="N",
                   help="with --fleet SPOOL_DIR: re-render the fleet "
                        "view every N seconds (mfu / goodput / step "
                        "budget header)")
    p.add_argument("--watch-iterations", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--fleet-worker", type=int, default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--spool", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.platform != "env":
        os.environ["JAX_PLATFORMS"] = args.platform

    if args.fleet_worker is not None:
        return _fleet_worker(args.fleet_worker, args.spool)
    if args.selftest and args.fleet is None and not args.slo:
        p.error("--selftest needs --fleet or --slo")
    if args.slo and args.selftest:
        return _slo_selftest(args.as_json, args.history)
    if args.watch is not None and args.fleet in (None, ""):
        p.error("--watch needs --fleet SPOOL_DIR")
    if args.fleet is not None:
        if args.selftest:
            return _fleet_selftest(args.as_json, args.trace)
        if not args.fleet:
            p.error("--fleet needs a SPOOL_DIR (or --selftest)")
        if args.watch is not None:
            return _watch(args.fleet, args.watch,
                          args.watch_iterations, args.as_json)
        return _fleet_report(args.fleet, args.as_json, args.trace)

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import telemetry

    telemetry.enable()
    main_p, startup_p, loss, feed_fn = build_model(
        args.model, model_args(batch_size=args.batch_size))
    exe = fluid.Executor()
    exe.run(startup_p, feed={}, fetch_list=[])
    # scope the metrics to the steady-state loop: the startup compile
    # is one-off noise next to `steps` worth of hit/miss accounting
    telemetry.reset()

    rng = np.random.RandomState(0)
    losses = []
    inflight_peak = 0
    if args.async_steps > 0:
        # pipelined loop: dispatch every step, consume at the end so
        # the window actually fills (consuming per-step would drain it)
        handles = []
        for _ in range(args.steps):
            feed = feed_fn(args.batch_size, rng)
            handles.append(exe.run(main_p, feed=feed,
                                   fetch_list=[loss],
                                   async_steps=args.async_steps))
            inflight_peak = max(inflight_peak, exe.inflight)
        exe.drain()
        losses = [float(np.asarray(h[0]).ravel()[0]) for h in handles]
    else:
        for _ in range(args.steps):
            feed = feed_fn(args.batch_size, rng)
            out = exe.run(main_p, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(out[0]).ravel()[0]))

    snap = telemetry.snapshot()   # the loop's: the profile's steps come after
    device_profile = None
    if args.profile_device:
        import contextlib
        from paddle_tpu import profiler
        feed = feed_fn(args.batch_size, rng)
        # three more steps under a profiler session: its report (device
        # time by Fluid op and name scope, host spans) is the profile
        with contextlib.redirect_stdout(sys.stderr if args.as_json
                                        else sys.stdout):
            with profiler.profiler("All", "total"):
                for _ in range(3):
                    exe.run(main_p, feed=feed, fetch_list=[loss])
        device_profile = {"host": profiler.summary()}

    problems = validate_metrics(snap, args.steps)

    trace_path = args.trace or f"/tmp/tpustat_{args.model}.trace.json"
    telemetry.write_chrome_trace(trace_path)
    try:
        with open(trace_path) as f:
            trace = json.loads(f.read())
        span_events = sum(1 for e in trace.get("traceEvents", [])
                          if e.get("ph") == "X")
        for e in trace.get("traceEvents", []):
            if e.get("ph") == "X" and ("ts" not in e or "dur" not in e):
                problems.append("trace X event missing ts/dur")
                break
        if span_events < args.steps:
            problems.append(
                f"trace has {span_events} span events < steps "
                f"{args.steps}")
    except (OSError, ValueError) as e:
        span_events = 0
        problems.append(f"trace does not round-trip: {e}")

    import jax
    # signature explosion at a glance: distinct compiled signatures
    # across the executor and inference engines (each gauge is set at
    # compile time — see executor.run / InferenceEngine._get_fn)
    signatures = int(max(snap.get("executor.signature_count", 0),
                         snap.get("inference.signature_count", 0)))
    slo_detail = None
    if args.slo:
        slo_problems, slo_detail = _slo_gate(
            snap, args.rules, args.history,
            platform=jax.devices()[0].platform)
        problems += slo_problems

    from paddle_tpu import diagnostics
    diag = diagnostics.status()
    result = {
        "model": args.model,
        "steps": args.steps,
        "batch_size": args.batch_size,
        "platform": jax.devices()[0].platform,
        "diagnostics": diag,
        "signatures": signatures,
        "async_steps": args.async_steps,
        "inflight_peak": inflight_peak,
        "final_loss": losses[-1] if losses else None,
        "metrics": snap,
        "trace": {"path": trace_path, "span_events": span_events},
        "problems": problems,
        "ok": not problems,
    }
    if device_profile is not None:
        result["device_profile"] = device_profile
    if slo_detail is not None:
        result["slo"] = slo_detail

    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        async_hdr = (f"async={args.async_steps} "
                     f"inflight_peak={inflight_peak} "
                     if args.async_steps > 0 else "")
        print(f"tpustat: {args.model} x {args.steps} steps "
              f"(batch {args.batch_size}) on "
              f"{result['platform']}, {signatures} compiled "
              f"signature{'s' if signatures != 1 else ''}, "
              f"{async_hdr}"
              f"nan_check={'on' if diag['nan_check'] else 'off'} "
              f"flight_recorder="
              f"{'on' if diag['flight_recorder'] else 'off'}")
        width = max((len(k) for k in snap), default=10)
        for name in sorted(snap):
            print(f"  {name:<{width}}  {_fmt_value(snap[name])}")
        print(f"trace: {trace_path} ({span_events} span events)")
        for prob in problems:
            print(f"MALFORMED: {prob}", file=sys.stderr)
    if args.prom:
        print(telemetry.prometheus_text(), end="")
    return 2 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
