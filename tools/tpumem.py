#!/usr/bin/env python
"""tpumem — the device-memory ledger's CLI.

Four jobs:

  demo        (default) run a tiny training job under the ledger
              (PADDLE_TPU_MEMLEDGER=1), let it fit under the device
              cap, then grow a decode KV cache past it and show the
              OOM doctor's MemoryReport: top allocations by category,
              peak-vs-cap, and the "what grew since the last fit"
              diff phrased in the shared ckey vocabulary.
  snapshot    pretty-print a live memory snapshot from a farm
              (`GET /v1/memory` URL) or a telemetry-dir memory.json.
  watch       re-poll a /v1/memory URL and print one line per sample.
  postmortem  pretty-print a flight-recorder dump that carries a
              memory report (reason memory_oom / memory_over_cap).
  --selftest  CI gate (pattern of tools/tpudoctor.py --selftest):
              the demo with assertions — the over-cap report names
              the correct top category with a ckey-vocab growth diff
              and round-trips through the flight recorder; ledger KV
              bytes match the engine's analytic kv_cache_bytes for
              fp32 AND int8; the measured runtime footprint
              reconciles against meshlint's static floor (and an
              injected mismatch trips the drift WARNING);
              ScalePlanner rejects a grow that measured bytes rule
              out (reason "measured") even though the static floor
              fits; and with PADDLE_TPU_MEMLEDGER unset a subprocess
              never imports the ledger module. One JSON verdict line
              with --json; exit 2 on any problem.

Examples:
  python tools/tpumem.py                          # demo
  python tools/tpumem.py snapshot http://HOST:PORT/v1/memory
  python tools/tpumem.py watch http://HOST:PORT/v1/memory -n 10
  python tools/tpumem.py postmortem flight_recorder/flight_123.json
  python tools/tpumem.py --selftest --json
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# -------------------------------------------------------------- rendering

def _fmt_bytes(n):
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.2f}{unit}")
        n /= 1024
    return f"{n:.2f}GiB"


def format_snapshot(payload):
    """Human rendering of a /v1/memory (or memory.json) payload."""
    if not payload.get("enabled", True):
        lines = ["memory ledger: disabled (PADDLE_TPU_MEMLEDGER unset)"]
        dev = payload.get("device") or {}
        for k, v in sorted(dev.items()):
            lines.append(f"  {k}: {_fmt_bytes(v)}")
        return "\n".join(lines)
    cap = payload.get("cap_bytes")
    lines = [
        f"memory ledger: {_fmt_bytes(payload.get('total_bytes', 0))} "
        f"live, {_fmt_bytes(payload.get('peak_bytes', 0))} peak / "
        f"{'cap ' + _fmt_bytes(cap) if cap else 'uncapped'} "
        f"({payload.get('steps', 0)} step samples)"]
    cats = payload.get("categories") or {}
    for c, b in sorted(cats.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {c:<13} {_fmt_bytes(b)}")
    owners = payload.get("owners") or []
    if owners:
        lines.append("  top owners:")
        for o in owners[:8]:
            lines.append(f"    {o['category']}/{o['owner']:<20} "
                         f"{_fmt_bytes(o['bytes'])}")
    rp = payload.get("replica_peaks") or {}
    if rp:
        peaks = " ".join(f"{k}={_fmt_bytes(v)}"
                         for k, v in sorted(rp.items()))
        lines.append(f"  replica peaks: {peaks}")
    if payload.get("last_report"):
        lr = payload["last_report"]
        lines.append(f"  last report: {lr.get('reason')} "
                     f"(top {lr.get('top_category')})")
    return "\n".join(lines)


def _fetch(src):
    if src.startswith(("http://", "https://")):
        from urllib.request import urlopen
        with urlopen(src, timeout=10) as r:
            return json.loads(r.read().decode())
    with open(src) as f:
        return json.load(f)


def cmd_snapshot(src, as_json):
    payload = _fetch(src)
    if as_json:
        print(json.dumps(payload, default=str))
    else:
        print(format_snapshot(payload))
    return 0


def cmd_watch(src, interval, iterations):
    i = 0
    while iterations is None or i < iterations:
        try:
            p = _fetch(src)
        except OSError as e:
            print(f"tpumem watch: {e}", file=sys.stderr)
            return 2
        cats = p.get("categories") or {}
        top = ",".join(f"{c}={_fmt_bytes(b)}" for c, b in sorted(
            cats.items(), key=lambda kv: -kv[1])[:3])
        cap = p.get("cap_bytes")
        print(f"[{time.strftime('%H:%M:%S')}] "
              f"live {_fmt_bytes(p.get('total_bytes', 0)):>10} "
              f"peak {_fmt_bytes(p.get('peak_bytes', 0)):>10} "
              f"{('cap ' + _fmt_bytes(cap)) if cap else 'uncapped':>12} "
              f" {top}")
        i += 1
        if iterations is None or i < iterations:
            time.sleep(interval)
    return 0


def cmd_postmortem(path):
    with open(path) as f:
        payload = json.load(f)
    rep = payload.get("report")
    if rep and rep.get("kind") == "memory":
        from paddle_tpu.telemetry.memledger import MemoryReport
        r = MemoryReport(
            rep.get("reason", "?"), error=rep.get("error"),
            context=rep.get("context"), cap_bytes=rep.get("cap_bytes"),
            total_bytes=rep.get("total_bytes", 0),
            peak_bytes=rep.get("peak_bytes", 0),
            categories=rep.get("categories"), top=rep.get("top"),
            growth=rep.get("growth"), hints=rep.get("hints"),
            device=rep.get("device"), timeline=rep.get("timeline"))
        print(f"flight dump {payload.get('reason')} "
              f"(pid {payload.get('pid')})")
        print(r.format())
        tl = rep.get("timeline") or []
        if tl:
            print(f"  timeline (last {min(len(tl), 8)} of {len(tl)}):")
            for t in tl[-8:]:
                print(f"    step {t.get('step'):>6}  "
                      f"{_fmt_bytes(t.get('total', 0))}")
    else:
        print(json.dumps(payload, indent=2, default=str))
    return 0


# ------------------------------------------------------------------- demo

def _mlp_stack():
    """Tiny FC/Momentum training program + a feed, the demo workload."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            x = layers.data("x", shape=[16])
            label = layers.data("label", shape=[1], dtype="int64")
            h = layers.fc(x, size=32, act="relu")
            pred = layers.fc(h, size=8, act="softmax")
            loss = layers.mean(
                layers.cross_entropy(input=pred, label=label))
            pt.optimizer.Momentum(0.1, momentum=0.9).minimize(loss)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 16).astype("float32"),
            "label": rng.randint(0, 8, (8, 1)).astype("int64")}
    return main, exe, loss, feed


def _decode_engine(kv_quant=None, num_slots=2, maxlen=12):
    """Tiny DecodeEngine (no warmup — init_state is the creation site
    under test, compiling nothing)."""
    import paddle_tpu as pt
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving.decode import DecodeEngine, DecodeEngineConfig
    cfg = tfm.TransformerConfig(
        src_vocab=32, trg_vocab=32, max_len=maxlen, d_model=16,
        d_inner=32, n_head=2, n_layer=2, dropout=0.0)
    infer, start = fw.Program(), fw.Program()
    with pt.program_guard(infer, start):
        with pt.unique_name.guard():
            tfm.build_infer_program(cfg, maxlen=maxlen)
    exe = pt.Executor(pt.CPUPlace())
    exe.run(start)
    import numpy as np
    scope = pt.global_scope()
    params = {v.name: np.asarray(scope.get(v.name))
              for v in infer.persistable_vars()}
    return DecodeEngine(cfg, params, DecodeEngineConfig(
        num_slots=num_slots, max_len=maxlen, prefill_buckets=(1, 2),
        kv_quant=kv_quant))


def run_demo(selftest=False):
    problems = []
    info = {}

    def check(cond, what):
        if not cond:
            problems.append(what)
        return cond

    from paddle_tpu import telemetry as tm
    tm.memledger_enable()
    tm.enable()
    from paddle_tpu.telemetry import memledger as ml
    from paddle_tpu.diagnostics import recorder as flight
    ml.reset()
    os.environ.pop("PADDLE_TPU_DEVICE_MEM_CAP", None)
    flight_dir = tempfile.mkdtemp(prefix="tpumem_flight_")
    flight.enable(out_dir=flight_dir, install_hooks=False)

    # ---- act 1: train a few steps, uncapped — the ledger marks fits
    main, exe, loss, feed = _mlp_stack()
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss])
    snap = ml.snapshot_report()
    fit_total = snap["total_bytes"]
    if not selftest:
        print("after 3 training steps (uncapped):")
        print(format_snapshot(snap))
    check(snap["categories"].get("params", 0) > 0,
          "no params bytes attributed after training steps")
    check(snap["categories"].get("optimizer", 0) > 0,
          "no optimizer slot bytes attributed (Momentum has velocity)")
    check(snap["categories"].get("feed", 0) > 0,
          "no feed bytes attributed")

    # ---- act 2: static-vs-runtime reconciliation on the same model
    # (before any serving state exists — the static floor prices
    # params + optimizer slots, so the measured side must too)
    from paddle_tpu.analysis import meshlint as mlint
    from paddle_tpu.analysis.meshlint.footprint import member_footprint
    fp = member_footprint(mlint.MeshLintContext(
        mlint.MeshSpec({"dp": 1}), program=main))
    rec = ml.reconcile(fp, tolerance=0.25, label="tpumem demo MLP")
    check(rec["ok"],
          f"runtime footprint {rec['measured_bytes']} drifted past "
          f"tolerance from static floor {rec['static_bytes']} "
          f"(x{rec['ratio']:.2f})")
    info["reconcile_ratio"] = round(rec["ratio"], 4)
    if not selftest:
        print(f"\nstatic floor {_fmt_bytes(rec['static_bytes'])} vs "
              f"measured peak {_fmt_bytes(rec['measured_bytes'])} "
              f"(x{rec['ratio']:.2f}) — "
              f"{'reconciled' if rec['ok'] else 'DRIFT'}")
    # an injected mismatch must trip the drift WARNING + alarm gauge
    import jax.numpy as jnp
    bogus = jnp.zeros(max(1, fp["total"] * 3 // 4), jnp.uint8)
    ml.register("params", "drift_probe", bogus)
    ml.on_step(context={"site": "tpumem.selftest"})
    bad = ml.reconcile(fp, tolerance=0.25, label="injected mismatch")
    check(not bad["ok"], "injected 1.75x mismatch not flagged")
    check(bad["diagnostic"] is not None
          and bad["diagnostic"].severity == "warning"
          and bad["diagnostic"].pass_name == "memledger-drift",
          "drift beyond tolerance produced no WARNING diagnostic")
    from paddle_tpu.telemetry import registry as treg
    check(treg.gauge("memledger.static_drift_alarm").value == 1.0,
          "memledger.static_drift_alarm gauge did not fire")
    del bogus

    # ---- act 3: KV parity, fp32 (the farm gauge's analytic number vs
    # what the creation site actually registered)
    eng_f32 = _decode_engine(kv_quant=None)
    before = ml.snapshot_report()["categories"].get("kv_cache", 0)
    state_f32 = eng_f32.init_state()         # keep the arrays alive
    after = ml.snapshot_report()["categories"].get("kv_cache", 0)
    check(after - before == eng_f32.kv_cache_bytes,
          f"kv_quant=None: ledger measured {after - before} bytes, "
          f"engine analytic kv_cache_bytes={eng_f32.kv_cache_bytes}")
    f32 = eng_f32.kv_cache_bytes
    i8_eng = _decode_engine(kv_quant="int8")  # params register at ctor
    i8 = i8_eng.kv_cache_bytes
    check(0.2 < i8 / f32 < 0.8,
          f"int8 KV cache not smaller than fp32 ({i8} vs {f32})")
    info["kv_fp32_bytes"] = f32
    info["kv_int8_bytes"] = i8

    # ---- act 4: one uncapped step marks the fit with everything but
    # the int8 engine's KV state; cap the device halfway into that
    # growth — creating the cache then stepping breaches, and the OOM
    # doctor's diff names the KV cache in ckey vocabulary
    exe.run(main, feed=feed, fetch_list=[loss])
    fit_total = ml.snapshot_report()["total_bytes"]
    cap_bytes = fit_total + i8 // 2
    os.environ["PADDLE_TPU_DEVICE_MEM_CAP"] = \
        str(cap_bytes / (1 << 20))
    before = ml.snapshot_report()["categories"].get("kv_cache", 0)
    state_i8 = i8_eng.init_state()
    after = ml.snapshot_report()["categories"].get("kv_cache", 0)
    check(after - before == i8,
          f"kv_quant=int8: ledger measured {after - before} bytes, "
          f"engine analytic kv_cache_bytes={i8}")
    exe.run(main, feed=feed, fetch_list=[loss])
    rep = ml.last_report()
    if check(rep is not None, "no MemoryReport after the over-cap "
                              "step"):
        check(rep.reason == "over_cap",
              f"report reason {rep.reason!r}, wanted 'over_cap'")
        check(rep.top_growth_category == "kv_cache",
              f"top growth category {rep.top_growth_category!r}, the "
              f"KV caches grew — wanted 'kv_cache'")
        phrases = [g["phrase"] for g in rep.growth]
        check(any("engine" in p for p in phrases),
              f"growth diff not phrased in ckey vocab (phrases: "
              f"{phrases})")
        check(any("kv_quant" in h or "int8" in h for h in rep.hints),
              f"no kv_quant fix hint in {rep.hints}")
        check(rep.peak_bytes > cap_bytes,
              "reported peak does not exceed the cap")
        info["report_top_growth"] = rep.top_growth_category
        if not selftest:
            print("\ncap set between the fit and the KV growth — the "
                  "over-cap doctor fired:")
            print(rep.format())
    dumps = [f for f in os.listdir(flight_dir) if f.endswith(".json")]
    if check(bool(dumps), "flight recorder wrote no memory dump"):
        with open(os.path.join(flight_dir, sorted(dumps)[-1])) as f:
            payload = json.load(f)
        check(payload.get("reason") == "memory_over_cap",
              f"dump reason {payload.get('reason')!r}")
        check((payload.get("report") or {}).get("kind") == "memory",
              "dump carries no typed memory report")
        # per-step HBM watermark rides the flight ring (satellite)
        recs = payload.get("records") or []
        check(any("hbm" in r for r in recs),
              "flight records carry no per-step hbm watermark")
    os.environ.pop("PADDLE_TPU_DEVICE_MEM_CAP", None)
    flight.disable()

    # ---- act 5: the measured gate — ScalePlanner rejects a grow the
    # runtime ledger rules out even though the static floor fits
    from paddle_tpu.serving.scale.planner import (ScalePlanner,
                                                  ScalePlanRejected)

    class _Slice(list):
        pass

    class _StubGroup:
        """Allocator-only surface: grow is rejected before spawn."""
        class config:
            devices = [0, 1, 2, 3]
        prefill_devices = ()
        replicas = ()
        model_cfg = None

    pl = ScalePlanner(_StubGroup(), devices=[0, 1, 2, 3], width=1,
                      verify=False,
                      measured_bytes=lambda: 2 * (1 << 20))
    os.environ["PADDLE_TPU_DEVICE_MEM_CAP"] = "1"    # 1 MiB cap
    check(pl.at_ceiling(), "measured 2MiB > 1MiB cap but at_ceiling "
                           "is False")
    try:
        pl.grow(1)
        problems.append("grow succeeded despite measured overrun")
    except ScalePlanRejected as e:
        check(e.reason == "measured",
              f"rejection reason {e.reason!r}, wanted 'measured'")
        check("measured per-replica peak" in str(e),
              f"rejection message unhelpful: {e}")
    pl2 = ScalePlanner(_StubGroup(), devices=[0, 1, 2, 3], width=1,
                       verify=False,
                       measured_bytes=lambda: 64 * 1024)
    check(not pl2.at_ceiling(),
          "64KiB measured under a 1MiB cap reported at_ceiling")
    os.environ.pop("PADDLE_TPU_DEVICE_MEM_CAP", None)
    info["planner_measured_gate"] = "rejected"
    if not selftest:
        print("\nScalePlanner: grow rejected (reason 'measured') — "
              "runtime bytes overruled the static floor")

    # ---- act 6: off-path purity — unset, the ledger module is never
    # imported (subprocess; the bench-contract test pins fetch bytes)
    if selftest:
        code = (
            "import os, sys\n"
            "os.environ.pop('PADDLE_TPU_MEMLEDGER', None)\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import paddle_tpu as pt\n"
            "from paddle_tpu import telemetry as tm\n"
            "assert tm.memledger_enabled() is False\n"
            "import numpy as np\n"
            "from paddle_tpu import layers\n"
            "main, st = pt.Program(), pt.Program()\n"
            "with pt.program_guard(main, st):\n"
            "    with pt.unique_name.guard():\n"
            "        x = layers.data('x', shape=[4])\n"
            "        y = layers.fc(x, size=2)\n"
            "exe = pt.Executor(pt.CPUPlace())\n"
            "exe.run(st)\n"
            "exe.run(main, feed={'x': np.ones((2, 4), 'float32')},\n"
            "        fetch_list=[y])\n"
            "assert 'paddle_tpu.telemetry.memledger' not in "
            "sys.modules, 'memledger imported on the off path'\n"
            "print('PURE')\n")
        env = dict(os.environ)
        env.pop("PADDLE_TPU_MEMLEDGER", None)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=240,
                           cwd=_REPO)
        check(r.returncode == 0 and "PURE" in r.stdout,
              f"off-path purity subprocess failed: "
              f"{r.stdout[-500:]} {r.stderr[-500:]}")

    tm.disable()
    tm.memledger_disable()
    ml.reset()
    return problems, info


# ------------------------------------------------------------------- main

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("command", nargs="?", default="demo",
                   choices=["demo", "snapshot", "watch", "postmortem"])
    p.add_argument("path", nargs="?", default=None,
                   help="snapshot/watch: /v1/memory URL or memory.json "
                        "path; postmortem: flight dump path")
    p.add_argument("--selftest", action="store_true",
                   help="run the CI gate assertions")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one machine-readable JSON verdict line")
    p.add_argument("-n", "--iterations", type=int, default=None,
                   help="watch: number of samples (default: forever)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="watch: seconds between samples")
    p.add_argument("--platform", default="env",
                   help="JAX_PLATFORMS to force before backend init "
                        "(default 'env': keep the environment's)")
    args = p.parse_args(argv)

    if args.command in ("snapshot", "watch", "postmortem") \
            and not args.path:
        p.error(f"{args.command} needs a URL or path")
    if args.command == "postmortem":
        return cmd_postmortem(args.path)
    if args.command == "snapshot":
        return cmd_snapshot(args.path, args.as_json)
    if args.command == "watch":
        return cmd_watch(args.path, args.interval, args.iterations)

    if args.platform != "env":
        os.environ["JAX_PLATFORMS"] = args.platform
    os.environ["PADDLE_TPU_MEMLEDGER"] = "1"

    problems, info = run_demo(selftest=args.selftest)
    result = {"ok": not problems, "problems": problems}
    result.update(info)
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        if problems:
            for prob in problems:
                print(f"PROBLEM: {prob}", file=sys.stderr)
        else:
            print("\ntpumem: all checks passed "
                  f"(kv fp32 {_fmt_bytes(info['kv_fp32_bytes'])}, "
                  f"int8 {_fmt_bytes(info['kv_int8_bytes'])}, "
                  f"reconcile x{info['reconcile_ratio']})")
    return 2 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
