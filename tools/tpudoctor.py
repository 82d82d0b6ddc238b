#!/usr/bin/env python
"""tpudoctor — the training-numerics doctor's CLI.

Three jobs:

  demo        (default) build the benchmark MNIST MLP, train a few
              healthy steps with the health monitor, then inject a
              numeric failure and show the doctor localizing it to the
              exact culprit op — NumericsReport + flight-recorder dump.
  postmortem  pretty-print a flight-recorder JSON dump
              (PADDLE_TPU_FLIGHT_RECORDER=<dir> writes them on NaN,
              uncaught exception, or exit).
  --selftest  CI gate (pattern of tools/tpuserve.py --selftest): runs
              the demo with assertions — culprit localized to the
              exact op type + block/op index, the NanInfError report is
              complete, the dump round-trips through this printer, and
              a diagnostics-off run takes zero snapshots. One JSON
              verdict line with --json; exit 2 on any problem.

Examples:
  python tools/tpudoctor.py                      # demo
  python tools/tpudoctor.py postmortem flight_recorder/flight_123.json
  python tools/tpudoctor.py --selftest --json
"""
import argparse
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# ------------------------------------------------------------ postmortem

def format_dump(payload):
    """Human-readable rendering of a flight-recorder dump payload."""
    records = payload.get("records", [])
    lines = [
        f"flight recorder dump — reason: {payload.get('reason')}, "
        f"pid {payload.get('pid')}, uptime "
        f"{payload.get('uptime_s', '?')}s, {len(records)} record(s) "
        f"(ring capacity {payload.get('capacity')})"
    ]
    events = payload.get("events", [])
    if events:
        lines.append("events:")
        for e in events[-16:]:
            extra = {k: v for k, v in e.items()
                     if k not in ("kind", "t")}
            lines.append(f"  [{e.get('t', 0):>9.3f}s] {e.get('kind')} "
                         + json.dumps(extra, default=str))
    if records:
        cols = ("step", "loss", "grad_norm", "update_ratio", "step_s",
                "compile", "program")
        if any("hbm" in r for r in records):
            cols += ("hbm",)    # memory-ledger runs watermark the ring
        lines.append("last steps:")
        lines.append("  " + "  ".join(f"{c:>12}" for c in cols))
        for r in records[-12:]:
            row = []
            for c in cols:
                v = r.get(c)
                if isinstance(v, float):
                    row.append(f"{v:>12.5g}")
                else:
                    row.append(f"{str(v) if v is not None else '-':>12}")
            lines.append("  " + "  ".join(row))
    if payload.get("report"):
        from paddle_tpu.diagnostics import NumericsReport
        lines.append("attached numerics report:")
        lines.append(NumericsReport.from_dict(payload["report"]).format())
    if payload.get("error"):
        lines.append("error:")
        lines.append(str(payload["error"]).rstrip())
    return "\n".join(lines)


def cmd_postmortem(path):
    with open(path) as f:
        payload = json.load(f)
    print(format_dump(payload))
    return 0


# ------------------------------------------------------------------ demo

def _build_mnist(health=True):
    import paddle_tpu as pt
    from paddle_tpu.models import mnist
    main_p, startup_p = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup_p):
        with pt.unique_name.guard():
            feeds, loss, acc = mnist.build_program(model="mlp")
            opt = pt.optimizer.Adam(1e-3)
            opt.minimize(loss, health=health)
    return main_p, startup_p, loss, opt


def _healthy_steps(exe, main_p, loss, monitor, rng, n=3):
    import numpy as np
    vitals = []
    for _ in range(n):
        feed = {"img": rng.rand(16, 784).astype("float32"),
                "label": rng.randint(0, 10, (16, 1)).astype("int64")}
        out = exe.run(main_p, feed=feed,
                      fetch_list=[loss] + monitor.fetch_list)
        monitor.observe_fetches(out[1:], loss=out[0])
        vitals.append([float(np.ravel(o)[0]) for o in out])
    return vitals


def run_demo(selftest=False):
    """Returns (problems, info). problems == [] means healthy."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import diagnostics as dg

    problems = []
    info = {}

    def check(ok, what):
        if not ok:
            problems.append(what)
        return ok

    # 0) diagnostics OFF must take zero snapshots / records
    dg.recorder.disable()
    main_p, startup_p, loss, opt = _build_mnist()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    rng = np.random.RandomState(0)
    with pt.scope_guard(scope):
        exe.run(startup_p)
        feed = {"img": rng.rand(16, 784).astype("float32"),
                "label": rng.randint(0, 10, (16, 1)).astype("int64")}
        exe.run(main_p, feed=feed, fetch_list=[loss])
    check(exe.diag_snapshot_count == 0,
          "diagnostics-off run took a pre-step snapshot")

    # 1) arm the flight recorder, run healthy steps with the monitor
    out_dir = tempfile.mkdtemp(prefix="tpudoctor_")
    rec = dg.recorder.enable(out_dir, capacity=64, install_hooks=False)
    monitor = opt.health_monitor
    with pt.scope_guard(scope):
        vitals = _healthy_steps(exe, main_p, loss, monitor, rng)
        info["healthy_vitals"] = vitals
        gnorms = [v[1] for v in vitals]
        check(all(np.isfinite(g) and g > 0 for g in gnorms),
              f"healthy grad norms not positive/finite: {gnorms}")
        check(not monitor.warnings,
              f"healthy steps fired warnings: {monitor.warnings}")

        # 2) inject: a feed that overflows the first fc matmul
        block = main_p.global_block()
        expect_idx = next(i for i, op in enumerate(block.ops)
                          if op.type == "mul")
        bad_feed = {"img": np.full((16, 784), 3e38, "float32"),
                    "label": np.zeros((16, 1), "int64")}
        report = None
        try:
            exe.run(main_p, feed=bad_feed, fetch_list=[loss],
                    check_nan_inf=True)
            problems.append("injected overflow raised no NanInfError")
        except dg.NanInfError as e:
            report = e.report
        except FloatingPointError as e:
            problems.append(f"raised plain FloatingPointError: {e}")
    if report is not None:
        info["culprit"] = {"phase": report.phase,
                           "op_type": report.op_type,
                           "block_idx": report.block_idx,
                           "op_idx": report.op_idx,
                           "hint": report.hint}
        check(report.phase == "forward",
              f"phase {report.phase!r} != 'forward'")
        check(report.op_type == "mul",
              f"culprit op type {report.op_type!r} != 'mul'")
        check(report.op_idx == expect_idx,
              f"culprit op idx {report.op_idx} != {expect_idx}")
        check(bool(report.input_stats) and bool(report.output_stats),
              "report missing tensor stats")
        check(bool(report.feed_fingerprint), "report missing feed "
              "fingerprint")
        check(bool(report.hint), "report missing fix hint")
        check(report.step is not None
              and report.program_version is not None,
              "report missing step/program fingerprint")
        if not selftest:
            print(report.format())
            print()

    # 3) the failure dumped the flight recorder; round-trip it
    dump_path = rec.last_dump_path
    info["dump"] = dump_path
    if check(dump_path is not None and os.path.exists(dump_path or ""),
             "no flight-recorder dump written on NaN"):
        with open(dump_path) as f:
            payload = json.load(f)
        check(payload.get("reason") == "nan_inf",
              f"dump reason {payload.get('reason')!r} != 'nan_inf'")
        check(len(payload.get("records", [])) >= 3,
              "dump lost the healthy-step records")
        check((payload.get("report") or {}).get("op_type") == "mul",
              "dump's attached report lost the culprit")
        text = format_dump(payload)
        check("nan_inf" in text and "mul" in text
              and "grad_norm" in text,
              "postmortem printer lost dump content")
        if not selftest:
            print(text)
    dg.recorder.disable()
    return problems, info


# ------------------------------------------------------------------ main

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("command", nargs="?", default="demo",
                   choices=["demo", "postmortem"])
    p.add_argument("path", nargs="?", default=None,
                   help="dump file for postmortem")
    p.add_argument("--selftest", action="store_true",
                   help="run the CI gate assertions")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one machine-readable JSON verdict line")
    p.add_argument("--platform", default="env",
                   help="JAX_PLATFORMS to force before backend init "
                        "(default 'env': keep the environment's)")
    args = p.parse_args(argv)

    if args.command == "postmortem":
        if not args.path:
            p.error("postmortem needs a dump path")
        return cmd_postmortem(args.path)

    if args.platform != "env":
        os.environ["JAX_PLATFORMS"] = args.platform

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
            print("tpudoctor: all checks passed "
                  f"(culprit {info.get('culprit')})")
    return 2 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
