#!/usr/bin/env python
"""tpulint — the unified static-analysis gate: proglint + meshlint.

One command, one exit code, for everything the static verifiers can
prove about this repo before anything traces or compiles:

  1. proglint over every benchmark model Program (tools/proglint.py —
     use-before-def, unknown ops, dead code, shape/dtype abstract
     interpretation incl. control-flow sub-blocks, WAW hazards,
     recompile hazards);
  2. meshlint over the sharded-execution configs: the passing parallel
     tests' configs (must produce ZERO errors — the false-positive
     pin), the gradsync / sparse policy grammars, and the serving
     FarmConfig shapes.

Exit status is non-zero when any error-severity diagnostic fires (or
any warning with --strict) — a CI gate, like proglint.

Examples:
  python tools/tpulint.py                      # the whole gate
  python tools/tpulint.py --json               # machine-readable
  python tools/tpulint.py --selftest           # fast smoke (tier-1)
"""
import argparse
import json
import os
import sys

# static analysis never needs an accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

# policy grammar strings the repo's docs/benchmarks advertise — each
# must parse (a grammar regression breaks users' env vars silently)
GRAMMAR_FIXTURES = {
    "grad_sync": ["fp32", "bf16", "int8", "int8:bucket_mb=1",
                  "bf16:bucket_kb=256,block=128",
                  "int8:overlap=0,ef=1", "fp32:reduce=sum"],
    "sparse": ["shard", "shard:stale=2", "shard:stale=4,cap=1024",
               "shard:kernel=0", "1", "on"],
}


def _meshlint():
    from paddle_tpu.analysis import meshlint
    return meshlint


def lint_models(names=None, quiet=False):
    """Section 1: proglint over the benchmark models."""
    import proglint
    out = {}
    for name in names or proglint.ALL_MODELS:
        diags, n_ops = proglint.lint_model(name)
        if quiet:
            diags = [d for d in diags if d.severity != "info"]
        out[name] = {"ops": n_ops,
                     "diagnostics": [d.to_dict() for d in diags]}
    return out


def lint_mesh_configs(quiet=False):
    """Section 2: meshlint — green control set, policy grammars, farm
    shapes."""
    ml = _meshlint()
    out = {"green": {}, "grammars": {}, "farm": {}, "errors": []}

    for label, mctx in ml.green_configs():
        diags = ml.run_mesh_passes(mctx)
        if quiet:
            diags = [d for d in diags if d.severity != "info"]
        out["green"][label] = [d.to_dict() for d in diags]
        for d in diags:
            if d.severity == "error":
                out["errors"].append(
                    f"FALSE POSITIVE: green config {label!r} got "
                    f"[{d.pass_name}] {d.message}")

    from paddle_tpu.parallel import gradsync, sparse
    for kind, parse in (("grad_sync", gradsync.parse_policy),
                        ("sparse", sparse.parse_policy)):
        for g in GRAMMAR_FIXTURES[kind]:
            try:
                parse(g)
                out["grammars"][f"{kind}:{g}"] = "ok"
            except Exception as e:
                out["grammars"][f"{kind}:{g}"] = f"FAIL: {e}"
                out["errors"].append(
                    f"{kind} grammar {g!r} no longer parses: {e}")

    from paddle_tpu.serving.farm import FarmConfig
    from paddle_tpu.serving.decode import DecodeEngineConfig
    farm_shapes = {
        "default": FarmConfig(),
        "prefill-disagg": FarmConfig(replicas=2, prefill_devices=1),
        "kv-int8": FarmConfig(engine=DecodeEngineConfig(
            num_slots=8, kv_quant="int8")),
    }
    for label, cfg in farm_shapes.items():
        diags = cfg.verify()
        if quiet:
            diags = [d for d in diags if d.severity != "info"]
        out["farm"][label] = [d.to_dict() for d in diags]
        for d in diags:
            if d.severity == "error":
                out["errors"].append(
                    f"farm shape {label!r}: [{d.pass_name}] "
                    f"{d.message}")
    return out


def selftest():
    """Fast smoke for tier-1 (tpudoctor pattern: last stdout line is a
    JSON object with an "ok" field). Exercises every pass once with a
    seeded defect and once clean — no model builds, subsecond."""
    ml = _meshlint()
    checks = {}
    # seeded defect: unknown axis + non-divisible dim must both fire
    mesh = ml.MeshSpec({"dp": 4, "tp": 2})
    use = ml.ShardMapUse("selftest", in_specs=[("xx",), ("dp", "tp")],
                         arg_shapes=[(8,), (6, 4)])  # 6 % dp=4 != 0
    diags = ml.run_mesh_passes(ml.MeshLintContext(mesh, uses=[use]),
                               passes=["mesh-spec"])
    errs = [d for d in diags if d.severity == "error"]
    checks["seeded_spec_defect_fires"] = len(errs) >= 2
    # clean config: no errors
    ok_use = ml.ShardMapUse("selftest-ok", in_specs=[("dp",)],
                            arg_shapes=[(8,)])
    diags = ml.run_mesh_passes(ml.MeshLintContext(mesh, uses=[ok_use]))
    checks["clean_config_quiet"] = not any(
        d.severity == "error" for d in diags)
    # every advertised pass is registered
    checks["passes_registered"] = set(ml.mesh_pass_names()) == {
        "mesh-spec", "collective-consistency", "donation-aliasing",
        "device-footprint", "mesh-recompile-hazard",
        "kern-capability"}
    # green control set stays quiet
    checks["green_zero_errors"] = all(
        not any(d.severity == "error" for d in ml.run_mesh_passes(m))
        for _, m in ml.green_configs())
    ok = all(checks.values())
    print(json.dumps({"ok": ok, "checks": checks,
                      "passes": ml.mesh_pass_names()}))
    return 0 if ok else 1


def main(argv=None):
    p = argparse.ArgumentParser(
        description="unified static-analysis gate (proglint + meshlint)")
    p.add_argument("models", nargs="*", default=None,
                   help="benchmark models to proglint (default: all)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report on stdout")
    p.add_argument("--strict", action="store_true",
                   help="warnings also fail the exit status")
    p.add_argument("--quiet", action="store_true",
                   help="suppress info-severity diagnostics")
    p.add_argument("--skip-models", action="store_true",
                   help="meshlint sections only (no model builds)")
    p.add_argument("--list-passes", action="store_true",
                   help="print proglint + meshlint pass names and exit")
    p.add_argument("--selftest", action="store_true",
                   help="fast smoke; last stdout line is JSON verdict")
    args = p.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.list_passes:
        from paddle_tpu.analysis import pass_names
        ml = _meshlint()
        print("\n".join(pass_names()))
        print("\n".join(ml.mesh_pass_names()))
        return 0

    mesh_report = lint_mesh_configs(quiet=args.quiet)

    model_report = {}
    if not args.skip_models:
        model_report = lint_models(args.models, quiet=args.quiet)

    failed = bool(mesh_report["errors"])
    n_warn_total = 0
    for name, rec in model_report.items():
        sevs = [d["severity"] for d in rec["diagnostics"]]
        n_err, n_warn = sevs.count("error"), sevs.count("warning")
        n_warn_total += n_warn
        if n_err:
            failed = True
        if not args.as_json:
            status = "FAIL" if n_err else ("warn" if n_warn else "ok")
            print(f"proglint {name:<24} {rec['ops']:>4} ops  "
                  f"{n_err} error(s), {n_warn} warning(s)  [{status}]")
    for label, dl in list(mesh_report["green"].items()) \
            + list(mesh_report["farm"].items()):
        n_warn_total += sum(d["severity"] == "warning" for d in dl)
    if args.strict and n_warn_total:
        failed = True

    if not args.as_json:
        print(f"meshlint {len(mesh_report['green'])} green configs, "
              f"{len(mesh_report['grammars'])} grammars, "
              f"{len(mesh_report['farm'])} farm shapes")
        for e in mesh_report["errors"]:
            print(f"  error: {e}")
    else:
        print(json.dumps({"models": model_report,
                          "meshlint": mesh_report}, indent=1))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
