#!/usr/bin/env python
"""tpukern — the kernel-registry CLI (ops/kern).

Subcommands:

  list        registered kernels: adapter keys, tolerance, one-line
              note. Loads no backend.
  probe       run each kernel's STATIC capability probe against its
              example shapes (jax.ShapeDtypeStruct — no data touches a
              device) and again with interpret=True; shows what the
              dispatch seam would accept where.
  --selftest  CI gate (pattern of tools/tpudoctor.py --selftest): every
              registered kernel probes its example statically and passes
              its parity gate in interpret mode, and every adapter key
              resolves. One JSON verdict line with --json; exit 2 on any
              problem.

Block sizes are each kernel's own defaults; sweep them on the chip
through the kernel's own function (tools/bench_attention.py,
tools/bench_expert_ffn.py).

Examples:
  python tools/tpukern.py list
  python tools/tpukern.py probe
  python tools/tpukern.py --selftest --json
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _load_registry():
    """Import the registry (and its kernel registrations) lazily so
    `--platform` lands in the environment first."""
    from paddle_tpu.ops import kern
    return kern


def _pick_specs(kern, names_csv):
    names = kern.names()
    if names_csv:
        want = [n.strip() for n in names_csv.split(",") if n.strip()]
        missing = [n for n in want if n not in names]
        if missing:
            raise SystemExit(f"unknown kernel(s) {missing}; "
                             f"registered: {names}")
        names = want
    return [kern.get(n) for n in names]


def _shape_structs(args):
    """Data-free probe operands: arrays become ShapeDtypeStructs,
    everything else passes through."""
    import jax
    out = []
    for a in args:
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            out.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
        else:
            out.append(a)
    return out


def _example(spec, seed=0):
    import numpy as np
    if spec.example is None:
        return None
    return spec.example(np.random.RandomState(seed))


# ------------------------------------------------------------------ list

def cmd_list(args):
    kern = _load_registry()
    rows = []
    for spec in kern.specs():
        rows.append((spec.name, ",".join(spec.op_types),
                     f"rtol={spec.tol[0]:g},atol={spec.tol[1]:g}",
                     spec.note))
    hdr = ("kernel", "adapter keys", "parity tol", "note")
    widths = [max(len(r[i]) for r in rows + [hdr]) for i in range(3)]
    for r in [hdr] + rows:
        print("  ".join(c.ljust(w) for c, w in zip(r[:3], widths))
              + "  " + r[3])
    return 0


# ----------------------------------------------------------------- probe

def cmd_probe(args):
    kern = _load_registry()
    bad = 0
    for spec in _pick_specs(kern, args.kernels):
        ex = _example(spec)
        if ex is None:
            print(f"{spec.name:<22} (no example registered)")
            continue
        a, kw = ex
        structs = _shape_structs(a)
        static = bool(spec.probe(*structs, **kw))
        interp = bool(spec.probe(*structs, interpret=True, **kw))
        mark = "ok" if interp else "REJECT"
        if not interp:
            bad += 1
        print(f"{spec.name:<22} static={'accept' if static else 'reject'}"
              f"  interpret={'accept' if interp else 'reject'}  [{mark}]")
    return 1 if bad else 0


# -------------------------------------------------------------- selftest

def run_selftest():
    problems = []
    info = {}

    def check(ok, msg):
        if not ok:
            problems.append(msg)
        return ok

    kern = _load_registry()
    from paddle_tpu.ops.registry import set_mode

    names = kern.names()
    info["kernels"] = names
    check(len(names) >= 5,
          f"registry holds {len(names)} kernels, expected >= 5")

    # 1) every kernel's static probe accepts its own example — on
    # ShapeDtypeStructs, the data-free path meshlint and `probe` use
    for spec in kern.specs():
        if not check(spec.example is not None,
                     f"{spec.name}: no example registered"):
            continue
        a, kw = _example(spec)
        check(bool(spec.probe(*_shape_structs(a), interpret=True, **kw)),
              f"{spec.name}: static probe rejects its own example")

    # 2) parity gate in interpret mode: kernel vs jnp reference
    set_mode("interpret")
    try:
        parity = {}
        for spec in kern.specs():
            if spec.example is None:
                continue
            a, kw = _example(spec)
            ok, detail = kern.parity_check(spec.name, a, kw)
            parity[spec.name] = detail
            check(ok is True,
                  f"{spec.name}: parity gate failed ({detail})")
        info["parity"] = parity
    finally:
        set_mode("auto")

    # 3) the dispatch seam resolves every adapter key to its kernel
    from paddle_tpu.ops.kern import registry as kreg
    for key, name in kreg.ADAPTERS.items():
        check(kern.adapter(key) is not None,
              f"adapter key {key!r} does not resolve")
        check(name in kreg.KERN_SPECS,
              f"adapter key {key!r} points at unknown kernel {name!r}")
    return problems, info


# ------------------------------------------------------------------ main

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--selftest", action="store_true",
                   help="run the CI gate assertions")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one machine-readable JSON verdict line")
    p.add_argument("--platform", default="env",
                   help="JAX_PLATFORMS to force before backend init "
                        "(default 'env': keep the environment's)")
    sub = p.add_subparsers(dest="command")
    sub.add_parser("list", help="registered kernels")
    sp = sub.add_parser("probe", help="capability probes on examples")
    sp.add_argument("--kernels", default="", help="csv subset")
    args = p.parse_args(argv)

    if args.platform != "env":
        os.environ["JAX_PLATFORMS"] = args.platform

    if args.selftest:
        problems, info = run_selftest()
        result = {"ok": not problems, "problems": problems}
        result.update(info)
        if args.as_json:
            print(json.dumps(result, default=str))
        else:
            if problems:
                for prob in problems:
                    print(f"PROBLEM: {prob}", file=sys.stderr)
            else:
                print("tpukern: all checks passed "
                      f"({len(info.get('kernels', []))} kernels)")
        return 2 if problems else 0

    if args.command == "list":
        return cmd_list(args)
    if args.command == "probe":
        return cmd_probe(args)
    p.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
