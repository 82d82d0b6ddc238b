#!/usr/bin/env python
"""tpukern — the kernel-registry CLI (ops/kern).

Subcommands:

  list        registered kernels: adapter keys, tolerance, tune space,
              one-line note. Loads no backend.
  probe       run each kernel's STATIC capability probe against its
              example shapes (jax.ShapeDtypeStruct — no data touches a
              device) and again with interpret=True; shows what the
              dispatch seam would accept where.
  tune        autotune block sizes for one/all kernels on the live
              backend; entries land in $PADDLE_TPU_KERN_CACHE and
              --emit-baseline writes/merges the committed
              KERN_TUNED.json warm-start (--tpu-defaults appends the
              docsweep v5e entries for the canonical bench shapes).
  bench       A/B each kernel vs its jnp reference composition (median
              jit wall time + max|Δ|); `--flash-ab` reproduces the
              retired tools/flash_ab.py measurement — causal fwd+bwd
              flash attention with the in-kernel probability exp in f32
              (exact algorithm) vs bf16 (VPU-pressure escape), wall
              time, attn-MFU, and output/grad deltas per seqlen.
  --selftest  CI gate (pattern of tools/tpudoctor.py --selftest): every
              registered kernel probes its example statically, passes
              its parity gate in interpret mode, and the autotune cache
              round-trips (publish -> reload -> torn entry rejected).
              One JSON verdict line with --json; exit 2 on any problem.

Examples:
  python tools/tpukern.py list
  python tools/tpukern.py probe
  python tools/tpukern.py tune --mode interpret --emit-baseline KERN_TUNED.json
  python tools/tpukern.py bench --kernels int8_quant,layer_norm
  python tools/tpukern.py bench --flash-ab --seqlens 8192,32768
  python tools/tpukern.py --selftest --json
"""
import argparse
import json
import os
import sys
import tempfile
import time

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
        ex = _example(spec)
        tunable = "yes" if spec.signature is not None else "no"
        ncand = len(spec.tune_space(*ex[0], **ex[1])) if (
            ex and spec.signature is not None) else 0
        rows.append((spec.name, ",".join(spec.op_types),
                     f"rtol={spec.tol[0]:g},atol={spec.tol[1]:g}",
                     f"{tunable}({ncand})" if ncand else tunable,
                     spec.note))
    widths = [max(len(r[i]) for r in rows) for i in range(4)]
    hdr = ("kernel", "adapter keys", "parity tol", "tunable", "note")
    widths = [max(w, len(h)) for w, h in zip(widths, hdr[:4])]
    print("  ".join(h.ljust(w) for h, w in zip(hdr[:4], widths))
          + "  " + hdr[4])
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r[:4], widths))
              + "  " + r[4])
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


# ------------------------------------------------------------------ tune

# Hardware warm-start entries for the canonical bench shapes, from the
# flash-attention docstring block sweep on v5e (8x128-lane tiles; see
# ops/pallas/flash_attention.py "block-size sweep" note). These are the
# shapes bench.py's flash stage and the serving decode tier actually
# run; `tpukern tune` on a real chip replaces them with measured
# entries under the same keys.
_TPU_DEFAULTS = [
    {"kernel": "flash_attention", "sig": [1, 8, 32768, 64, 32768, 64],
     "dtype": "bfloat16", "platform": "tpu",
     "config": {"block_q": 1024, "block_k": 2048},
     "source": "default-docsweep"},
    {"kernel": "flash_attention", "sig": [1, 8, 8192, 64, 8192, 64],
     "dtype": "bfloat16", "platform": "tpu",
     "config": {"block_q": 1024, "block_k": 2048},
     "source": "default-docsweep"},
]


def cmd_tune(args):
    kern = _load_registry()
    from paddle_tpu.ops.kern import autotune
    from paddle_tpu.ops.pallas import flash_attention as fa
    if args.mode != "env":
        fa.set_mode(args.mode)
    entries = []
    for spec in _pick_specs(kern, args.kernels):
        ex = _example(spec)
        if ex is None or spec.signature is None:
            print(f"{spec.name}: not tunable, skipped")
            continue
        a, kw = ex
        cfg = autotune.autotune(spec, a, kw, repeats=args.repeats)
        rep = autotune.autotune.last_report or {}
        ran = [c for c in rep.get("candidates", []) if "ms" in c]
        if not cfg:
            print(f"{spec.name}: no candidate ran "
                  f"({len(rep.get('candidates', []))} tried)")
            continue
        key = rep["key"]
        best_ms = min(c["ms"] for c in ran)
        print(f"{spec.name}: best {cfg} @ {best_ms:.3f} ms "
              f"({len(ran)} candidates, platform {key[3]})")
        entries.append({"kernel": key[0], "sig": key[1],
                        "dtype": key[2], "platform": key[3],
                        "config": cfg, "source": "autotune",
                        "ms": best_ms})
    if args.tpu_defaults:
        entries.extend(_TPU_DEFAULTS)
    if args.emit_baseline:
        path = args.emit_baseline
        doc = {"schema": autotune.SCHEMA, "entries": []}
        try:
            with open(path) as f:
                old = json.load(f)
            if isinstance(old, dict) and old.get("schema") == \
                    autotune.SCHEMA:
                doc = old
        except (ValueError, OSError):
            pass
        # merge on the full key: new measurements replace old ones
        def _k(e):
            return json.dumps([e.get("kernel"), list(e.get("sig") or []),
                               e.get("dtype"), e.get("platform")],
                              sort_keys=True)
        index = {_k(e): e for e in doc.get("entries", [])}
        for e in entries:
            index[_k(e)] = e
        doc["entries"] = sorted(
            index.values(),
            key=lambda e: (e.get("kernel") or "", _k(e)))
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"baseline: {len(doc['entries'])} entries -> {path}")
    return 0


# ----------------------------------------------------------------- bench

def cmd_bench(args):
    if args.flash_ab:
        return _flash_ab(args)
    import numpy as np
    import jax
    kern = _load_registry()
    from paddle_tpu.ops.pallas import flash_attention as fa
    if args.mode != "env":
        fa.set_mode(args.mode)

    def med_ms(fn, operands):
        # jit only the arrays; scalars/flags stay static so the try_*
        # entries can branch on them
        arr_idx = [i for i, a in enumerate(operands)
                   if hasattr(a, "shape") and hasattr(a, "dtype")]
        arrs = [operands[i] for i in arr_idx]

        def run(*a):
            full = list(operands)
            for i, v in zip(arr_idx, a):
                full[i] = v
            return fn(*full)

        jfn = jax.jit(run)
        try:
            out = jfn(*arrs)
        except Exception as e:
            return f"error:{type(e).__name__}"
        if out is None or (isinstance(out, (tuple, list))
                           and all(o is None for o in out)):
            return None
        jax.block_until_ready(out)
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*arrs))
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2] * 1e3

    for spec in _pick_specs(kern, args.kernels):
        ex = _example(spec)
        if ex is None:
            print(f"{spec.name:<22} (no example registered)")
            continue
        a, kw = ex
        k_ms = med_ms(lambda *o: spec.fn(*o, **kw), a)
        r_ms = med_ms(lambda *o: spec.reference(*o, **kw), a)
        if not isinstance(k_ms, float) or not isinstance(r_ms, float):
            print(f"{spec.name:<22} kernel={k_ms or 'rejected'}  "
                  f"reference={r_ms}")
            continue
        ok, detail = kern.parity_check(spec.name, a, kw)
        print(f"{spec.name:<22} kernel={k_ms:.3f} ms  "
              f"reference={r_ms:.3f} ms  "
              f"x{r_ms / max(k_ms, 1e-9):.2f}  parity={ok} ({detail})")
    return 0


def _flash_ab(args):
    """The retired tools/flash_ab.py measurement: causal fwd+bwd flash
    wall time + attn-MFU with the in-kernel probability exp in f32 vs
    bf16, and max|Δ| of loss and grads between the two."""
    import numpy as np

    def measure(T, dtype_name, repeats=3, inner=5):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import flash_attention as fa
        import bench

        B, H, D = 1, 8, 64
        rng = np.random.RandomState(0)
        q, k, v = [jnp.asarray(rng.randn(B, H, T, D).astype("float32"),
                               jnp.bfloat16) for _ in range(3)]
        p_dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
        # CPU smoke: force the Pallas interpreter when the real kernel
        # can't run (non-TPU backend); on the chip this stays False
        use_pallas, interpret = fa.active()
        interpret = interpret or not use_pallas

        def loss_fn(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True,
                                     softmax_dtype=p_dtype,
                                     interpret=interpret)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))
        val, grads = g(q, k, v)
        np.asarray(grads[0][0, 0, 0])  # completion barrier
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(inner):
                val, grads = g(q, k, v)
            np.asarray(grads[0][0, 0, 0])
            times.append((time.perf_counter() - t0) / inner)
        dt = sorted(times)[len(times) // 2]
        fl = 12 * B * H * T * T * D * 0.5  # causal fwd+bwd matmul flops
        peak = bench._peak_flops(jax.devices()[0])  # None on CPU smoke
        return {"ms": round(dt * 1e3, 2),
                "attn_mfu": round(fl / dt / peak, 4) if peak else None,
                "out": val, "grads": grads}

    report = {}
    for T in [int(s) for s in args.seqlens.split(",")]:
        f32 = measure(T, "f32")
        b16 = measure(T, "bf16")
        dg = max(float(np.max(np.abs(
            np.asarray(a, dtype=np.float32) -
            np.asarray(b, dtype=np.float32))))
            for a, b in zip(f32["grads"], b16["grads"]))
        report[f"T{T}"] = {
            "f32_ms": f32["ms"], "f32_attn_mfu": f32["attn_mfu"],
            "bf16_ms": b16["ms"], "bf16_attn_mfu": b16["attn_mfu"],
            "speedup": round(f32["ms"] / b16["ms"], 3),
            "loss_rel_delta": abs(float(f32["out"]) - float(b16["out"]))
            / max(abs(float(f32["out"])), 1e-9),
            "grad_max_abs_delta": dg,
        }
    print(json.dumps(report, indent=2))
    return 0


# -------------------------------------------------------------- selftest

def run_selftest():
    problems = []
    info = {}

    def check(ok, msg):
        if not ok:
            problems.append(msg)
        return ok

    kern = _load_registry()
    from paddle_tpu.ops.kern import autotune
    from paddle_tpu.ops.pallas import flash_attention as fa

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
    fa.set_mode("interpret")
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

        # 3) autotune cache round-trip on the cheapest tunable kernel.
        # The committed KERN_TUNED.json warm start is pointed away so
        # the disk-cache path (not the baseline) is what's exercised.
        spec = kern.get("int8_quant")
        a, kw = _example(spec)
        with tempfile.TemporaryDirectory() as tmp:
            old = os.environ.get(autotune.ENV_CACHE)
            old_base = os.environ.get(autotune.ENV_BASELINE)
            os.environ[autotune.ENV_CACHE] = tmp
            os.environ[autotune.ENV_BASELINE] = \
                os.path.join(tmp, "no_baseline.json")
            try:
                autotune.reset()
                cfg = autotune.autotune(spec, a, kw, repeats=1)
                if check(bool(cfg), "autotune found no legal config "
                         "for int8_quant"):
                    key = autotune.cache_key(spec, a, kw)
                    autotune.reset()   # force the disk read path
                    got = autotune.tuned_config(spec, a, kw)
                    check(got == cfg,
                          f"published config {cfg} did not round-trip "
                          f"({got})")
                    # torn entry: corrupt the payload -> validate()
                    # fails -> skipped, default blocks
                    d = os.path.join(tmp, key[0],
                                     autotune._digest(key))
                    with open(os.path.join(d, "tuned.json"), "w") as f:
                        f.write('{"torn": ')
                    autotune.reset()
                    rej0 = autotune.STATS["entries_rejected"]
                    got = autotune.tuned_config(spec, a, kw)
                    check(got == {},
                          f"torn cache entry was not rejected ({got})")
                    check(autotune.STATS["entries_rejected"] > rej0,
                          "torn entry not counted as rejected")
            finally:
                if old is None:
                    os.environ.pop(autotune.ENV_CACHE, None)
                else:
                    os.environ[autotune.ENV_CACHE] = old
                if old_base is None:
                    os.environ.pop(autotune.ENV_BASELINE, None)
                else:
                    os.environ[autotune.ENV_BASELINE] = old_base
                autotune.reset()
    finally:
        fa.set_mode("auto")

    # 4) the dispatch seam resolves every adapter key to its kernel
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
    st = sub.add_parser("tune", help="autotune block sizes")
    st.add_argument("--kernels", default="", help="csv subset")
    st.add_argument("--mode", default="env",
                    choices=["env", "auto", "interpret", "off"],
                    help="pallas mode for the timing run")
    st.add_argument("--repeats", type=int, default=3)
    st.add_argument("--emit-baseline", default=None, metavar="PATH",
                    help="write/merge the KERN_TUNED.json warm-start")
    st.add_argument("--tpu-defaults", action="store_true",
                    help="append the docsweep v5e default entries")
    sb = sub.add_parser("bench", help="kernel vs reference A/B")
    sb.add_argument("--kernels", default="", help="csv subset")
    sb.add_argument("--mode", default="env",
                    choices=["env", "auto", "interpret", "off"])
    sb.add_argument("--repeats", type=int, default=5)
    sb.add_argument("--flash-ab", action="store_true",
                    help="the retired tools/flash_ab.py f32-vs-bf16 "
                         "softmax A/B")
    sb.add_argument("--seqlens", default="8192,32768")
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
    if args.command == "tune":
        return cmd_tune(args)
    if args.command == "bench":
        return cmd_bench(args)
    p.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
