"""python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json, in a new process. Without a TPU
(or with fewer chips than the cell asks for) it exits non-zero and prints
no result; an explicit JAX_PLATFORMS=cpu is the tests' rehearsal and the
line then says `"platform": "cpu"` and claims no device metric. The last
line of standard output is the result, one JSON object.

`--control 1` is for the builder, not the driver: it also reads the
lower-precision control and the planted faults against the reference (see
PERF.md, "How correct is decided") and prints them under `control`.
"""
import time
T_START = time.perf_counter()           # process start, as near as Python gets

import argparse                          # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402
import types                             # noqa: E402

from chipbench import correct, manifest  # noqa: E402


def say(msg):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Counts XLA compile requests and persistent-cache traffic from JAX's
    own monitoring events (a copy of chip_smoke.CompileWatch)."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def requests(self):
        """Programs XLA was asked for: compiled anew or loaded from disk."""
        return max(self.compiles, self.cache_hits + self.cache_misses)


def find_devices(chips):
    """The devices of this run, or None where JAX has no TPU and the CPU
    was not asked for by name."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            say(f"JAX found platform {platform!r}, not a TPU; nothing run")
            return None
        say("JAX_PLATFORMS=cpu: a rehearsal, no device metric is claimed")
    if len(devices) < chips:
        say(f"the cell asks for {chips} chips, JAX has {len(devices)}")
        return None
    return devices[:chips]


def memory_peak(devices):
    """The peak on the fullest chip, from the allocator's own statistics.
    Live arrays count as bytes in use; a running program's scratch counts
    as RESERVED bytes and never as in use (a jitted function with 12.885e9
    bytes of scratch left peak_bytes_in_use at 0.276e9 and
    peak_bytes_reserved at 12.885e9: my chip run, PR 26). The scratch is
    held on top of the arrays alive while the program runs, which are the
    ones alive now, when the window has just closed. Neither sum nor
    maximum of the two peaks is exact; this is the lower of the two
    readings that could be: the larger of the peak in use, and what is in
    use now plus the largest reservation."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(max(st.get("peak_bytes_in_use", 0),
                         st.get("bytes_in_use", 0)
                         + st.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def main(argv=None, root=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest.Manifest(root).validate()
    if args.workload not in man.cells:
        say(f"no cell {args.workload!r} in BENCHMARK.json")
        return 2
    cell = man.cells[args.workload]
    # the compile cache: where the environment says, else a fixed path in
    # the checkout (the path is part of the cache's key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(man.root, ".jax_compile_cache"))
    # keep the small programs too (JAX drops what compiled in under a
    # second), so that a second run finds every program in the cache
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    devices = find_devices(cell["chips"])
    if devices is None:
        return 2
    on_chip = devices[0].platform == "tpu"
    if on_chip:
        from chipbench import counts
        counts.peak(devices[0].device_kind)      # unknown kind: an error

    cfg = man.config(cell["config"])
    marks, watch = [], CompileWatch()

    def mark(what):
        marks.append(f"{what} {time.perf_counter() - T_START:.1f} s "
                     f"({watch.compiles} compiled, {watch.cache_hits} from "
                     f"the cache)")

    mark("chip found")
    traffic = man.traffic(cell["traffic"])
    # a traced window is short (traces are large and tracing slows the host)
    seconds = min(args.seconds, traffic.get("trace_s", 5)) if args.trace \
        else args.seconds
    ctx = types.SimpleNamespace(
        mark=mark, man=man, cell=cell, cfg=cfg, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        control=bool(args.control), devices=devices, on_chip=on_chip,
        t_start=T_START, say=say, watch=watch,
        trace_dir=os.path.join(man.root, ".chipbench_trace", cell["name"]),
        memory_peak=lambda: memory_peak(devices))
    out = man.driver(cfg["driver"]).run(ctx)

    say("set-up, since the process started: " + "; ".join(marks))
    say("compile cache at " + os.environ["JAX_COMPILATION_CACHE_DIR"] + "; "
        + ", ".join(f"{k}={v}" for k, v in sorted(os.environ.items())
                    if k.startswith(("JAX_COMPILATION_CACHE",
                                     "JAX_PERSISTENT_CACHE"))))
    rows, ok = correct.judge(out["numbers"], cfg.get("limits", {}))
    ok = ok and out["failed"] == 0 and out["attempted"] > 0
    facts = out["facts"]
    metrics = {}
    if args.trace:
        for m in man.cell_per_layer(cell["name"]):
            value = man.reader(m["name"]).read(facts, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in man.cell_end_to_end(cell["name"]):
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(ok), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        red = facts.get("trace") or {}
        device["busy_s"] = red.get("busy_s", 0.0)
        device["window_s"] = red.get("window_s", facts.get("window_s"))
        if red.get("breakdown"):
            result["breakdown"] = red["breakdown"]
        # what the readers saw, for whoever tunes a reader (ignored by git)
        with open(os.path.join(ctx.trace_dir, "reduced.json"), "w") as f:
            json.dump({k: v for k, v in red.items() if k != "raw"}, f)
        say(f"trace: {red.get('chips')} chip(s), busy {device['busy_s']} s "
            f"of {device['window_s']} s")
    if out.get("control"):
        result["control"] = out["control"]
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim, _ in rows}
    say(f"correct={ok} attempted={out['attempted']} failed={out['failed']}")
    for n, v, lim, good in rows:      # the last lines on standard error
        say(f"check {n}: {v!r} limit {lim!r} {'ok' if good else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
