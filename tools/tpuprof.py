"""python tools/tpuprof.py --cell <cell> --seed <n> [--seconds 5]

One benchmark cell's program (built as `chipbench/entries/train.py` builds
it) run for `--seconds` under `fluid.profiler.profiler()`: prints the
report, then one JSON line: its rows, the matrix products by site with
their needed TFLOP/s (a site's FLOPs: 6 x tokens x K x N of its weight,
`chipbench/models/*`'s rule; `step_flops` is the model's own count of the
whole step), and the roll-up by op type beside what the benchmark's reader
makes of the same file (`train_op_ms_per_step.*`).
"""
import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None, root=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import jax
    import paddle_tpu as fluid
    from chipbench import manifest, program_trace
    from chipbench.entries.train import Trainer
    man = manifest.Manifest(root).validate()
    cell = man.cells[args.cell]
    cfg, traffic = man.config(cell["config"]), man.traffic(cell["traffic"])
    model = man.model(cfg.get("model", "nmt"))
    ctx = types.SimpleNamespace(
        cfg=cfg, traffic=traffic, seed=args.seed, mark=lambda what: None,
        on_chip=jax.devices()[0].platform == "tpu")
    batches = model.make_batches(traffic, cfg, args.seed)
    trainer = Trainer(ctx, model)
    for b in batches[:5]:
        trainer.step(b)
    steps = 0
    with fluid.profiler.profiler("All", "total"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < args.seconds:
            trainer.step(batches[steps % len(batches)])
            steps += 1
    rep = fluid.profiler.last_report()
    block, tokens = trainer.main.global_block(), model.tokens_per_step(traffic)
    flops, sites = {}, {}
    for op in block.ops:
        if op.type in ("mul", "matmul") and op.attrs.get("op_namescope"):
            k, n = block.var(op.inputs["Y"][0]).shape
            site = op.attrs["op_namescope"]
            flops[site] = flops.get(site, 0.0) + 6.0 * tokens * k * n
    for r in rep["device"] or ():
        if r["op"] in ("mul", "matmul") and not r["kernel"]:
            s = sites.setdefault(r["scope"], {"ms_per_step": 0.0})
            s["ms_per_step"] += r["ms_per_step"]
            s[r["phase"] + "_ms"] = r["ms_per_step"]
    for site, s in sites.items():
        s["tflops"] = flops.get(site, 0.0) / s["ms_per_step"] / 1e9
    # the benchmark's reader on the session's own file, handed its path
    text = fluid.telemetry.compiled_text(rep["header"]["program"])
    theirs = program_trace.op_seconds(
        program_trace.read_xplane(rep["header"]["xplane"])["chips"],
        program_trace.scopes_of(text))[1] if text else {}
    print(json.dumps({
        "cell": args.cell, "seed": args.seed, "steps": steps, "report": rep,
        "mul_by_site": sites, "sites_flops": sum(flops.values()),
        "step_flops": model.step_flops(cfg, traffic),
        "rollup_op": fluid.profiler.rollup(rep["device"] or [], "op"),
        "benchmark_op_ms_per_step": {
            k: 1e3 * v / steps for k, v in theirs.items()}}))


if __name__ == "__main__":
    main()
