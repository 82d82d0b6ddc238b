"""Runs one cell several times, a new process each, and records every
result line: the builder's tool for the recorded runs under chipbench/runs/.

    python -m chipbench.sets --workload nmt_train_1chip --seeds 11,12,13 \
        --seconds 20 --set 1 --out chiprun_out/runs [--trace 1] [--control 1]

This process never touches JAX, so each child gets the chip.
"""
import argparse
import json
import os
import subprocess
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.sets")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload + ".jsonl")
    for seed in args.seeds.split(","):
        cmd = [sys.executable, "-m", "chipbench.run", "--workload",
               args.workload, "--seed", seed, "--seconds",
               str(args.seconds), "--trace", str(args.trace)]
        if args.control:
            cmd += ["--control", "1"]
        t0 = time.time()
        proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
        wall = time.time() - t0
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1])
        except (IndexError, ValueError):
            result = None
        rec = {"cell": args.workload, "seed": int(seed), "set": args.set,
               "trace": args.trace, "seconds": args.seconds,
               "rc": proc.returncode, "wall_s": wall, "result": result}
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        with open(os.path.join(args.out, f"{args.workload}.{args.set}."
                               f"{seed}.err"), "w") as f:
            f.write(proc.stderr)
        tail = [ln for ln in proc.stderr.splitlines()
                if ln.startswith("[chipbench]")]
        print(f"--- {args.workload} seed {seed} set {args.set} rc "
              f"{proc.returncode} wall {wall:.1f} s", flush=True)
        print("\n".join(tail[-12:]), flush=True)
        if result is None:
            print(proc.stderr[-3000:], flush=True)
        else:
            print(json.dumps({k: result[k] for k in
                              ("correct", "metrics", "device")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
