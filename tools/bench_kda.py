#!/usr/bin/env python
"""Time one gated delta-rule scan alone (`kda_attention`): the Mosaic
kernels of `ops/pallas/kda.py` and the jnp composition
`kernels_scan.kda_chunked` they stand in for, forward and forward +
backward, and how far the kernels' output and gradients are from the
composition's on the same operands.

This is how the one-scan numbers of PERF.md sections 5 and 6 (PR 35) were
measured, at the shape of the cell solar_train_1chip:

    chiprun --chips 1 -- python tools/bench_kda.py '[[1,8192,8,128]]'

Shapes are [batch, tokens, heads, head size]; bf16 q, k, v, a float32
log-decay down to -1.6 a step, steps in (0, 2). One JSON line per (shape,
piece); all of them again in chiprun_out/bench_kda.json. A time means
something on the chip only: where JAX's first device is not a TPU the
tool measures nothing, writes nothing and exits 2.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def operands(B, T, H, D):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)

    def unit(*shape):
        x = rng.standard_normal(shape)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True),
                           jnp.bfloat16)

    return (unit(B, T, H, D), unit(B, T, H, D),
            jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16),
            jnp.asarray(-rng.uniform(0.001, 1.6, (B, T, H, D)), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 2.0, (B, T, H)), jnp.float32))


def pieces():
    """{piece: function of (q, k, v, g, beta)}."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import kernels_scan as scan
    from paddle_tpu.ops.pallas import kda

    def grad(fn):
        def loss(*a):
            return jnp.sum(jnp.square(fn(*a).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))

    return {"kernels_fwd": kda.kda, "kernels_fwd_bwd": grad(kda.kda),
            "composition_fwd": scan.kda_chunked,
            "composition_fwd_bwd": grad(scan.kda_chunked)}


def bench(fn, args, n=20):
    """(ms a call of the jitted `fn`, its last result)."""
    import jax
    step = jax.jit(fn)
    for _ in range(2):
        jax.block_until_ready(step(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n, out


def gap(got, want):
    """The largest |got - want| over the largest |want|, leaf by leaf."""
    import jax
    import jax.numpy as jnp
    return [round(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                        - w.astype(jnp.float32)))
                        / jnp.max(jnp.abs(w.astype(jnp.float32)))), 6)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


def main(argv):
    import jax
    shapes = json.loads(argv[0]) if argv else [[1, 8192, 8, 128]]
    n = int(argv[1]) if len(argv) > 1 else 20
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench_kda: the first device is a {platform}, not a TPU; "
              "nothing measured", file=sys.stderr)
        return 2
    lines = []
    for shape in shapes:
        args = operands(*shape)
        outs = {}
        for piece, fn in pieces().items():
            line = {"platform": platform, "piece": piece, "shape": shape}
            try:
                ms, outs[piece] = bench(fn, args, n)
                line["ms"] = round(ms, 4)
                want = outs.get(piece.replace("composition", "kernels"))
                if piece.startswith("composition") and want is not None:
                    line["kernels_gap"] = gap(want, outs[piece])
            except Exception as e:   # out of memory, a refused lowering
                line["error"] = str(e)[:300]
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_kda.json", "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
