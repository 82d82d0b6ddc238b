#!/usr/bin/env python
"""Time one expert layer alone (`ops/pallas/grouped_matmul.py`) and its
pieces: the plan, the gather into the sorted buffer (`gather`: the loop
over the tiles in use into a buffer that starts unwritten) and the loop
over a zero-filled buffer that it replaced (`take_gather`), the way back
to the tokens (`moe_combine`, with and without the routing weights),
XLA's gather over every (token, choice) that `moe_combine` replaced, and
the whole layer forward and forward + backward, under a random router.

This is how the one-layer numbers of PERF.md section 5 and 6 (PR 31, PR
37) were measured, at the shapes of the cells lfm2_train_1chip,
solar_train_1chip and mellum2_train_1chip:

    chiprun --chips 1 -- python tools/bench_expert_ffn.py \\
        '[[16384,4,2048,1536,8,64]]'
    ... '[[8192,8,4096,1280,8,320]]'
    ... '[[8192,8,2304,896,16,64]]'

Shapes are [tokens, k, hidden, expert width, experts held, experts]. One
JSON line per (shape, piece); all of them again in
chiprun_out/bench_expert_ffn.json. A time means something on the chip
only: where JAX's first device is not a TPU the tool measures nothing,
writes nothing and exits 2.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def take_combine(rows, dest, weights=None):
    """What `moe_combine` replaced: a row gathered for each of the N * k
    pairs, a pair that is not held reading a filled-in zero."""
    import jax.numpy as jnp
    picked = jnp.take(rows, dest, axis=0, mode="fill", fill_value=0)
    picked = picked.astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[..., None]
    return jnp.sum(picked, axis=1).astype(rows.dtype)


def take_gather(x, plan, tm):
    """What `_gather_rows` was until PR 37: the same loop over the tiles
    in use, into a buffer of the worst case's rows that XLA filled with
    zeros first."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    zeros = jnp.zeros((plan["src"].shape[0], x.shape[1]), x.dtype)
    return gm._fill_tiles(zeros, x, plan, tm)


def pieces(N, k, H, F, held, experts):
    """{piece: (function, arguments)} on seeded bf16 arrays; every token
    chooses k distinct experts at random, the first `held` are here."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    rng = np.random.default_rng(0)
    tm = gm.DEFAULT_TILE_ROWS

    def rand(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.bfloat16)

    idx = jnp.asarray(np.argsort(rng.random((N, experts)), axis=1)[:, :k],
                      jnp.int32)
    tw = jnp.asarray(rng.uniform(0.1, 0.4, (N, k)), jnp.float32)
    x, w1, w3 = rand(N, H), rand(held, H, F, scale=.02), \
        rand(held, H, F, scale=.02)
    w2 = rand(held, F, H, scale=.02)
    plan = jax.jit(lambda i: gm.make_plan(i, 0, held, tm))(idx)
    plan.pop("counts")
    rows = rand(plan["src"].shape[0], H)

    def layer(x, tw, w1, w3, w2):
        return gm.expert_ffn(x, idx, tw, w1, w3, w2)[0]

    def loss(*a):
        return jnp.sum(jnp.square(layer(*a).astype(jnp.float32)))

    return {
        "plan": (lambda i: gm.make_plan(i, 0, held, tm), (idx,)),
        "gather": (lambda x, p: gm._gather_rows(x, p, tm), (x, plan)),
        "take_gather": (lambda x, p: take_gather(x, p, tm), (x, plan)),
        "combine": (lambda r, p: gm._combine(r, p, None, held, tm, False),
                    (rows, plan)),
        "combine_weighted": (
            lambda r, p, w: gm._combine(r, p, w, held, tm, False),
            (rows, plan, tw)),
        "take": (lambda r, p: take_combine(r, p["dest"]), (rows, plan)),
        "take_weighted": (lambda r, p, w: take_combine(r, p["dest"], w),
                          (rows, plan, tw)),
        "layer_fwd": (layer, (x, tw, w1, w3, w2)),
        "layer_fwd_bwd": (jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                          (x, tw, w1, w3, w2)),
    }, int(np.sum(np.asarray(idx) < held))


def bench(fn, args, n=20):
    """ms a call of the jitted `fn`."""
    import jax
    step = jax.jit(fn)
    for _ in range(2):
        jax.block_until_ready(step(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def main(argv):
    import jax
    shapes = json.loads(argv[0]) if argv else [[16384, 4, 2048, 1536, 8, 64]]
    n = int(argv[1]) if len(argv) > 1 else 20
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench_expert_ffn: the first device is a {platform}, not a "
              "TPU; nothing measured", file=sys.stderr)
        return 2
    lines = []
    for shape in shapes:
        todo, pairs = pieces(*shape)
        for piece, (fn, args) in todo.items():
            line = {"platform": platform, "piece": piece, "shape": shape,
                    "pairs_held": pairs}
            try:
                line["ms"] = round(bench(fn, args, n), 4)
            except Exception as e:   # out of memory, no tiling
                line["error"] = str(e)[:300]
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_expert_ffn.json", "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
