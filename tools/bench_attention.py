#!/usr/bin/env python
"""Time one attention, forward + backward and forward alone, three ways:
the short-sequence Pallas kernel, XLA's composition (`_sdpa`) and the
tiled Pallas kernel, on arrays in the op's `bthd` layout ([B, T, H*D],
as `layers.multi_head_attention` has them; the tiled kernel's time
includes the transposes `try_flash` makes for it).

This is how the crossovers in `ops/pallas/flash_attention.py` (the table
above SHORT_MIN_SEQ_LEN) and PERF.md section 6 (PR 28) were measured:

    chiprun --chips 1 -- python tools/bench_attention.py \\
        '[[128,128,128,8,64],[128,256,256,8,64],[128,512,512,8,64]]' \\
        short,sdpa,tiled

Shapes are [B, T, S, H, D]. One JSON line per (shape, causal,
implementation, mode); all of them again in
chiprun_out/bench_attention.json. A time means something on the chip
only: where JAX's first device is not a TPU the tool measures nothing,
writes nothing and exits 2.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _implementations():
    from paddle_tpu.ops import kernels_nn
    from paddle_tpu.ops.pallas import flash_attention as fa

    def sdpa(q, k, v, bias, causal):
        ins = {"Q": [q], "K": [k], "V": [v], "Mask": [bias]}
        attrs = {"layout": "bthd", "causal": causal,
                 "scale": q.shape[-1] ** -0.5}
        return kernels_nn._sdpa(None, ins, attrs)["Out"][0]

    def tiled(q, k, v, bias, causal):
        return fa.flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            bias=bias, causal=causal).swapaxes(1, 2)

    def short(q, k, v, bias, causal):
        return fa.flash_attention_bthd(q, k, v, bias=bias, causal=causal)

    return {"short": short, "sdpa": sdpa, "tiled": tiled}


def bench(impl, B, T, S, H, D, causal, fwd_only=False, n=20):
    """ms a call of `impl` (a name of _implementations) on seeded bf16
    arrays with a key-padding bias; the backward gets a random
    cotangent."""
    import jax
    import jax.numpy as jnp
    fn = _implementations()[impl]
    rng = np.random.RandomState(0)
    HD = H * D

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    q, k, v, w = rand(B, T, HD), rand(B, S, HD), rand(B, S, HD), \
        rand(B, T, HD)
    lens = rng.randint(S // 2, S + 1, (B,))
    keep = (np.arange(S)[None] < lens[:, None]).astype("float32")
    bias = jnp.asarray((keep - 1) * 1e9).reshape(B, 1, 1, S)

    def attend(q, k, v):
        return fn(q.reshape(B, T, H, D), k.reshape(B, S, H, D),
                  v.reshape(B, S, H, D), bias, causal).reshape(B, T, HD)

    def both(q, k, v):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(w)

    step = jax.jit(attend if fwd_only else both)
    for _ in range(2):
        jax.block_until_ready(step(q, k, v))
    t0 = time.perf_counter()
    for _ in range(n):
        out = step(q, k, v)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def main(argv):
    import jax
    shapes = json.loads(argv[0]) if argv else [[128, 256, 256, 8, 64]]
    impls = argv[1].split(",") if len(argv) > 1 \
        else ["short", "sdpa", "tiled"]
    n = int(argv[2]) if len(argv) > 2 else 20
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench_attention: the first device is a {platform}, not a "
              "TPU; nothing measured", file=sys.stderr)
        return 2
    lines = []
    for B, T, S, H, D in shapes:
        for causal in (False, True):
            for impl in impls:
                for fwd_only in (False, True):
                    line = {"platform": platform, "impl": impl, "B": B,
                            "T": T, "S": S, "H": H, "D": D,
                            "causal": causal, "fwd_only": fwd_only}
                    try:
                        line["ms"] = round(bench(impl, B, T, S, H, D,
                                                 causal, fwd_only, n), 4)
                    except Exception as e:   # out of memory, no tiling
                        line["error"] = str(e)[:300]
                    print(json.dumps(line), flush=True)
                    lines.append(line)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/bench_attention.json", "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
