#!/usr/bin/env python
"""Time one attention, forward and backward apart, three ways: the
short-sequence Pallas kernel, XLA's composition (`_sdpa`) and the tiled
Pallas kernel, on arrays in the op's `bthd` layout ([B, T, H*D], as
`layers.multi_head_attention` has them; the tiled kernel's time includes
the transposes `try_flash` makes for it).

This is how the crossovers in `ops/pallas/flash_attention.py` (the table
above SHORT_MIN_SEQ_LEN; PERF.md section 6, PR 28) and the block sweep
above DEFAULT_BLOCK_Q (PR 33) were measured:

    chiprun --chips 1 -- python tools/bench_attention.py \\
        '[[128,128,128,8,64],[128,256,256,8,64],[128,512,512,8,64]]' \\
        short,sdpa,tiled
    chiprun --chips 1 -- python tools/bench_attention.py \\
        '[[2,8192,8192,32,64,8]]' tiled,tiled:512x512,tiled:1024x1024 \\
        20 causal,nobias
    chiprun --chips 1 -- python tools/bench_attention.py \
        '[[1,8192,8192,32,128,4]]' tiled,tiled:512x512,tiled:256x512 \
        20 causal,nobias,window1024

Shapes are [B, T, S, H, D] or, with key-value heads shared by groups of
query heads (the tiled kernel and the composition's reference only),
[B, T, S, H, D, KVH]. `tiled:<block_q>x<block_k>` is the tiled kernel at
those blocks (`tiled`: at its defaults). The fourth argument is a comma
list of `causal` / `full` (one masking only; both by default), `nobias`
(no key-padding bias) and `window<n>` (a sliding window of n keys under
the causal diagonal: the band's block sweep above DEFAULT_BLOCK_Q). One
JSON line per (shape, causal,
implementation) with `fwd_ms`, `bwd_ms` (the vjp alone, the residuals
kept) and `ms` (both in one program); all of them again in
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


def _implementation(impl, window=None):
    """`impl` (short, sdpa, tiled, tiled:<bq>x<bk>) as a function of
    `bthd` q, k, v, bias and causal under `window` (the caller's: none
    where nothing is causal; the short kernel has none)."""
    from paddle_tpu.ops import kernels_nn
    from paddle_tpu.ops.pallas import flash_attention as fa
    name, _, blocks = impl.partition(":")
    blocks = dict(zip(("block_q", "block_k"),
                      (int(b) for b in blocks.split("x")))) if blocks else {}

    def sdpa(q, k, v, bias, causal):
        group = q.shape[2] // k.shape[2]
        if group > 1:
            k, v = (x.repeat(group, axis=2) for x in (k, v))
        ins = {"Q": [q], "K": [k], "V": [v],
               "Mask": [] if bias is None else [bias]}
        attrs = {"layout": "bthd", "causal": causal,
                 "scale": q.shape[-1] ** -0.5,
                 "window": window}
        return kernels_nn._sdpa(None, ins, attrs)["Out"][0]

    def tiled(q, k, v, bias, causal):
        return fa.flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            bias=bias, causal=causal, window=window,
            **blocks).swapaxes(1, 2)

    def short(q, k, v, bias, causal):
        return fa.flash_attention_bthd(q, k, v, bias=bias, causal=causal)

    return {"short": short, "sdpa": sdpa, "tiled": tiled}[name]


def bench(impl, B, T, S, H, D, causal, KVH=None, with_bias=True, n=20,
          window=None):
    """{fwd_ms, bwd_ms, ms} a call of `impl` on seeded bf16 arrays with a
    key-padding bias; the backward gets a random cotangent."""
    import jax
    import jax.numpy as jnp
    fn = _implementation(impl, window)
    rng = np.random.RandomState(0)
    KVH = KVH or H

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    q, k, v, w = rand(B, T, H * D), rand(B, S, KVH * D), \
        rand(B, S, KVH * D), rand(B, T, H * D)
    lens = rng.randint(S // 2, S + 1, (B,))
    keep = (np.arange(S)[None] < lens[:, None]).astype("float32")
    bias = jnp.asarray((keep - 1) * 1e9).reshape(B, 1, 1, S) \
        if with_bias else None

    def attend(q, k, v):
        return fn(q.reshape(B, T, H, D), k.reshape(B, S, KVH, D),
                  v.reshape(B, S, KVH, D), bias, causal).reshape(B, T, H * D)

    def both(q, k, v):
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(w)

    def ms(step, *args):
        for _ in range(2):
            jax.block_until_ready(step(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            out = step(*args)
        jax.block_until_ready(out)
        return round(1e3 * (time.perf_counter() - t0) / n, 4)

    # the vjp alone: jax.vjp's function is a pytree of the residuals
    vjp = jax.jit(lambda q, k, v: jax.vjp(attend, q, k, v)[1])(q, k, v)
    return {"fwd_ms": ms(jax.jit(attend), q, k, v),
            "bwd_ms": ms(jax.jit(lambda f, w: f(w)), vjp, w),
            "ms": ms(jax.jit(both), q, k, v)}


def main(argv):
    import jax
    shapes = json.loads(argv[0]) if argv else [[128, 256, 256, 8, 64]]
    impls = argv[1].split(",") if len(argv) > 1 \
        else ["short", "sdpa", "tiled"]
    n = int(argv[2]) if len(argv) > 2 else 20
    flags = argv[3].split(",") if len(argv) > 3 else []
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench_attention: the first device is a {platform}, not a "
              "TPU; nothing measured", file=sys.stderr)
        return 2
    only = {"causal", "full"} & set(flags)
    window = next((int(f[len("window"):]) for f in flags
                   if f.startswith("window")), None)
    maskings = [c for c in (False, True)
                if not only or ("causal" if c else "full") in only]
    lines = []
    for B, T, S, H, D, *kvh in shapes:
        for causal in maskings:
            for impl in impls:
                line = {"platform": platform, "impl": impl, "B": B,
                        "T": T, "S": S, "H": H, "D": D,
                        "KVH": kvh[0] if kvh else H, "causal": causal,
                        "bias": "nobias" not in flags,
                        "window": window}
                try:
                    line.update(bench(impl, B, T, S, H, D, causal,
                                      line["KVH"], line["bias"], n,
                                      line["window"]))
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
