"""One general traffic generator, driven by the data files under traffic/.

A traffic file is a JSON object with a `kind`. Today there is one:

- `train_batches`: `batch_rows` x `src_len` / `trg_len` token batches, a
  `pool` of distinct seeded host batches fed in turn.

The program sees only what is generated here. Everything is a function of
(traffic file, sizes, seed); `--seed` may pass 2**31.
"""
import numpy as np


def make_train_batches(traffic, cfg, seed):
    """`pool` host batches {src, src_len, trg, trg_len, label}, rows all
    different. The target is a fixed function of the source (shifted by
    one id), so the loss can fall; no padding (a token-bucketed batch)."""
    rng = np.random.default_rng([int(seed), 1])
    B, Ts, Tt = traffic["batch_rows"], traffic["src_len"], traffic["trg_len"]
    lo = traffic.get("first_token_id", 3)
    out = []
    for _ in range(traffic["pool"]):
        src = rng.integers(lo, cfg["src_vocab"], (B, Ts)).astype("int64")
        label = ((src[:, :Tt] + 1) % cfg["trg_vocab"]).astype("int64")
        trg = np.concatenate([np.zeros((B, 1), "int64"), label[:, :-1]], 1)
        out.append({"src": src, "src_len": np.full((B,), Ts, "int64"),
                    "trg": trg, "trg_len": np.full((B,), Tt, "int64"),
                    "label": label})
    return out
