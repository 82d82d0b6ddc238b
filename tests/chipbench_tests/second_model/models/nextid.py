"""Model `nextid`: the tests' stand-in for a second model. Token ids
[rows, length] -> embedding -> one fc with relu -> fc to the vocabulary ->
softmax cross-entropy on the next id, built from `fluid.layers`; its own
parameters, traffic kind (`id_batches`), count and plain float32 reference.
It is copied into a temporary root's `chipbench/models/` beside a
configuration, a traffic file and appended entries, and nothing that is
there is edited (chipbench_tiny.py, test_second_model.py)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import seed_key

HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ the program
def build(cfg, traffic, fluid):
    layers = fluid.layers
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            T = traffic["length"]
            ids = layers.data("ids", shape=[T], dtype="int64")
            nxt = layers.data("next_id", shape=[T], dtype="int64")
            x = layers.embedding(ids, size=[cfg["vocab"], cfg["d_embed"]])
            h = layers.fc(x, cfg["d_hidden"], num_flatten_dims=2, act="relu")
            logits = layers.fc(h, cfg["vocab"], num_flatten_dims=2)
            loss = layers.mean(layers.softmax_with_cross_entropy(
                logits, layers.unsqueeze(nxt, [2])))
            fluid.optimizer.Adam(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["epsilon"]).minimize(loss)
    return main, startup, loss


def param_specs(cfg):
    V, E, H = cfg["vocab"], cfg["d_embed"], cfg["d_hidden"]
    return [("embedding_0.w_0", (V, E), "embedding"),
            ("fc_0.w_0", (E, H), "matrix"), ("fc_0.b_0", (H,), "bias"),
            ("fc_1.w_0", (H, V), "matrix"), ("fc_1.b_0", (V,), "bias")]


def make_params(cfg, seed, dtype):
    specs = param_specs(cfg)

    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "bias":
                out[name] = jnp.zeros(shape, dtype)
            else:
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * shape[0] ** -0.5).astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))


# ------------------------------------------------------------ the traffic
def make_batches(traffic, cfg, seed):
    if traffic["kind"] != "id_batches":
        raise ValueError(f"nextid reads id_batches, not {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 7])
    out = []
    for _ in range(traffic["pool"]):
        ids = rng.integers(0, cfg["vocab"],
                           (traffic["rows"], traffic["length"] + 1))
        out.append({"ids": ids[:, :-1].astype("int64"),
                    "next_id": ids[:, 1:].astype("int64")})
    return out


def tokens_per_step(traffic):
    return traffic["rows"] * traffic["length"]


def step_flops(cfg, traffic):
    per_token = 2 * (cfg["d_embed"] * cfg["d_hidden"]
                     + cfg["d_hidden"] * cfg["vocab"])
    return 3 * tokens_per_step(traffic) * per_token


# ---------------------------------------------------------- the reference
def _int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, prec):
    if prec == "int8":
        a, b = _int8(a), _int8(b)
    return jnp.matmul(a, b, precision=HI)


def loss_sum(p, batch, prec):
    x = jnp.take(p["embedding_0.w_0"], batch["ids"], axis=0)
    h = jax.nn.relu(_mm(x, p["fc_0.w_0"], prec) + p["fc_0.b_0"])
    logits = _mm(h, p["fc_1.w_0"], prec) + p["fc_1.b_0"]
    picked = jnp.take_along_axis(logits, batch["next_id"][..., None], -1)
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked[..., 0])


def tree_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames="prec")
def _grad(p, batch, prec):
    return jax.value_and_grad(loss_sum)(p, batch, prec)


def reference_steps(params, cfg, batches, opt, prec, block_rows, rows=None):
    """Adam steps in float32, the batch walked in blocks of rows; Adam as
    Fluid's (Kingma & Ba's efficient form)."""
    p0 = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    p, b1, b2 = p0, opt["beta1"], opt["beta2"]
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    out = {"loss": []}
    for t, batch in enumerate(batches, 1):
        if rows is not None:
            batch = {k: x[rows] for k, x in batch.items()}
        n, total, grad = len(batch["ids"]), 0.0, None
        for lo in range(0, n, block_rows):
            blk = {k: np.asarray(x[lo:lo + block_rows], np.int32)
                   for k, x in batch.items()}
            part, g = _grad(p, blk, prec)
            total += part
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
        ntok = float(batch["ids"].size)
        grad = {k: g / ntok for k, g in grad.items()}
        out["loss"].append(float(total / ntok))
        if t == 1:
            out["grad_norm"] = {k: float(x)
                                for k, x in tree_norms(grad).items()}
        lr_t = opt["lr"] * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
        m = {k: b1 * m[k] + (1 - b1) * grad[k] for k in p}
        v = {k: b2 * v[k] + (1 - b2) * jnp.square(grad[k]) for k in p}
        p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + opt["epsilon"])
             for k in p}
    out["delta_norm"] = {k: float(x) for k, x in tree_norms(
        {k: p[k] - p0[k] for k in p}).items()}
    return out
