"""The model's parameters, made on the device from --seed in one jitted call.

The benchmark makes the weights; the program and the plain reference are
both handed these (the program through its scope, by the parameter names
its transformer declares with `fused_qkv=True`). Initialisation: Xavier
uniform for matrices, N(0, 1/d_model) for embeddings, LayerNorm scale 1 and
every bias 0. Matrices and embeddings come in `dtype`, the type they are
run in (bfloat16 for the training cell); LayerNorm parameters are float32.
"""
import jax
import jax.numpy as jnp


def param_specs(cfg):
    """[(name, shape, kind)] in the order the program declares them."""
    d, di, L = cfg["d_model"], cfg["d_inner"], cfg["n_layer"]
    specs, ln = [], 0

    def norm():
        nonlocal ln
        specs.append((f"layer_norm_{ln}.w_0", (d,), "ln_scale"))
        specs.append((f"layer_norm_{ln}.b_0", (d,), "ln_bias"))
        ln += 1

    def ffn(name):
        specs.append((f"{name}_fc1.w_0", (d, di), "matrix"))
        specs.append((f"{name}_fc1.b_0", (di,), "bias"))
        specs.append((f"{name}_fc2.w_0", (di, d), "matrix"))
        specs.append((f"{name}_fc2.b_0", (d,), "bias"))

    specs.append(("src_emb.w_0", (cfg["src_vocab"], d), "embedding"))
    for i in range(L):
        specs.append((f"enc{i}_qkv.w_0", (d, 3 * d), "matrix"))
        specs.append((f"enc{i}_o.w_0", (d, d), "matrix"))
        norm()
        ffn(f"enc{i}_ffn")
        norm()
    specs.append(("trg_emb.w_0", (cfg["trg_vocab"], d), "embedding"))
    for i in range(L):
        specs.append((f"dec{i}_self_qkv.w_0", (d, 3 * d), "matrix"))
        specs.append((f"dec{i}_self_o.w_0", (d, d), "matrix"))
        norm()
        specs.append((f"dec{i}_cross_q.w_0", (d, d), "matrix"))
        specs.append((f"dec{i}_cross_kv.w_0", (d, 2 * d), "matrix"))
        specs.append((f"dec{i}_cross_o.w_0", (d, d), "matrix"))
        norm()
        ffn(f"dec{i}_ffn")
        norm()
    specs.append(("proj.w_0", (d, cfg["trg_vocab"]), "matrix"))
    return specs


def seed_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31).
    The generator is named: importing the program switches JAX's default
    to "rbg", and the weights must not depend on who was imported first."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32"), seed >> 31)


def make_params(cfg, seed, dtype):
    """{name: array} on the default device, one jitted call."""
    specs = param_specs(cfg)
    dtype = jnp.dtype(dtype)

    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            if kind == "matrix":
                lim = (6.0 / (shape[0] + shape[1])) ** 0.5
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -lim, lim).astype(dtype)
            elif kind == "embedding":
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * shape[1] ** -0.5).astype(dtype)
            elif kind == "ln_scale":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "ln_bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, dtype)
        return out

    return jax.jit(make)(seed_key(seed))
