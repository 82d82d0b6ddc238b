"""Systematic finite-difference gradient sweep over the kernel registry
(VERDICT r4 #4 — the analog of the reference's op_test.py check_grad
harness, /root/reference/python/paddle/fluid/tests/unittests/op_test.py).

Every op type in paddle_tpu.ops.registry.KERNELS must be EITHER:
  - spec'd in SPECS below → its kernel is grad-checked: analytic grads
    (jax.grad of a fixed random projection of all float outputs) vs
    central finite differences in float64, a few coordinates per input;
  - or excluded in EXCLUDE with an honest reason (non-differentiable,
    integer/bool domain, optimizer update, discrete selection, ...).
test_registry_fully_classified enforces the partition is total and the
lists carry no stale entries, exactly like the parity sweeps — so a new
kernel cannot land unchecked silently.

Kernels run DIRECTLY (fn(ctx, ins, attrs)) rather than through a full
Program: what is being checked is each kernel's differentiability and
gradient correctness (custom_vjp bodies, where()-NaN traps, stop-
gradient mistakes), not the executor plumbing, which test_grad_check.py
already covers end-to-end.
"""
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — populates the registry
from paddle_tpu.ops.registry import KERNELS, KernelCtx, get_kernel

# ---------------------------------------------------------------------------
# spec machinery
# ---------------------------------------------------------------------------

_RNG_SEED = 20240731


def S(ins, attrs=None, diff=None, eps=1e-5, rtol=2e-3, atol=1e-7,
      n_coords=3, f32=False):
    """ins: {slot: value-spec or [value-spec, ...]} where a value-spec is
      (shape...)            float input, default away-from-zero signed gen
      ("pos", shape)        uniform(0.3, 1.5)  — log/sqrt domains
      ("unit", shape)       uniform(-0.85, 0.85) — asin/acos domains
      ("prob", shape)       softmax'd positive rows — probability inputs
      ("int", shape, hi)    integer input in [0, hi)
      ("zero_one", shape)   random 0/1 floats — binary labels
      np.ndarray            used verbatim
    diff: slots to differentiate (default: every float slot).
    f32=True: the kernel deliberately computes in float32 internally
      (fp32-accumulate TPU pattern — .astype(jnp.float32) in the kernel
      body), so finite differences carry float32 rounding noise
      ~eps_f32*|f|/eps; use the f32-optimal step and tolerances."""
    if f32:
        eps, rtol, atol = max(eps, 2e-3), max(rtol, 2.5e-2), \
            max(atol, 2.5e-3)
    return {"ins": ins, "attrs": attrs or {}, "diff": diff, "eps": eps,
            "rtol": rtol, "atol": atol, "n_coords": n_coords}


def _make_value(spec, rng):
    if isinstance(spec, np.ndarray):
        return spec
    if isinstance(spec, tuple) and spec and isinstance(spec[0], str):
        kind = spec[0]
        if kind == "pos":
            return rng.uniform(0.3, 1.5, spec[1]).astype(np.float64)
        if kind == "unit":
            return rng.uniform(-0.85, 0.85, spec[1]).astype(np.float64)
        if kind == "prob":
            z = rng.uniform(0.2, 1.0, spec[1]).astype(np.float64)
            return z / z.sum(axis=-1, keepdims=True)
        if kind == "int":
            return rng.randint(0, spec[2], spec[1]).astype(np.int32)
        if kind == "zero_one":
            return rng.randint(0, 2, spec[1]).astype(np.float64)
        raise ValueError(f"unknown gen kind {kind}")
    # plain shape tuple: signed values with |x| in [0.3, 1.5] — keeps
    # clear of the kinks at 0 (relu/abs) and of pool/max ties
    arr = rng.uniform(0.3, 1.5, spec) * rng.choice([-1.0, 1.0], spec)
    return arr.astype(np.float64)


def _is_float(a):
    return jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)


def _run_grad_check(op, spec):
    rng = np.random.RandomState(
        _RNG_SEED + zlib.crc32(op.encode()) % 1000)
    with jax.enable_x64():
        ins = {}
        for slot, vs in spec["ins"].items():
            vals = vs if isinstance(vs, list) else [vs]
            ins[slot] = [jnp.asarray(_make_value(v, rng)) for v in vals]
        ctx = KernelCtx(key=jax.random.PRNGKey(7), is_test=False)
        fn = get_kernel(op)

        diff_slots = spec["diff"] or [s for s in ins
                                      if all(_is_float(a)
                                             for a in ins[s])]
        flat = [(slot, i) for slot in diff_slots
                for i in range(len(ins[slot]))]
        assert flat, f"{op}: no differentiable inputs in spec"

        # fixed random projection of every float output → scalar
        outs0 = fn(ctx, {k: list(v) for k, v in ins.items()},
                   spec["attrs"])
        projs = []
        for oslot in sorted(outs0):
            for j, o in enumerate(outs0[oslot]):
                if o is not None and _is_float(o) \
                        and np.asarray(o).size:
                    projs.append((oslot, j, jnp.asarray(
                        rng.uniform(0.5, 1.5, np.shape(o)))))
        assert projs, f"{op}: kernel produced no float outputs"

        def scalar_fn(*args):
            ins2 = {k: list(v) for k, v in ins.items()}
            for (slot, i), a in zip(flat, args):
                ins2[slot][i] = a
            outs = fn(ctx, ins2, spec["attrs"])
            total = 0.0
            for oslot, j, p in projs:
                total = total + jnp.sum(outs[oslot][j] * p)
            # pull NON-float outputs (argmax masks, index tensors) into
            # the trace at zero weight: the executor traces every op
            # output, so a primitive that breaks linearization when its
            # int output is live (e.g. a pair-carrying reduce_window)
            # must fail HERE, not only in end-to-end training
            for oslot in sorted(outs):
                for o in outs[oslot]:
                    if o is not None and not _is_float(o) \
                            and getattr(o, "size", 0):
                        total = total + 0.0 * jnp.sum(
                            jnp.asarray(o).astype(jnp.float32))
            return total

        args0 = [ins[slot][i] for slot, i in flat]
        val0, grads = jax.value_and_grad(
            scalar_fn, argnums=tuple(range(len(args0))))(*args0)
        assert np.isfinite(float(val0)), f"{op}: non-finite output"

        jfn = jax.jit(scalar_fn)
        eps = spec["eps"]
        for k, ((slot, i), g) in enumerate(zip(flat, grads)):
            g = np.asarray(g)
            assert np.all(np.isfinite(g)), \
                f"{op}: non-finite analytic grad for {slot}[{i}]"
            base = np.asarray(args0[k])
            fsize = base.size
            if fsize == 0:
                continue
            coords = rng.choice(fsize, size=min(spec["n_coords"], fsize),
                                replace=False)
            for c in coords:
                pert = base.reshape(-1).copy()
                pert[c] += eps
                hi_args = list(args0)
                hi_args[k] = jnp.asarray(pert.reshape(base.shape))
                hi = float(jfn(*hi_args))
                pert[c] -= 2 * eps
                hi_args[k] = jnp.asarray(pert.reshape(base.shape))
                lo = float(jfn(*hi_args))
                fd = (hi - lo) / (2 * eps)
                an = float(g.reshape(-1)[c])
                tol = spec["atol"] + spec["rtol"] * max(
                    abs(fd), abs(an), 1e-3)
                assert abs(fd - an) <= tol, (
                    f"{op} {slot}[{i}] coord {c}: "
                    f"analytic {an:.6g} vs fd {fd:.6g} (tol {tol:.2g})")


# ---------------------------------------------------------------------------
# specs — inputs follow the reference op conventions (slot names from
# the corresponding kernels_*.py registrations)
# ---------------------------------------------------------------------------

SPECS = {}

# activations / unary: slot X
for _op in ["abs", "cos", "cosh", "elu", "erf", "exp", "gelu",
            "leaky_relu", "logsigmoid", "mish", "reciprocal", "relu",
            "selu", "sigmoid", "silu", "sin", "sinh", "softplus",
            "softsign", "square", "swish", "tan", "tanh",
            "tanh_shrink", "stanh", "soft_relu", "hard_swish"]:
    SPECS[_op] = S({"X": (3, 4)})
SPECS["relu6"] = S({"X": (3, 4)})           # gen keeps |x| ≤ 1.5 < 6
SPECS["hard_sigmoid"] = S({"X": (3, 4)})    # kinks at ±3; |x| ≤ 1.5
SPECS["thresholded_relu"] = S({"X": (3, 4)},
                              {"threshold": 0.2})  # |x| ≥ 0.3
SPECS["log"] = S({"X": ("pos", (3, 4))})
SPECS["log1p"] = S({"X": ("pos", (3, 4))})
SPECS["sqrt"] = S({"X": ("pos", (3, 4))})
SPECS["rsqrt"] = S({"X": ("pos", (3, 4))})
SPECS["asin"] = S({"X": ("unit", (3, 4))})
SPECS["acos"] = S({"X": ("unit", (3, 4))})
SPECS["atan"] = S({"X": (3, 4)})
SPECS["pow"] = S({"X": ("pos", (3, 4))}, {"factor": 2.5})
SPECS["clip"] = S({"X": (3, 4)}, {"min": -1.4, "max": 1.4})
SPECS["scale"] = S({"X": (3, 4)}, {"scale": 2.0, "bias": 0.5})
SPECS["clip_by_norm"] = S({"X": (3, 4)}, {"max_norm": 1.0}, f32=True)

# elementwise binary: X, Y
for _op in ["elementwise_add", "elementwise_sub", "elementwise_mul",
            "elementwise_div"]:
    SPECS[_op] = S({"X": (3, 4), "Y": (3, 4)})
SPECS["elementwise_max"] = S({"X": (3, 4), "Y": (3, 4)})
SPECS["elementwise_min"] = S({"X": (3, 4), "Y": (3, 4)})
SPECS["elementwise_pow"] = S({"X": ("pos", (3, 4)),
                              "Y": ("pos", (3, 4))})
SPECS["elementwise_mod"] = S({"X": ("pos", (3, 4)),
                              "Y": np.full((3, 4), 2.0)}, diff=["X"])
SPECS["minus"] = S({"X": (3, 4), "Y": (3, 4)})
SPECS["maximum"] = S({"X": (3, 4), "Y": (3, 4)})

# matmul family
SPECS["matmul"] = S({"X": (3, 4), "Y": (4, 5)})
SPECS["matmul_v2"] = S({"X": (2, 3, 4), "Y": (2, 4, 5)})
SPECS["mul"] = S({"X": (3, 4), "Y": (4, 5)})
SPECS["bmm"] = S({"X": (2, 3, 4), "Y": (2, 4, 5)})
SPECS["dot"] = S({"X": (3, 6), "Y": (3, 6)})
SPECS["bilinear_tensor_product"] = S(
    {"X": (3, 4), "Y": (3, 5), "Weight": (6, 4, 5), "Bias": (1, 6)})
SPECS["cos_sim"] = S({"X": (3, 6), "Y": (3, 6)})
SPECS["fc"] = S({"Input": (3, 4), "W": (4, 5), "Bias": (5,)})

# reductions
for _op in ["reduce_sum", "reduce_mean", "reduce_prod"]:
    SPECS[_op] = S({"X": (3, 4)}, {"dim": [1], "keep_dim": False})
SPECS["reduce_max"] = S({"X": (3, 4)}, {"dim": [1]})
SPECS["reduce_min"] = S({"X": (3, 4)}, {"dim": [1]})
SPECS["max"] = S({"X": (3, 4), "Y": (3, 4)})
SPECS["logsumexp"] = S({"X": (3, 4)})
SPECS["frobenius_norm"] = S({"X": (3, 4)}, {"dim": [1]})
SPECS["l1_norm"] = S({"X": (3, 4)})
SPECS["squared_l2_norm"] = S({"X": (3, 4)}, f32=True)
SPECS["squared_l2_distance"] = S({"X": (3, 4), "Y": (3, 4)})
SPECS["l2_normalize"] = S({"X": (3, 4)}, {"axis": 1})
SPECS["norm"] = S({"X": (3, 4)}, {"axis": 1})
SPECS["mean"] = S({"X": (3, 4)})
SPECS["sum"] = S({"X": [(3, 4), (3, 4), (3, 4)]})
SPECS["cumsum"] = S({"X": (3, 4)}, {"axis": 1})

# shape/data movement (all linear maps)
SPECS["reshape"] = S({"X": (3, 4)}, {"shape": [4, 3]})
SPECS["reshape2"] = S({"X": (3, 4)}, {"shape": [2, 6]})
SPECS["transpose"] = S({"X": (2, 3, 4)}, {"axis": [2, 0, 1]})
SPECS["transpose2"] = S({"X": (2, 3, 4)}, {"axis": [1, 0, 2]})
SPECS["flatten"] = S({"X": (2, 3, 4)}, {"axis": 1})
SPECS["flatten2"] = S({"X": (2, 3, 4)}, {"axis": 2})
SPECS["squeeze"] = S({"X": (3, 1, 4)}, {"axes": [1]})
SPECS["squeeze2"] = S({"X": (3, 1, 4)}, {"axes": [1]})
SPECS["unsqueeze"] = S({"X": (3, 4)}, {"axes": [1]})
SPECS["unsqueeze2"] = S({"X": (3, 4)}, {"axes": [0]})
SPECS["concat"] = S({"X": [(3, 2), (3, 3)]}, {"axis": 1})
SPECS["split"] = S({"X": (3, 6)}, {"num": 3, "axis": 1})
SPECS["stack"] = S({"X": [(3, 4), (3, 4)]}, {"axis": 0})
SPECS["unstack"] = S({"X": (3, 4)}, {"axis": 0, "num": 3})
SPECS["slice"] = S({"Input": (3, 6)},
                   {"axes": [1], "starts": [1], "ends": [5]})
SPECS["strided_slice"] = S(
    {"Input": (3, 8)},
    {"axes": [1], "starts": [0], "ends": [8], "strides": [2]})
SPECS["expand"] = S({"X": (1, 4)}, {"expand_times": [3, 1]})
SPECS["expand_as"] = S({"X": (1, 4), "target_tensor": (3, 4)},
                       diff=["X"])
SPECS["tile"] = S({"X": (2, 3)}, {"repeat_times": [2, 2]})
SPECS["roll"] = S({"X": (3, 4)}, {"shifts": [1], "axis": [1]})
SPECS["reverse"] = S({"X": (3, 4)}, {"axis": [1]})
SPECS["pad"] = S({"X": (3, 4)}, {"paddings": [1, 1, 0, 2],
                                 "pad_value": 0.0})
SPECS["pad2d"] = S({"X": (2, 3, 4, 4)},
                   {"paddings": [1, 1, 1, 1], "mode": "constant"})
SPECS["pad_constant_like"] = S({"X": (4, 5), "Y": (3, 4)}, diff=["Y"])
SPECS["crop"] = S({"X": (4, 6)}, {"offsets": [1, 1], "shape": [2, 3]})
SPECS["gather"] = S({"X": (5, 4), "Index": ("int", (3,), 5)})
SPECS["gather_nd"] = S({"X": (4, 5), "Index": ("int", (3, 2), 4)})
SPECS["scatter"] = S({"X": (5, 4), "Ids": np.array([1, 3], np.int32),
                      "Updates": (2, 4)}, diff=["X", "Updates"])
SPECS["scatter_nd_add"] = S(
    {"X": (5, 4), "Index": np.array([[1], [3]], np.int32),
     "Updates": (2, 4)}, diff=["X", "Updates"])
SPECS["where"] = S({"Condition": np.random.RandomState(0)
                    .randint(0, 2, (3, 4)).astype(bool),
                    "X": (3, 4), "Y": (3, 4)}, diff=["X", "Y"])
SPECS["multiplex"] = S(
    {"Ids": np.array([[0], [1], [0]], np.int32),
     "X": [(3, 4), (3, 4)]}, diff=["X"])
SPECS["space_to_depth"] = S({"X": (2, 3, 4, 4)}, {"blocksize": 2})
SPECS["pixel_shuffle"] = S({"X": (2, 4, 3, 3)}, {"upscale_factor": 2})
SPECS["shuffle_channel"] = S({"X": (2, 4, 3, 3)}, {"group": 2})

# conv / pool / norm
_conv_attrs = {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
               "groups": 1}
SPECS["conv2d"] = S({"Input": (2, 3, 6, 6), "Filter": (4, 3, 3, 3)},
                    _conv_attrs)
SPECS["depthwise_conv2d"] = S(
    {"Input": (2, 4, 6, 6), "Filter": (4, 1, 3, 3)},
    {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
     "groups": 4})
SPECS["conv2d_transpose"] = S(
    {"Input": (2, 4, 5, 5), "Filter": (4, 3, 3, 3)}, _conv_attrs)
SPECS["depthwise_conv2d_transpose"] = S(
    {"Input": (2, 4, 5, 5), "Filter": (4, 1, 3, 3)},
    {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
     "groups": 4})
SPECS["conv3d"] = S({"Input": (1, 2, 4, 4, 4), "Filter": (3, 2, 3, 3, 3)},
                    {"strides": [1, 1, 1], "paddings": [1, 1, 1],
                     "dilations": [1, 1, 1], "groups": 1})
SPECS["conv3d_transpose"] = S(
    {"Input": (1, 3, 4, 4, 4), "Filter": (3, 2, 3, 3, 3)},
    {"strides": [1, 1, 1], "paddings": [1, 1, 1],
     "dilations": [1, 1, 1], "groups": 1})
SPECS["conv_shift"] = S({"X": (2, 6), "Y": (2, 3)})
SPECS["pool2d"] = S({"X": (2, 3, 6, 6)},
                    {"pooling_type": "avg", "ksize": [2, 2],
                     "strides": [2, 2], "paddings": [0, 0]})
SPECS["pool3d"] = S({"X": (1, 2, 4, 4, 4)},
                    {"pooling_type": "avg", "ksize": [2, 2, 2],
                     "strides": [2, 2, 2], "paddings": [0, 0, 0]})
SPECS["max_pool2d_with_index"] = S(
    {"X": (2, 3, 6, 6)}, {"ksize": [2, 2], "strides": [2, 2],
                          "paddings": [0, 0]})
SPECS["max_pool3d_with_index"] = S(
    {"X": (1, 2, 4, 4, 4)}, {"ksize": [2, 2, 2], "strides": [2, 2, 2],
                             "paddings": [0, 0, 0]})
SPECS["unpool"] = S(
    {"X": (1, 2, 3, 3),
     "Indices": np.arange(18, dtype=np.int32).reshape(1, 2, 3, 3) * 2},
    {"unpooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
     "paddings": [0, 0]}, diff=["X"])
SPECS["maxout"] = S({"X": (2, 6, 3, 3)}, {"groups": 2})
SPECS["spp"] = S({"X": (1, 2, 6, 6)},
                 {"pyramid_height": 2, "pooling_type": "avg"})
SPECS["batch_norm"] = S(
    {"X": (4, 3, 5, 5), "Scale": (3,), "Bias": (3,),
     "Mean": ("pos", (3,)), "Variance": ("pos", (3,))},
    {"epsilon": 1e-5, "momentum": 0.9},
    diff=["X", "Scale", "Bias"], f32=True)
SPECS["layer_norm"] = S(
    {"X": (3, 8), "Scale": (8,), "Bias": (8,)},
    {"begin_norm_axis": 1, "epsilon": 1e-5}, f32=True)
SPECS["group_norm"] = S(
    {"X": (2, 4, 3, 3), "Scale": (4,), "Bias": (4,)},
    {"groups": 2, "epsilon": 1e-5}, f32=True)
SPECS["instance_norm"] = S(
    {"X": (2, 3, 4, 4), "Scale": (3,), "Bias": (3,)},
    {"epsilon": 1e-5}, f32=True)
SPECS["lrn"] = S({"X": (2, 4, 4, 4)}, {"n": 3, "alpha": 1e-4,
                                       "beta": 0.75, "k": 1.0})
SPECS["prelu"] = S({"X": (3, 4), "Alpha": ("pos", (1,))},
                   {"mode": "all"})
SPECS["affine_channel"] = S(
    {"X": (2, 3, 4, 4), "Scale": (3,), "Bias": (3,)})
SPECS["dropout"] = S({"X": (4, 6)},
                     {"dropout_prob": 0.4,
                      "dropout_implementation": "upscale_in_train"})
SPECS["row_conv"] = S({"X": (2, 5, 4), "Filter": (3, 4)})
SPECS["im2sequence"] = S({"X": (1, 2, 5, 5)},
                         {"kernels": [2, 2], "strides": [1, 1],
                          "paddings": [0, 0, 0, 0]})
SPECS["grid_sampler"] = S({"X": (1, 2, 4, 4), "Grid": ("unit",
                                                       (1, 3, 3, 2))})
SPECS["affine_grid"] = S(
    {"Theta": (1, 2, 3)}, {"output_shape": [1, 1, 4, 4]})
SPECS["bilinear_interp"] = S({"X": (1, 2, 4, 4)},
                             {"out_h": 6, "out_w": 6,
                              "align_corners": True})
SPECS["nearest_interp"] = S({"X": (1, 2, 4, 4)},
                            {"out_h": 6, "out_w": 6,
                             "align_corners": True})
SPECS["interpolate"] = S({"X": (1, 2, 4, 4)},
                         {"out_h": 6, "out_w": 6,
                          "interp_method": "bilinear",
                          "align_corners": True})

# softmax / losses
SPECS["softmax"] = S({"X": (3, 5)})
SPECS["log_softmax"] = S({"X": (3, 5)})
SPECS["cross_entropy"] = S(
    {"X": ("prob", (4, 5)), "Label": ("int", (4, 1), 5)})
SPECS["softmax_with_cross_entropy"] = S(
    {"Logits": (4, 5), "Label": ("int", (4, 1), 5)}, f32=True)
SPECS["sigmoid_cross_entropy_with_logits"] = S(
    {"X": (4, 5), "Label": ("zero_one", (4, 5))}, diff=["X"])
SPECS["mse_loss"] = S({"X": (4, 3), "Y": (4, 3)})
SPECS["square_error_cost"] = S({"X": (4, 3), "Y": (4, 3)})
SPECS["log_loss"] = S(
    {"Predicted": ("prob", (4, 2)), "Labels": ("zero_one", (4, 1))},
    {"epsilon": 1e-4}, diff=["Predicted"])
SPECS["huber_loss"] = S({"X": (4, 3), "Y": np.zeros((4, 3))},
                        {"delta": 0.1}, diff=["X"])
SPECS["smooth_l1_loss"] = S({"X": (4, 3), "Y": np.zeros((4, 3))},
                            {"sigma": 1.0}, diff=["X"])
SPECS["kldiv_loss"] = S(
    {"X": ("prob", (4, 5)), "Target": ("prob", (4, 5))},
    {"reduction": "mean"}, diff=["X"])
SPECS["bpr_loss"] = S({"X": ("prob", (4, 5)),
                       "Label": ("int", (4, 1), 5)})
SPECS["dice_loss"] = S(
    {"X": ("prob", (4, 2)), "Label": ("zero_one", (4, 1))}, diff=["X"])
SPECS["hinge_loss"] = S({"Logits": (4, 1),
                         "Labels": ("zero_one", (4, 1))},
                        diff=["Logits"])
SPECS["modified_huber_loss"] = S(
    {"X": (4, 1), "Y": ("zero_one", (4, 1))}, diff=["X"])
SPECS["rank_loss"] = S(
    {"Left": (4, 1), "Right": (4, 1), "Label": ("zero_one", (4, 1))},
    diff=["Left", "Right"])
SPECS["margin_rank_loss"] = S(
    {"X1": (4, 1), "X2": (4, 1),
     "Label": np.full((4, 1), 1.0)}, {"margin": 10.0},
    diff=["X1", "X2"])
SPECS["label_smooth"] = S({"X": ("prob", (4, 5))}, {"epsilon": 0.1})

# embeddings
SPECS["lookup_table"] = S(
    {"W": (6, 4), "Ids": ("int", (3, 1), 6)})
SPECS["lookup_table_v2"] = S({"W": (6, 4), "Ids": ("int", (3,), 6)})
SPECS["embedding"] = S({"W": (6, 4), "Ids": ("int", (3, 1), 6)})
SPECS["fused_embedding_seq_pool"] = S(
    {"W": (6, 4), "Ids": ("int", (3, 2), 6), "Weight": (3, 2)},
    {"pooltype": "sum", "padding_idx": -1}, f32=True)

# attention
SPECS["scaled_dot_product_attention"] = S(
    {"Q": (2, 3, 4), "K": (2, 3, 4), "V": (2, 3, 4)}, {"causal": False},
    f32=True)
SPECS["flash_attention"] = S(
    {"Q": (1, 4, 2, 4), "K": (1, 4, 2, 4), "V": (1, 4, 2, 4)},
    {"causal": False, "scale": 0.5, "layout": "bthd"}, f32=True)
SPECS["add_position_encoding"] = S({"X": (2, 5, 4)},
                                   {"alpha": 1.0, "beta": 1.0})

# decoder-only language-model blocks (float32 inside, as the norms)
SPECS["rms_norm"] = S({"X": (2, 3, 8), "Scale": (8,)}, {"epsilon": 1e-5},
                      f32=True)
SPECS["rotary_embedding"] = S({"X": (1, 5, 2, 8)}, {"theta": 100.0},
                              f32=True)
SPECS["short_conv"] = S({"X": (2, 6, 4), "Filter": (4, 3)}, f32=True)
SPECS["swiglu"] = S({"X": (3, 4), "Y": (3, 4)}, f32=True)
# sparse experts: the selection is piecewise constant (the generator's
# scores stand apart), the routing weights and the experts are smooth
SPECS["moe_route"] = S(
    {"X": (5, 6), "Weight": (6, 4), "Bias": (4,)},
    {"k": 2, "norm_topk_prob": True, "routed_scaling_factor": 1.0},
    diff=["X", "Weight"], f32=True)
SPECS["moe_expert_ffn"] = S(
    {"X": (6, 4), "TopkIdx": ("int", (6, 2), 4), "TopkW": ("pos", (6, 2)),
     "W1": (2, 4, 5), "W3": (2, 4, 5), "W2": (2, 5, 4)},
    {"first_expert": 1}, f32=True)

# the linear-attention layer: a log-decay below zero, a step in (0, 2);
# 70 tokens are one chunk and a part of the next
SPECS["l2_norm"] = S({"X": (2, 3, 8)}, {"epsilon": 1e-6}, f32=True)
SPECS["kda_gate"] = S({"X": (1, 4, 2, 3), "ALog": (2,), "DtBias": (2, 3)},
                      f32=True)
SPECS["kda_attention"] = S(
    {"Q": (1, 70, 2, 4), "K": (1, 70, 2, 4), "V": (1, 70, 2, 4),
     "G": -np.random.RandomState(34).uniform(0.05, 2.0, (1, 70, 2, 4)),
     "Beta": ("pos", (1, 70, 2))}, {"scale": 0.5}, f32=True)
# the diagonal selective scan: a step dt in (0.3, 1.5) over 20 tokens
# (a decay of exp(-dt exp(A_log)) a token); B, C, D, A_log signed
SPECS["selective_scan"] = S(
    {"X": (1, 20, 3), "Dt": ("pos", (1, 20, 3)), "ALog": (3, 4),
     "B": (1, 20, 4), "C": (1, 20, 4), "D": (3,)}, f32=True)

# recurrent (weights + input grads through lax.scan)
SPECS["lstm"] = S(
    {"Input": (2, 5, 4), "WeightIH": (4, 12), "WeightHH": (3, 12)},
    {"use_peepholes": False}, f32=True)
SPECS["gru"] = S(
    {"Input": (2, 5, 4), "WeightIH": (4, 9), "WeightHH": (3, 9)},
    f32=True)
SPECS["lstm_unit"] = S({"X": (3, 16), "C_prev": (3, 4)})
SPECS["gru_unit"] = S(
    {"Input": (3, 12), "HiddenPrev": (3, 4), "Weight": (4, 12),
     "Bias": (1, 12)})

# sequence ops (padded + length-mask representation)
_LEN = np.array([5, 3], np.int32)
SPECS["sequence_softmax"] = S({"X": (2, 5), "SeqLen": _LEN},
                              diff=["X"])
SPECS["sequence_pool"] = S({"X": (2, 5, 4), "SeqLen": _LEN},
                           {"pooltype": "AVERAGE"}, diff=["X"])
SPECS["sequence_reverse"] = S({"X": (2, 5, 4), "SeqLen": _LEN},
                              diff=["X"])
SPECS["sequence_conv"] = S(
    {"X": (2, 5, 4), "Filter": (3 * 4, 6), "SeqLen": _LEN},
    {"context_length": 3, "context_start": -1}, diff=["X", "Filter"])
SPECS["sequence_concat"] = S({"X": [(2, 5, 4), (2, 5, 4)],
                              "SeqLen": [_LEN, _LEN]}, diff=["X"])
SPECS["sequence_expand"] = S(
    {"X": (2, 1, 4), "Y": (2, 5, 4), "SeqLen": _LEN}, diff=["X"])
SPECS["sequence_expand_as"] = S(
    {"X": (2, 1, 4), "Y": (2, 5, 4)}, diff=["X"])
SPECS["sequence_pad"] = S(
    {"X": (2, 5, 4), "PadValue": np.zeros(()), "SeqLen": _LEN},
    {"padded_length": 6}, diff=["X"])
SPECS["sequence_unpad"] = S({"X": (2, 5, 4), "Length": _LEN},
                            diff=["X"])
SPECS["sequence_reshape"] = S({"X": (2, 6, 4)}, {"new_dim": 8},
                              diff=["X"])
SPECS["sequence_slice"] = S(
    {"X": (2, 5, 4), "Offset": np.array([[1], [0]], np.int32),
     "Length": np.array([[2], [3]], np.int32)}, diff=["X"])
SPECS["sequence_scatter"] = S(
    {"X": (2, 6), "Ids": ("int", (2, 3), 6), "Updates": (2, 3),
     "SeqLen": np.array([3, 3], np.int32)}, diff=["X", "Updates"])

# structured prediction
SPECS["linear_chain_crf"] = S(
    {"Emission": (2, 4, 3), "Transition": (5, 3),
     "Label": ("int", (2, 4), 3),
     "SeqLen": np.array([4, 2], np.int32)},
    diff=["Emission", "Transition"], eps=1e-4, rtol=5e-3)
SPECS["warpctc"] = S(
    {"Logits": (2, 4, 6), "Label": np.array([[1, 2], [3, 4]], np.int32),
     "LogitsLength": np.array([4, 3], np.int32),
     "LabelLength": np.array([2, 1], np.int32)},
    {"blank": 0}, diff=["Logits"], f32=True)

# misc float ops
SPECS["hsigmoid"] = S(
    {"X": (3, 4), "W": (5, 4), "Bias": (5, 1),
     "Label": ("int", (3, 1), 6)},
    {"num_classes": 6}, diff=["X", "W", "Bias"])
SPECS["hierarchical_sigmoid"] = S(
    {"X": (3, 4), "W": (5, 4), "Bias": (5, 1),
     "Label": ("int", (3, 1), 6)},
    {"num_classes": 6}, diff=["X", "W", "Bias"])
SPECS["nce"] = S(
    {"Input": (3, 4), "Weight": (6, 4), "Bias": (6,),
     "Label": ("int", (3, 1), 6),
     "SampleWeight": np.ones((3,))},
    {"num_total_classes": 6, "num_neg_samples": 2},
    diff=["Input", "Weight", "Bias"], f32=True)
SPECS["sampled_softmax_ce"] = S(
    {"X": (3, 4), "W": (6, 4), "B": (6,),
     "Label": ("int", (3, 1), 6)},
    {"num_samples": 3, "num_classes": 6}, diff=["X", "W", "B"],
    f32=True)
SPECS["roi_align"] = S(
    {"X": (1, 2, 6, 6),
     "ROIs": np.array([[0.5, 0.5, 4.0, 4.0]], np.float64)},
    {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0,
     "sampling_ratio": 2}, diff=["X"])

# ---------------------------------------------------------------------------
# DIFF_ONLY tier: ops whose output involves discrete selection/matching
# (finite differences would straddle the decision boundaries, so an FD
# comparison is meaningless) but which sit on TRAINING paths — the
# detection losses chiefly. For these the sweep checks exactly the
# property the executor needs: jax.value_and_grad runs through the
# kernel (with every output live — the max_pool_with_index crash
# class) and yields finite gradients.
# ---------------------------------------------------------------------------

_PRIORS = np.array([[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9],
                    [0.2, 0.6, 0.45, 0.95], [0.55, 0.1, 0.95, 0.45]],
                   np.float64)

DIFF_ONLY = {
    "ssd_loss": S(
        {"Loc": (2, 4, 4), "Conf": (2, 4, 3),
         "GtBox": np.array([[[0.12, 0.1, 0.42, 0.38],
                             [0.5, 0.52, 0.88, 0.9]],
                            [[0.2, 0.62, 0.44, 0.93],
                             [0.0, 0.0, 0.0, 0.0]]], np.float64),
         "GtLabel": np.array([[1, 2], [1, -1]], np.int32),
         "PriorBox": _PRIORS, "PriorVar": np.full((4, 4), 0.1)},
        {"overlap_threshold": 0.5}, diff=["Loc", "Conf"]),
    "yolov3_loss": S(
        {"X": (1, 2 * 7, 4, 4),
         "GTBox": np.array([[[0.3, 0.3, 0.2, 0.25],
                             [0.7, 0.6, 0.3, 0.2]]], np.float64),
         "GTLabel": np.array([[0, 1]], np.int32)},
        {"anchors": [10, 13, 16, 30], "class_num": 2,
         "ignore_thresh": 0.7}, diff=["X"]),
    "roi_pool": S(
        {"X": (1, 2, 6, 6),
         "ROIs": np.array([[0.5, 0.5, 4.0, 4.0]], np.float64)},
        {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0},
        diff=["X"]),
    "psroi_pool": S(
        {"X": (1, 8, 6, 6),
         "ROIs": np.array([[0.5, 0.5, 4.0, 4.0]], np.float64)},
        {"pooled_height": 2, "pooled_width": 2, "output_channels": 2,
         "spatial_scale": 1.0}, diff=["X"]),
    "iou_similarity": S({"X": _PRIORS[:2], "Y": _PRIORS},
                        diff=["X", "Y"]),
    "box_coder": S(
        {"PriorBox": _PRIORS, "PriorBoxVar": np.full((4, 4), 0.1),
         "TargetBox": ("pos", (4, 4))},
        {"code_type": "encode_center_size"}, diff=["TargetBox"]),
}


def _run_diff_only_check(op, spec):
    """value_and_grad through the kernel with all outputs live; finite
    grads required, no FD comparison (discrete selection inside)."""
    rng = np.random.RandomState(
        _RNG_SEED + zlib.crc32(op.encode()) % 1000)
    with jax.enable_x64():
        ins = {slot: [jnp.asarray(_make_value(v, rng))
                      for v in (vs if isinstance(vs, list) else [vs])]
               for slot, vs in spec["ins"].items()}
        ctx = KernelCtx(key=jax.random.PRNGKey(7), is_test=False)
        fn = get_kernel(op)
        diff_slots = spec["diff"]
        flat = [(slot, i) for slot in diff_slots
                for i in range(len(ins[slot]))]

        def scalar_fn(*args):
            ins2 = {k: list(v) for k, v in ins.items()}
            for (slot, i), a in zip(flat, args):
                ins2[slot][i] = a
            outs = fn(ctx, ins2, spec["attrs"])
            total = 0.0
            for oslot in sorted(outs):
                for o in outs[oslot]:
                    if o is None or not getattr(o, "size", 0):
                        continue
                    if _is_float(o):
                        total = total + jnp.sum(o)
                    else:
                        total = total + 0.0 * jnp.sum(
                            jnp.asarray(o).astype(jnp.float32))
            return total

        args0 = [ins[slot][i] for slot, i in flat]
        val, grads = jax.value_and_grad(
            scalar_fn, argnums=tuple(range(len(args0))))(*args0)
        assert np.isfinite(float(val)), f"{op}: non-finite output"
        for (slot, i), g in zip(flat, grads):
            assert np.all(np.isfinite(np.asarray(g))),                 f"{op}: non-finite grad for {slot}[{i}]"


# ---------------------------------------------------------------------------
# exclusions — closed list, every entry carries its reason
# ---------------------------------------------------------------------------

EXCLUDE = {
    # derivative zero almost everywhere (integer-valued outputs)
    "floor": "derivative 0 a.e.", "ceil": "derivative 0 a.e.",
    "round": "derivative 0 a.e.", "sign": "derivative 0 a.e.",
    "elementwise_floordiv": "derivative 0 a.e.",
    # integer / bool / comparison domain
    "arg_max": "integer output", "arg_min": "integer output",
    "argsort": "integer permutation output",
    "equal": "bool output", "not_equal": "bool output",
    "greater_than": "bool output", "greater_equal": "bool output",
    "less_than": "bool output", "less_equal": "bool output",
    "logical_and": "bool domain", "logical_or": "bool domain",
    "logical_not": "bool domain", "logical_xor": "bool domain",
    "is_empty": "bool output", "isfinite": "bool output",
    "reduce_all": "bool domain", "reduce_any": "bool domain",
    "has_inf": "bool output", "has_nan": "bool output",
    "one_hot": "integer input, constant output",
    "shape": "integer output", "where_index": "integer output",
    "top_k": "discrete selection (value path == reduce_max, checked)",
    "top_k_v2": "discrete selection (value path == reduce_max, checked)",
    "sequence_mask": "integer input, 0/1 output",
    "sequence_enumerate": "integer op",
    "sequence_erase": "integer op",
    "edit_distance": "integer string metric",
    "ctc_align": "integer decode", "ctc_greedy_decoder": "argmax decode",
    "crf_decoding": "argmax decode (grad path covered by "
                    "linear_chain_crf)",
    "beam_search": "discrete search", "beam_search_decode":
        "discrete search", "beam_search_loop": "discrete search",
    "hash": "integer hashing",
    # metrics (integer counts / streaming state)
    "accuracy": "metric, integer counts", "auc": "streaming metric",
    "chunk_eval": "metric", "precision_recall": "metric",
    "positive_negative_pair": "metric", "detection_map": "metric",
    "mean_iou": "metric, integer intersection counts",
    # random generators (no input to differentiate)
    "gaussian_random": "RNG source",
    "gaussian_random_batch_size_like": "RNG source",
    "uniform_random": "RNG source",
    "uniform_random_batch_size_like": "RNG source",
    "truncated_gaussian_random": "RNG source",
    "randint": "RNG source", "sampling_id": "RNG sample",
    "random_crop": "RNG crop (selection, not transform)",
    # constant fills / assigns (no differentiable input)
    "fill": "constant source", "fill_constant": "constant source",
    "fill_any_like": "constant output irrespective of input values",
    "fill_zeros_like": "constant output",
    "fill_constant_batch_size_like": "constant source",
    "assign": "identity plumbing", "assign_value": "constant source",
    "linspace": "constant source", "range": "constant source",
    "increment": "counter plumbing",
    "cast": "dtype conversion (identity on float→float)",
    # optimizer update rules (in-place param update semantics; their
    # numerics are pinned op-by-op in test_optimizers*.py)
    "sgd": "optimizer update", "momentum": "optimizer update",
    "adam": "optimizer update", "adamax": "optimizer update",
    "adadelta": "optimizer update", "adagrad": "optimizer update",
    "decayed_adagrad": "optimizer update", "ftrl": "optimizer update",
    "lamb": "optimizer update", "lars_momentum": "optimizer update",
    "rmsprop": "optimizer update",
    "proximal_adagrad": "optimizer update",
    "proximal_gd": "optimizer update",
    "sparse_adam": "optimizer update (row-sparse)",
    "sparse_sgd": "optimizer update (row-sparse)",
    "average_accumulates": "optimizer state accumulation",
    "global_norm_clip": "multi-tensor optimizer infra",
    # quantization (round inside → derivative 0 a.e.)
    "quantize": "quantization rounding", "dequantize": "scale by "
        "constant derived from int tensor",
    "fake_quantize_abs_max": "quantization rounding",
    "fake_quantize_range_abs_max": "quantization rounding",
    "fake_dequantize_max_abs": "paired with fake_quantize",
    "dequantize_abs_max": "paired with quantize",
    # detection: discrete matching / box assignment / NMS
    "anchor_generator": "constant box grid",
    "prior_box": "constant box grid",
    "density_prior_box": "constant box grid",
    "bipartite_match": "discrete matching",
    "multiclass_nms": "discrete suppression",
    "mine_hard_examples": "discrete mining",
    "generate_proposals": "discrete proposal selection",
    "generate_proposal_labels": "discrete label assignment",
    "rpn_target_assign": "discrete assignment",
    "target_assign": "discrete assignment",
    "polygon_box_transform": "geometry decode, not a training path",
    "roi_perspective_transform": "discrete geometric resampling",
    # IR / runtime plumbing
    "alloc_array": "TensorArray allocation",
    "array_read": "TensorArray plumbing",
    "array_write": "TensorArray plumbing",
    "tensor_array_to_tensor": "TensorArray plumbing",
    "lod_reset": "LoD metadata only", "print": "side-effect op",
    "py_func": "arbitrary python callback",
    "load_from_file": "IO op",
    "lookup_sparse_table": "distributed sparse-table fetch",
    "mask_merge": "internal mask plumbing",
    "reorder_by_rank": "rank-table permutation",
    "similarity_focus": "discrete channel selection",
    "attention_lstm": "composite exercised via test_models stacked "
        "LSTM (per-gate paths covered by lstm/lstm_unit)",
    "lstmp": "projection LSTM exercised via lstm spec family in "
        "test_ops_torch",
}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def test_registry_fully_classified():
    """Every registered kernel is grad-checked, diff-only-checked, or
    excluded with a reason — and the three lists are disjoint with no
    stale entries."""
    reg = set(KERNELS)
    spec, donly, excl = set(SPECS), set(DIFF_ONLY), set(EXCLUDE)
    for a, b in [(spec, donly), (spec, excl), (donly, excl)]:
        assert not (a & b), f"double-classified: {sorted(a & b)}"
    for name, grp in [("specs", spec), ("diff-only", donly),
                      ("exclusions", excl)]:
        assert not (grp - reg), f"stale {name}: {sorted(grp - reg)}"
    missing = reg - spec - donly - excl
    assert not missing, (
        f"{len(missing)} kernels are neither grad-checked, "
        f"diff-only-checked, nor excluded-with-reason: "
        f"{sorted(missing)}")


@pytest.mark.parametrize("op", sorted(SPECS))
def test_op_grad(op):
    _run_grad_check(op, SPECS[op])


@pytest.mark.parametrize("op", sorted(DIFF_ONLY))
def test_op_differentiable(op):
    _run_diff_only_check(op, DIFF_ONLY[op])


def test_train_through_max_pool_with_index():
    """End-to-end regression for the class of bug the sweep's
    non-float-output tracing hunts: max_pool2d_with_index was built on
    a pair-carrying reduce_window with no linearization rule, so any
    program TRAINING through it failed to differentiate even though
    the mask is unused by the loss (the executor traces every op
    output). Must train, not just run."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.layers.nn import LayerHelper
    img = layers.data("img", shape=[1, 8, 8])
    c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                      act="relu")
    h = LayerHelper("mpwi")
    out = h.create_variable_for_type_inference(c.dtype,
                                               (c.shape[0], 4, 4, 4))
    mask = h.create_variable_for_type_inference(
        "int32", (c.shape[0], 4, 4, 4), True)
    h.append_op("max_pool2d_with_index", {"X": [c]},
                {"Out": [out], "Mask": [mask]},
                {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]})
    label = layers.data("label", shape=[1], dtype="int64")
    pred = layers.fc(out, 10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.Adam(1e-2).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    rng = np.random.RandomState(0)
    x = rng.randn(16, 1, 8, 8).astype("float32")
    y = rng.randint(0, 10, (16, 1))
    losses = [float(np.asarray(exe.run(
        feed={"img": x, "label": y}, fetch_list=[loss])[0]))
        for _ in range(8)]
    assert losses[-1] < losses[0], losses
