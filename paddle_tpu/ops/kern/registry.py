"""Kernel registry: capability-probed Pallas dispatch in one place.

Parity note: the reference framework registers ~429 hand-written CUDA
kernels through OpKernelType/REGISTER_OP_CUDA_KERNEL — a (place, dtype,
layout) key picked at run time per op. Here the registry holds a
KernelSpec per Pallas kernel: a STATIC capability probe (shapes/dtypes
the kernel accepts — the PR-9 embedding-template gate), the jnp
reference composition it must match, and a numerics tolerance for the
parity gate. Block sizes are each kernel's own defaults, swept on the
chip through the kernel's own function. Dispatch is trace-time: the
op kernel asks through ops.registry.accel(), gets the kernel result
or None, and lowers its own jnp fallback on None —
exactly the try_* convention the three original pallas modules used,
now behind one seam instead of three ad-hoc import sites.

STATS is trace-time evidence (the house pattern of
ops/pallas/flash_attention.STATS): tests assert the registry path ran,
not that it silently fell back.
"""
import functools

__all__ = ["KernelSpec", "register", "get", "names", "specs", "adapter",
           "dispatch", "parity_check", "compare_leaves", "STATS",
           "KERN_SPECS", "ADAPTERS"]

KERN_SPECS = {}   # kernel name -> KernelSpec
ADAPTERS = {}     # adapter key (op type or library-call name) -> kernel name

STATS = {"dispatches": 0, "accepted": 0, "rejected": 0, "by_kernel": {}}


class KernelSpec:
    """One registered Pallas kernel.

    name        registry key ("flash_attention", "decode_attend", ...)
    fn          THE dispatch entry (try_* convention): self-gates on
                active() + its own probe, returns the kernel result or
                None -> caller lowers the jnp fallback.
    reference   jnp reference composition with the same user-level
                signature as fn — the numerics ground truth.
    probe       fn(*args, interpret=False, **kw) -> bool. STATIC
                shape/dtype acceptance only (no backend check — fn owns
                the active() gate). Works on jax.ShapeDtypeStruct too,
                so meshlint and the CLI can probe without data.
    tol         (rtol, atol) for the parity gate vs reference.
    op_types    dispatch-seam keys this kernel serves: op type strings
                ("layer_norm") and/or library-call names
                ("dequant_attend_int8"). Defaults to (name,).
    example     fn(rng: np.random.RandomState) -> (args, kwargs) —
                small interpret-runnable inputs for the CLI/selftest
                parity gate.
    note        one-line human description for `tpukern list`.
    """

    def __init__(self, name, fn, reference, probe, tol=(2e-5, 2e-5),
                 op_types=None, example=None, note=""):
        self.name = name
        self.fn = fn
        self.reference = reference
        self.probe = probe
        self.tol = tuple(tol)
        self.op_types = tuple(op_types or (name,))
        self.example = example
        self.note = note


def register(spec):
    if spec.name in KERN_SPECS:
        raise ValueError(f"duplicate kern registration: {spec.name!r}")
    KERN_SPECS[spec.name] = spec
    for t in spec.op_types:
        if t in ADAPTERS:
            raise ValueError(
                f"adapter key {t!r} already serves {ADAPTERS[t]!r}")
        ADAPTERS[t] = spec.name
    return spec


def get(name):
    spec = KERN_SPECS.get(name)
    if spec is None:
        raise KeyError(f"no kern kernel {name!r} "
                       f"(registered: {sorted(KERN_SPECS)})")
    return spec


def names():
    return sorted(KERN_SPECS)


def specs():
    return [KERN_SPECS[n] for n in names()]


def dispatch(name, *args, **kwargs):
    """Run kernel `name` and count the call: the result, or None when
    fn's own gate rejects (backend, mode, shapes)."""
    out = get(name).fn(*args, **kwargs)
    STATS["dispatches"] += 1
    per = STATS["by_kernel"].setdefault(name, {"accepted": 0,
                                               "rejected": 0})
    if out is None:
        STATS["rejected"] += 1
        per["rejected"] += 1
    else:
        STATS["accepted"] += 1
        per["accepted"] += 1
    return out


def adapter(key):
    """The callable ops.registry.accel() hands to op kernels for one
    adapter key, or None when nothing is registered for it."""
    name = ADAPTERS.get(key)
    if name is None:
        return None
    return functools.partial(dispatch, name)


def _leaves(tree):
    if tree is None:
        return []
    if isinstance(tree, (tuple, list)):
        out = []
        for t in tree:
            out.extend(_leaves(t))
        return out
    return [tree]


def compare_leaves(got, ref, tol, scale_atol=False):
    """Compare two output trees leaf by leaf within `tol` = (rtol,
    atol): integers exactly, floats by |g - r| <= atol + rtol * |r|.
    `tol` is an fp32 tolerance — a leaf stored in fewer bits cannot
    agree more closely than its own epsilon, so both terms are floored
    there. `scale_atol` scales atol by the leaf's largest magnitude
    (for long reductions, whose error follows the size of the terms
    and not of a possibly cancelling result). Returns (ok, detail)."""
    import numpy as np
    import jax.numpy as jnp
    got_l, ref_l = _leaves(got), _leaves(ref)
    if len(got_l) != len(ref_l):
        return False, (f"output arity {len(got_l)} != reference "
                       f"{len(ref_l)}")
    worst = 0.0
    for i, (g, r) in enumerate(zip(got_l, ref_l)):
        rtol, atol = tol
        if jnp.issubdtype(g.dtype, jnp.floating) \
                and jnp.finfo(g.dtype).bits < 32:
            eps = float(jnp.finfo(g.dtype).eps)
            rtol, atol = max(rtol, eps), max(atol, eps)
        g, r = np.asarray(g), np.asarray(r)
        if g.shape != r.shape:
            return False, f"leaf {i}: shape {g.shape} != {r.shape}"
        if g.dtype.kind in "iu":
            if not np.array_equal(g, r):
                return False, f"leaf {i}: integer mismatch"
            continue
        g64, r64 = g.astype(np.float64), r.astype(np.float64)
        if scale_atol:
            atol *= max(1.0, float(np.abs(r64).max(initial=0.0)))
        diff = np.abs(g64 - r64)
        worst = max(worst, float(diff.max(initial=0.0)))
        over = float((diff - (atol + rtol * np.abs(r64))).max(
            initial=0.0))
        if not over <= 0:       # NaN fails too
            return False, (f"leaf {i}: tolerance exceeded by "
                           f"{over:.3e} (rtol={rtol}, atol={atol})")
    return True, f"max |diff| {worst:.3e} over {len(got_l)} outputs"


def parity_check(name, args, kwargs=None):
    """The numerics gate every registered kernel carries: run fn as
    deployed vs the reference at "highest" matmul precision on the
    same inputs, compare within spec.tol (compare_leaves). Returns
    (ok, detail) — ok is None when the kernel's own gate rejected the
    inputs (nothing ran, nothing to compare)."""
    import jax
    spec = get(name)
    kwargs = dict(kwargs or {})
    out = spec.fn(*args, **kwargs)
    if out is None:
        return None, "probe rejected (jnp fallback path)"
    # the ground truth multiplies at full precision: on a TPU the
    # default for an fp32 jnp matmul is a single bf16 pass
    with jax.default_matmul_precision("highest"):
        ref = spec.reference(*args, **kwargs)
    return compare_leaves(out, ref, spec.tol)
