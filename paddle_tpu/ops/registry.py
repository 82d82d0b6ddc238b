"""Op kernel registry.

Parity: paddle/fluid/framework/op_registry.h — the reference registers
per-device C++ kernels under op type strings. Here each op type maps to
ONE pure JAX function; device specialization is XLA's job at compile time,
not the registry's. Programs stay serializable because Operators carry
only the type string.

Kernel signature:
    fn(ctx, ins: dict[slot -> list[Array]], attrs: dict) -> dict[slot -> list[Array]]

`ctx` (ops.registry.KernelCtx) provides:
    .key      per-op PRNG key (deterministic: fold_in(program seed, op index))
    .is_test  executor mode (inference disables dropout etc.)
    .place    the target Place
    .accel    the Pallas dispatch seam (see accel() below)

How an op reaches a Pallas kernel, all of it: the op kernel asks
ctx.accel(op_type); accel() looks the op type up in the kern table and
hands back a callable that counts the call and runs the kernel's try_*
entry; try_* asks active() (may this trace lower a kernel at all) and
then its own shape / length policy, and returns the kernel's result or
None, on which the op lowers its jnp composition.
"""
import contextlib
import threading

__all__ = ["kernel", "get_kernel", "has_kernel", "closest_kernels",
           "KernelCtx", "KERNELS", "autocast", "accel", "lowering_for",
           "mosaic_target", "set_mode", "active"]

KERNELS = {}

_lowering = threading.local()


@contextlib.contextmanager
def lowering_for(platform, partitioned=False):
    """Scope a trace to the platform it is compiled for. The tracers
    (core/trace.py, ParallelExecutor, IncrementalDecoder) enter it with
    the platform of their Place / mesh / device, so Pallas dispatch
    follows the program's target and not the process default:
    Executor(CPUPlace()) on a TPU host lowers no Mosaic kernel.
    `partitioned=True` marks a jit that GSPMD partitions over several
    devices — Mosaic custom calls cannot be auto-partitioned (JAX
    refuses to lower them outside a fully-manual shard_map), so kernels
    stay off there. platform=None leaves the scope unchanged."""
    if platform is None:
        yield
        return
    prev = getattr(_lowering, "target", None)
    _lowering.target = (platform, partitioned)
    try:
        yield
    finally:
        _lowering.target = prev


def mosaic_target():
    """True when the trace in progress may lower a Mosaic (compiled
    Pallas TPU) kernel: its target — the enclosing lowering_for scope,
    else the process's default backend — is a TPU, unpartitioned."""
    target = getattr(_lowering, "target", None)
    if target is None:
        import jax
        return jax.default_backend() == "tpu"
    platform, partitioned = target
    return platform == "tpu" and not partitioned


# "auto": kernels iff the trace's target is a TPU (mosaic_target);
# "interpret": every kernel through the Pallas interpreter (CPU tests);
# "off": no kernel, every op lowers its jnp composition.
_MODE = "auto"


def set_mode(mode):
    global _MODE
    assert mode in ("auto", "interpret", "off")
    _MODE = mode


def active():
    """(use_pallas, interpret) for the trace in progress — THE gate
    every kernel's try_* entry asks first: compiled kernels only where
    the program is being lowered for a TPU (mosaic_target), the
    interpreter when forced, nothing when off."""
    if _MODE == "off":
        return False, False
    if _MODE == "interpret":
        return True, True
    return mosaic_target(), False


def accel(op_type):
    """The ONE Pallas dispatch seam: a callable running the registered
    kernel for `op_type` (returns the kernel result, or None when its
    own gate rejects — the try_* convention), or None when the kern
    table holds nothing for this op. Op kernels reach this through
    ctx.accel; trace-time lowering consults the table here instead of
    per-call-site pallas imports."""
    from . import kern
    return kern.adapter(op_type)


def autocast(*arrays):
    """AMP dtype alignment for MXU ops: if float operand dtypes are mixed
    and any is bfloat16, compute in bfloat16 (amp.cast_program_to_bf16
    keeps feeds/norm-params fp32, so conv(img_fp32, w_bf16) is the normal
    autocast boundary — the reference float16 transpiler inserted explicit
    cast ops here)."""
    import numpy as np
    import jax.numpy as jnp
    floats = [a for a in arrays if jnp.issubdtype(a.dtype, jnp.floating)]
    dts = {np.dtype(a.dtype) for a in floats}
    if len(dts) > 1 and np.dtype(jnp.bfloat16) in dts:
        return tuple(a.astype(jnp.bfloat16)
                     if jnp.issubdtype(a.dtype, jnp.floating) else a
                     for a in arrays)
    return arrays


class KernelCtx:
    def __init__(self, key=None, is_test=False, place=None, accel=accel):
        self.key = key
        self.is_test = is_test
        self.place = place
        self.accel = accel


def kernel(*types):
    """Decorator registering fn under one or more op type names."""
    def deco(fn):
        for t in types:
            if t in KERNELS:
                raise ValueError(f"duplicate kernel registration: {t}")
            KERNELS[t] = fn
        return fn
    return deco


def closest_kernels(type, n=3, cutoff=0.6):
    """Closest registered op type names to `type` (difflib ratio) —
    shared by get_kernel's error message and the analysis unknown-op
    pass."""
    import difflib
    return difflib.get_close_matches(type, list(KERNELS), n=n,
                                     cutoff=cutoff)


def get_kernel(type):
    fn = KERNELS.get(type)
    if fn is None:
        suggestions = closest_kernels(type)
        hint = (f"; did you mean {', '.join(map(repr, suggestions))}?"
                if suggestions else "")
        raise NotImplementedError(
            f"no kernel registered for op type {type!r} "
            f"(registered: {len(KERNELS)} ops){hint}")
    return fn


def has_kernel(type):
    return type in KERNELS
