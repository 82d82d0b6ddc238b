"""tpukern — the Pallas kernel registry subsystem.

Owns Pallas dispatch end-to-end (ROADMAP item 3, the TPP thesis: a
small set of tuned, registered primitives beats ad-hoc lowering):

- registry.py       KernelSpec records (capability probe, jnp reference
                    composition, numerics tolerance) and the counting
                    dispatch that op kernels reach through the ONE seam
                    in ops/registry.py (`accel`), beside the gate
                    (`active`) every kernel's try_* asks.
- quant.py          the shared int8 blockwise quantize/dequantize
                    primitive (gradsync buckets, the KV cache, and the
                    collective wire all route here).
- decode_attention.py  single-token flash attention over the decode
                    slot pool's [slots, T_max] ragged cache layout,
                    plus the fused int8 dequantize-attend variant.
- registrations.py  every kernel declared to the registry.

Import discipline: this package body is LAZY (PEP 562). Importing
`ops.kern` (or the pure-jnp `ops.kern.quant`, which every int8 producer
shares) loads no Pallas code; the registry and its kernel modules load
when ops.registry.accel() first resolves an adapter.
"""
import importlib

__all__ = ["KernelSpec", "register", "get", "names", "specs", "adapter",
           "dispatch", "parity_check", "STATS", "registry"]

# attributes of kern.registry re-exported at package level
_API = ("KernelSpec", "register", "get", "names", "specs", "adapter",
        "dispatch", "parity_check", "STATS", "KERN_SPECS", "ADAPTERS")

_LAZY = ("quant", "decode_attention")


def __getattr__(name):
    if name in _API or name in ("registry", "registrations"):
        registry = importlib.import_module(".registry", __name__)
        registrations = importlib.import_module(".registrations",
                                                __name__)
        if name == "registry":
            return registry
        if name == "registrations":
            return registrations
        return getattr(registry, name)
    if name in _LAZY:
        return importlib.import_module("." + name, __name__)
    raise AttributeError(f"module {__name__!r} has no attribute "
                         f"{name!r}")
