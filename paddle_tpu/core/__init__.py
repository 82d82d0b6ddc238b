from . import framework
from . import place
from . import scope
from . import executor
from . import backward


class EOFException(Exception):
    """Raised by Executor.run when a py_reader/file reader is exhausted
    (parity: paddle.fluid.core.EOFException from the C++ reader queue)."""


def is_compiled_with_tpu():
    """True when this process's JAX backend holds a TPU. Asks the
    backend (initializing it if nothing has yet), so the answer is
    about the machine, not about a configuration string."""
    import jax
    return jax.default_backend() == "tpu"


# CUDA-availability compat (ref core.is_compiled_with_cuda): reference
# programs branch on this to pick CUDAPlace, and CUDAPlace aliases
# TPUPlace here (MIGRATING.md), so it has to give the same answer.
is_compiled_with_cuda = is_compiled_with_tpu
