"""Op kernel library — importing this module registers all kernels."""
from . import registry
from . import kernels_tensor
from . import kernels_math
from . import kernels_nn
from . import kernels_optim
from . import kernels_detection
from . import kernels_sequence
from . import kernels_struct
from . import kernels_vision
from . import kernels_control
from . import kernels_extra
from . import kernels_moe
from . import kernels_scan
from .registry import KERNELS, get_kernel, has_kernel
