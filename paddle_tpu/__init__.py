"""paddle_tpu — a TPU-native deep-learning framework with the
capabilities of PaddlePaddle Fluid (reference: /root/reference).

Front-end API mirrors `paddle.fluid` (Program/Executor/layers/optimizer);
execution is whole-program XLA compilation via JAX (see SURVEY.md §1 for
the design map). Usage:

    import paddle_tpu as fluid
    img = fluid.layers.data('img', shape=[784])
    ...
    exe = fluid.Executor(fluid.TPUPlace(0))
"""
import os as _os

import jax as _jax

# TPU-native PRNG: XLA's RngBitGenerator ("rbg") instead of JAX's default
# threefry. threefry lowers to a long scalar-ish VPU program that costs
# ~40% of a dropout-heavy train step on TPU; rbg is a hardware RNG
# instruction AND is partitionable — under pjit/shard_map each shard
# generates its bits locally with no cross-device dependency (the same
# reason the scaling playbook recommends it). Counter-based determinism
# per (seed, step) is preserved; bit-exact streams just aren't portable
# across backends, matching the reference's per-device cuRAND behavior.
_jax.config.update("jax_default_prng_impl", "rbg")

# Persistent XLA compilation cache, placed from outside: JAX itself
# reads JAX_COMPILATION_CACHE_DIR, so when it is set nothing is set
# here; otherwise the cache lives at a fixed path inside the checkout
# (the path is part of the cache key — a directory that moves never
# hits). This is the only place the package names a cache directory.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_compile_cache"))

from . import telemetry         # runtime metrics/spans (dep-free; first)
telemetry.compiles.install()    # the compile log's listener: jax is here
from . import ops               # registers all kernels
from . import unique_name
from .core.framework import (
    Program, Block, Operator, Variable, Parameter,
    default_main_program, default_startup_program, program_guard,
    name_scope,
)
from .core.place import CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace
from .core.scope import Scope, global_scope, scope_guard
from .core.executor import Executor
from .core.backward import append_backward, gradients
from . import layers
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import nets
from . import metrics
from .param_attr import ParamAttr, WeightNormParamAttr
from . import io
from .io import (save_params, save_persistables, load_params,
                 load_persistables, save_inference_model,
                 load_inference_model, save_checkpoint, load_checkpoint)
from . import lod
from .lod import LoDTensor, LoDTensorArray, create_lod_tensor
from . import parallel
from .parallel.parallel_executor import ParallelExecutor
from .core.compiler import CompiledProgram, BuildStrategy, ExecutionStrategy
from . import amp
from . import profiler
from .data_feeder import DataFeeder
from . import reader
from . import dataset
from . import models
from . import imperative
from . import utils
# reference import-path aliases: paddle.fluid.{framework,executor,
# parallel_executor,backward} are real modules there — expose the same
# paths so `fluid.framework.Program` / `from paddle_tpu.executor
# import Executor` work after the s/paddle.fluid/paddle_tpu/ swap
from . import framework
from . import executor
from . import parallel_executor
from . import backward
from .trainer import Trainer, Inferencer, CheckpointConfig
from . import average
from .average import WeightedAverage
from . import evaluator
from . import lod_tensor
from .lod_tensor import create_random_int_lodtensor
from . import transpiler
from .transpiler import (DistributeTranspiler, DistributeTranspilerConfig,
                         InferenceTranspiler, memory_optimize,
                         release_memory, HashName, RoundRobin)
from . import analysis
from . import diagnostics
from . import resilience
from . import contrib
from .async_executor import AsyncExecutor
from .data_feed_desc import DataFeedDesc
from . import default_scope_funcs
from . import distribute_lookup_table
from . import distributed
from . import net_drawer
from . import op
from .core import EOFException
from . import annotations
from . import compat
from . import graphviz
from . import inferencer
from . import inference
from . import serving
from .batch import batch
from . import recordio_writer
from .core import backward
# the reference's pre-layers LR-decay module name (same functions as
# layers.learning_rate_scheduler)
from .layers import learning_rate_scheduler as learning_rate_decay

# Tensor/LoDTensor aliases (ref fluid.Tensor is LoDTensor without LoD)
Tensor = LoDTensor

__version__ = "0.1.0"
