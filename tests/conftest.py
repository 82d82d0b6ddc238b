"""Test config: force an 8-virtual-device CPU platform BEFORE jax import
so parallel tests exercise real mesh sharding without TPU hardware
(SURVEY §4)."""
import os

# Tests run on an 8-virtual-device CPU mesh wherever they are started
# (a TPU host included): set the env before jax is imported and the
# config after, so an ambient JAX_PLATFORMS cannot pick another backend.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope + name counter."""
    import paddle_tpu as pt
    from paddle_tpu.core import framework as fw
    from paddle_tpu.core import scope as sc
    from paddle_tpu import unique_name
    old_main, old_startup = fw._main_program, fw._startup_program
    fw._main_program, fw._startup_program = fw.Program(), fw.Program()
    old_scope = sc._global_scope
    sc._global_scope = sc.Scope()
    with unique_name.guard():
        yield
    fw._main_program, fw._startup_program = old_main, old_startup
    sc._global_scope = old_scope
