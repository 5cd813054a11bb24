"""Test configuration: run everything on a virtual 8-device CPU mesh.

The reference tests multi-device logic on CPU by mapping ctx groups to
mx.cpu(0)/mx.cpu(1) (SURVEY.md §4 "multi-device-without-GPUs trick"). The JAX
equivalent is --xla_force_host_platform_device_count: 8 virtual CPU devices,
so sharding/collective paths compile and run without TPU hardware.

Tests never touch the chip (the tier-1 command in ROADMAP.md sets
JAX_PLATFORMS=cpu; the platform is pinned again here so a bare ``pytest``
behaves the same). XLA_FLAGS is read at CPU-client creation, so the
virtual-device count can be injected through the environment.
"""
import os

os.environ["MXNET_DEFAULT_CONTEXT"] = "cpu"  # default ctx → virtual CPU devices
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")
# hermetic: the pytest process neither reads nor fills the persistent compile
# cache of the checkout (mxnet_tpu/compile_cache.py keeps it on for real runs)
jax.config.update("jax_enable_compilation_cache", False)
