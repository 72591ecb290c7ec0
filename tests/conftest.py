"""Test harness: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is not available in CI; sharding/collective code is
validated on a virtual CPU mesh (the standard JAX testing pattern), mirroring
how the reference tests multi-node behavior with N raylets on one machine
(/root/reference/python/ray/cluster_utils.py:135).
"""

import os

# Tests run on the CPU backend whatever the machine holds: the chip is
# exercised by chip_smoke.py, one process at a time, never by pytest. The
# variables are set before jax is imported and are inherited by every
# process a test spawns; config.update below covers a jax that something
# imported earlier (backends initialize lazily, at the first
# jax.devices() call).
os.environ["JAX_PLATFORMS"] = "cpu"
# ray_tpu.init() places JAX's persistent compilation cache
# (core/compile_cache.py). It exists for the chip's long compiles; on this
# backend XLA:CPU's loader logs a machine-feature error for every entry it
# reads back, and a compile for a described TPU topology can be written
# but not read. The harness and its children stay off it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture
def runtime():
    """A fresh single-node runtime per test."""
    import ray_tpu

    rt = ray_tpu.init(num_cpus=8, detect_accelerators=False)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def cluster4():
    """A 4-logical-node cluster (multi-node-on-one-host test pattern)."""
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4, num_nodes=4, detect_accelerators=False)
    yield rt
    ray_tpu.shutdown()
