"""Test harness: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is not available in CI; sharding/collective code is
validated on a virtual CPU mesh (the standard JAX testing pattern), mirroring
how the reference tests multi-node behavior with N raylets on one machine
(/root/reference/python/ray/cluster_utils.py:135).
"""

import os

# WHERE A LONG TEST LIVES. The driver runs this tree with `-n 6 --dist
# loadfile`: xdist hands out whole FILES, ordered by their number of tests,
# most first, and gives a worker its next file when it has two tests or fewer
# left. So a file of few tests starts last, and a long test at the end of a
# file of many holds the next file back on a busy worker: the wall follows
# the place of the long tests, not the work. The rule: a test over 25 s (on
# the driver's loaded machine: ~12 s alone) lives in a file of at most 6
# tests (`*_long.py` beside its origin, `test_tpu_compile_cells.py` and,
# since PR 48, `test_tpu_compile_cells_2.py` for a cell's compiled step),
# and such a file takes no more than 450 s. A new model's compile test and
# reference test go there from the start.
# `python scripts/suite_schedule.py <junit.xml>` names what breaks it.

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
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# What the tests spend is XLA:CPU's code generation, not the tiny programs'
# run time: with LLVM's optimiser off a file of compile-bound tests takes
# about half its seconds (tests/test_ssd.py 50 -> 28 s alone, PR 49, when the
# gate stood at 1,469 s of its 1,470). It changes which instructions the CPU
# runs, so a value pinned to the last bit is pinned under it (the one such
# pin, test_mixed_stack's hash of a seeded tree); the TPU compiles of
# test_tpu_compile*.py are libtpu's and keep their payload hashes.
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags

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
