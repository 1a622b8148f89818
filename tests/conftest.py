"""Test config: force CPU platform with 8 virtual devices.

Mirrors the reference's test strategy translation (SURVEY.md §4): logic and
sharding tests run on a virtual multi-device CPU mesh
(``xla_force_host_platform_device_count``); TPU benchmarking happens
separately via bench.py on real hardware.

Must run before jax is imported anywhere in the test process.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# The config update (post-import, pre-backend-init) pins tests to the
# virtual CPU mesh even where a TPU is attached.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def _vm_map_count() -> int:
    """Live ``mmap`` region count for this process (0 off-Linux)."""
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True, scope="module")
def _bounded_executable_maps():
    """Keep the process under ``vm.max_map_count`` (default 65530).

    Every compiled XLA:CPU executable pins code pages + constant
    buffers as live mappings in jax's global jit cache for the life of
    the process; a full-suite run accumulates ~65k regions and the
    NEXT compile past the sysctl ceiling segfaults inside LLVM's mmap
    (observed deterministically at ~93% of the suite). Dropping the
    compiled-program caches between modules caps the growth; the
    threshold keeps small runs free of recompile cost.
    """
    yield
    if _vm_map_count() > 40_000:
        import gc
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def devices():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 virtual cpu devices, got {len(d)}"
    return d


@pytest.fixture
def rng_np():
    return np.random.default_rng(42)
