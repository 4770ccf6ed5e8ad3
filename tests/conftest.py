"""Test harness: force an 8-virtual-device CPU backend before JAX initializes.

Mirrors the reference's strategy of testing cluster behavior without a cluster
(SURVEY.md §4: LocalServer / mock connections): shard_map/pjit paths run on
xla_force_host_platform_device_count=8 virtual devices.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import galaxysql_tpu  # noqa: E402,F401 — the package import enables x64

import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_compiled_caches():
    """Free compiled XLA programs between test modules.

    The full suite compiles thousands of kernels; XLA:CPU's compiler has been
    observed to segfault late in the run under that accumulated state.  Dropping
    the process-wide jit caches (ours + jax's) at module boundaries keeps the
    live-executable population bounded without changing any test's behavior
    (first query of each module recompiles)."""
    yield
    from galaxysql_tpu.exec import operators as _ops
    with _ops._JIT_CACHE_LOCK:
        _ops._JIT_CACHE.clear()
    from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE
    GLOBAL_DEVICE_CACHE.clear()
    from galaxysql_tpu.parallel.mesh import GLOBAL_MESH_CACHE
    with GLOBAL_MESH_CACHE._lock:
        GLOBAL_MESH_CACHE._map.clear()
    jax.clear_caches()
