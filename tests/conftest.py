"""Test harness: force an 8-virtual-device CPU backend before JAX initializes.

Mirrors the reference's strategy of testing cluster behavior without a cluster
(SURVEY.md §4: LocalServer / mock connections): shard_map/pjit paths run on
xla_force_host_platform_device_count=8 virtual devices.
"""

import contextlib
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
    _ops.PROGRAMS._programs.clear()
    from galaxysql_tpu.exec.device_cache import GLOBAL_DEVICE_CACHE
    GLOBAL_DEVICE_CACHE.clear()
    from galaxysql_tpu.parallel.mesh import GLOBAL_MESH_CACHE
    with GLOBAL_MESH_CACHE._lock:
        GLOBAL_MESH_CACHE._map.clear()
    jax.clear_caches()


@contextlib.contextmanager
def _chip_formulations():
    from galaxysql_tpu.exec import operators as ops
    from galaxysql_tpu.kernels import relational as K
    real, K.prefer_scatter = K.prefer_scatter, lambda: False
    with ops._JIT_CACHE_LOCK:
        saved = dict(ops._JIT_CACHE)
        ops._JIT_CACHE.clear()
    # the registry describes the programs of `_JIT_CACHE`, key for key
    described = dict(ops.PROGRAMS._programs)
    ops.PROGRAMS._programs.clear()
    try:
        yield
    finally:
        K.prefer_scatter = real
        with ops._JIT_CACHE_LOCK:
            ops._JIT_CACHE.clear()
            ops._JIT_CACHE.update(saved)
        ops.PROGRAMS._programs.clear()
        ops.PROGRAMS._programs.update(described)


@pytest.fixture
def chip_formulation():
    """The formulations a TPU traces (`K.prefer_scatter` false: sort group-by,
    sorted join), for one test on this CPU.  Programs are keyed alike under
    either formulation, so `_JIT_CACHE` (and the registry that describes it)
    is emptied for the test and put back after it: none built under the patch
    outlives it, none built before it is taken for the chip's."""
    with _chip_formulations():
        yield


@pytest.fixture(scope="module")
def chip_formulation_module():
    """`chip_formulation` for a module whose fixture builds the programs."""
    with _chip_formulations():
        yield
