"""Process start-up and device selection.

The few facts every entry point and every backend-adaptive call site must
agree on: where JAX keeps compiled programs, which device a program placed
*now* will run on, and what this host's CPU is (native library + AOT cache
keys).  x64 is enabled by the package import itself (`galaxysql_tpu/__init__`).
"""

from __future__ import annotations

import functools
import hashlib
import os
import platform as _platform

import jax

# the directory that holds the `galaxysql_tpu` package (the git checkout)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory in use.

    `JAX_COMPILATION_CACHE_DIR` wins and nothing is set in code (JAX reads the
    variable itself).  Otherwise the cache lives at the fixed path
    `<checkout>/.jax_cache`: the path is part of every entry's key, so it never
    depends on the home directory, a temp name, a pid or the host."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def exec_device():
    """The device a program dispatched by this thread runs on when its inputs
    are uncommitted (host numpy): the `jax.default_device` pin when one is
    active (the TP host path), else the default backend's first device."""
    d = jax.config.jax_default_device
    if d is None:
        return jax.devices()[0]
    if isinstance(d, str):
        return jax.devices(d)[0]
    return d


def exec_platform() -> str:
    """Platform of `exec_device()`.  Inside the CPU-pinned TP context
    `jax.default_backend()` still names the accelerator, so every
    backend-adaptive choice (scatter vs sort formulations, program keys)
    asks this instead."""
    return exec_device().platform


def device_report() -> dict:
    """What JAX says it runs on; every printed measurement carries this."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


@functools.cache
def host_isa_id() -> str:
    """Stable fingerprint of this host's CPU ISA (machine + model + feature
    flags, no frequencies): keys artifacts compiled for the host CPU."""
    desc = _platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            desc += "".join(sorted({ln for ln in f if ln.startswith(
                ("flags", "Features", "model name", "CPU part"))}))
    except OSError:
        desc += _platform.processor()
    return hashlib.md5(desc.encode()).hexdigest()[:12]
