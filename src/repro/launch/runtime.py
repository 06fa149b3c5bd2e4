"""Process set-up for entry points: compile cache and device counts.

Entry points (``chip_smoke.py``, ``examples/``, ``repro.launch.*``) call
these before they build anything; importing the library never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout: the cache key includes the directory, so
# a path that moved between runs would never hit
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed; otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def use_cpu_devices(n: int) -> None:
    """Give XLA's CPU backend ``n`` devices (a CPU stand-in for an n-chip
    mesh).  Only the CPU backend reads this, so a TPU run is unaffected.
    Must run before JAX initializes its backends."""
    if n > 1:
        jax.config.update("jax_num_cpu_devices", n)


def require_devices(n: int, what: str) -> list:
    """The first ``n`` devices JAX sees; a clear error when there are fewer.

    Nothing re-executes itself with more devices: one process holds every
    chip it can see, so a child process could not reach them."""
    devices = jax.devices()
    if n > len(devices):
        raise RuntimeError(
            f"{what} needs {n} devices but this process sees {len(devices)} "
            f"({devices[0].platform}); on the CPU, run it with "
            f"JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return devices[:n]
