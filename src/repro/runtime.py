"""Process-level JAX setup shared by the scripts: compile cache and devices.

Entry-point scripts (``chip_smoke.py``, ``benchmarks/run.py``,
``examples/*.py``) call these before their first computation. The library
itself never does: importing ``repro`` changes no JAX configuration.
"""

from __future__ import annotations

import importlib.util
import os

__all__ = ["CHECKOUT", "compile_cache_dir", "enable_compile_cache",
           "simulate_cpu_devices"]

# root of the source checkout (this file is <checkout>/src/repro/runtime.py)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives.

    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else the
    fixed ``<checkout>/.jax_cache``: the path is part of the cache key, so
    it must not move between runs.
    """
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set — JAX already
    uses that directory.
    """
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def simulate_cpu_devices(n: int) -> None:
    """Give the CPU backend ``n`` host devices, so multi-rank paths run.

    Applies only where JAX will run on the CPU — ``JAX_PLATFORMS`` selects
    it, or no TPU runtime is installed — and only before JAX initializes.
    On a TPU host the device count is the hardware's. Appends to
    ``XLA_FLAGS``, keeping a caller's other options and an explicit count.
    """
    platforms = os.environ.get("JAX_PLATFORMS", "")
    on_cpu = (platforms.split(",")[0] == "cpu"
              or (not platforms and importlib.util.find_spec("libtpu")
                  is None))
    flags = os.environ.get("XLA_FLAGS", "")
    if on_cpu and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
