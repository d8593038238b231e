"""Fixed, checkout-local homes for what a run caches between processes.

JAX's persistent compilation cache is keyed partly by its directory, so
a directory that moves never hits. :func:`enable_compile_cache` therefore
uses ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself;
nothing else is set in code) and otherwise one fixed directory inside
the checkout, ``<checkout>/.jax_cache`` — never a temporary name, a pid
or a time. Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, the benchmarks) call it once before compiling.
"""
from __future__ import annotations

import os


def checkout_root() -> str:
    """The checkout this package runs from (``src/repro/..`` → root)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(checkout_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
