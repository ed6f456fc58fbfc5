"""JAX's persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.serve_so3``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their
first compile; library code never does.  Where
``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is set here.  Otherwise the cache lives in ``.jax_cache/`` at the
repository root: a fixed path, because the path is part of what a later
process must find again.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
