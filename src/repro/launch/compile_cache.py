"""JAX's persistent compilation cache for the launch drivers.

A compiled program is keyed on the cache directory among other things, so
the directory is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads the variable itself), else ``.jax_cache`` at
the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/launch/``.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile.  Returns the cache directory.  Every compile is cached, however
    quick: an autotune compiles dozens of kernels of about a second each."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
