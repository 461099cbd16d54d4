"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``, the benchmark
harness) call :func:`enable_compile_cache` once, before their first
compile. Importing ``repro`` never does: the tests' compile rehearsals
for a described TPU must not write entries that no chip can read back.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

# <checkout>/.jax_cache: a fixed path, since the directory is part of
# what a later process must find again.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
    itself); otherwise the cache lives in :data:`DEFAULT_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    return path
