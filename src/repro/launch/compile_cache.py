"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/profile_stack.py``) calls ``enable()`` before its first
compile, and no other code sets a cache directory. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module leaves it alone. Otherwise the cache goes to the fixed
``<checkout>/.jax_cache``: the directory is part of each entry's key, so
it is never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable(environ: Mapping[str, str] = os.environ) -> str:
    """Returns the cache directory, after pointing JAX at the checkout's
    own when ``environ`` names none."""
    if environ.get(ENV_VAR):
        return environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
