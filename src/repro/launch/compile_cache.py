"""JAX's persistent compilation cache, placed from outside the program.

Every entry point that compiles (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it; otherwise the cache lives in one fixed
directory of the checkout.  The path is part of what makes an entry hit,
so it is never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_compile_cache
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def compile_cache_dir(env: Optional[Mapping[str, str]] = None) -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed in-checkout directory."""
    env = os.environ if env is None else env
    return env.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Entries are kept whatever their compile time: the serving path compiles
    many small eager programs, and their sum is what a warm start saves.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
