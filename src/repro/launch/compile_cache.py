"""JAX's persistent compilation cache, at a directory that can be placed.

Entry points (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``enable_compile_cache()`` once in ``main``,
before anything compiles; importing this module changes nothing.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and no other
is used. Otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed
path derived from this file's location: the path is part of the cache
key, so a directory named after a pid, a temporary name or the time would
never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
_CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives (not created here)."""
    return os.environ.get(ENV_VAR) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory. Must run before the first compile."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
