"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles (``python -m repro.exp``, the
``benchmarks`` mains, ``chip_smoke.py``) calls :func:`enable` once,
before its first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set,
places the cache from outside, and no other directory is used.
Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (git-ignored) — a fixed path, because the path is
part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> Path:
    """The directory the persistent compilation cache uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> Path:
    """Turn on JAX's persistent compilation cache at :func:`cache_dir`."""
    import jax
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
