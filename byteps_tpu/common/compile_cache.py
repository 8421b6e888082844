"""Persistent XLA compilation cache, placed from outside.

A 24-layer train step compiles in tens of seconds to minutes; every fresh
process pays that unless jax's persistent cache is on. The cache's path is
part of its key, so it must not move: ``JAX_COMPILATION_CACHE_DIR`` when
the caller set it (jax reads the variable itself — no other path is set
in code), else ``<checkout>/.jax_cache``, fixed by this file's location.
Entry points (``chip_smoke.py``, ``benchmark/``, the example scripts) call
:func:`enable_compile_cache` once, before their first compile.
"""

from __future__ import annotations

import os

# <checkout>/byteps_tpu/common/compile_cache.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
