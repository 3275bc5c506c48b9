"""One placement rule for JAX's persistent compilation cache.

Every entry script (``chip_smoke.py``, ``bench.py``, the examples,
``tools/kernel_parity.py``) calls :func:`enable_compile_cache` before
its first compile.  The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing
  is set in code and whoever launched the process owns the placement;
- unset: ``<checkout>/.jax_cache`` (gitignored).  The directory is part
  of the cache key, so it is a fixed path and never a temporary name, a
  pid or a time.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in effect."""
    import jax

    # cache every program: the engine's small copy/import programs cost
    # a cold compile each just like the big ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from_env = os.environ.get(_ENV)
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
