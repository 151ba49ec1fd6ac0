"""Where jax's persistent compilation cache lives — decided in ONE place.

The cache key includes the directory path, so a cache that moves never
hits: no path here is built from a temporary name, a pid or the time.
Every process entry (``chip_smoke.py``, the ``bench.py`` child, the CLI,
the runner, the examples, the scale-out worker, ``tests/conftest.py``)
calls :func:`enable_compile_cache` once before its first compile.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "compile_cache_dir"]

#: the checkout root: <checkout>/transmogrifai_tpu/utils/compile_cache.py
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory :func:`enable_compile_cache` puts into effect
    (JAX-free: safe to call from a parent that must stay off the chip)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache(cache_everything: bool = False) -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this sets NO directory in code (the operator, or the machine the
    program was sent to, placed the cache); otherwise the fixed
    ``<checkout>/.jax_cache`` is used. ``cache_everything`` drops the
    min-compile-time / min-entry-size thresholds — serving replicas want
    every small program cached so a sibling maps it from disk instead of
    compiling; the default keeps sub-0.5 s programs out (the test suite
    compiles thousands of them)."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    if cache_everything:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    else:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return compile_cache_dir()
