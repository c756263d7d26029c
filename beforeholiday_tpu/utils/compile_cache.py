"""Where the persistent XLA compile cache lives — one rule for every launcher.

A chip call may start on a machine with nothing compiled, and the flagship
step takes tens of seconds to build. Every entry point (``chip_smoke.py``,
``benchmark/run.py``, ``testing.tpu_checks``, the example trainers and the
serve driver) calls :func:`enable_compile_cache` before its first compile.

The directory is part of the cache key's lookup path, so it must not move
between runs: never a ``tempfile`` name, a pid or a timestamp.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/beforeholiday_tpu/utils/compile_cache.py -> <checkout>
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself and
    no directory is set in code — whoever placed the cache from outside keeps
    control of it. Otherwise the cache is ``<checkout>/.jax_cache`` (ignored
    by git), the checkout found from this file's own location."""
    # The key covers the scope names: by default JAX strips them before it
    # hashes a program, so a program that differs from a cached one only in
    # its ``monitor.spans`` scopes would be served the cached executable, and
    # a device trace of it would carry the OLD names (seen on the CPU cache,
    # PR 24). The per-layer metrics read those names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
