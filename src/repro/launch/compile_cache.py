"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points call ``use_compile_cache()`` from ``main()``, never at import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and it wins.
Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path, because
a directory that moves between runs never hits.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = os.path.normpath(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
