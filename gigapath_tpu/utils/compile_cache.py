"""The one place that decides where JAX's persistent compile cache lives.

Every driver's ``main`` calls :func:`enable_compile_cache` before its
first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module touches nothing. Where it is not, the cache goes to
``<checkout>/.jax_cache``: a FIXED path inside the checkout (git-ignored),
because the directory is part of the cache key — a path built from a temp
name, a pid or a time never hits. No other code in the repository sets a
cache directory.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Resolve the compile-cache directory and return it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
