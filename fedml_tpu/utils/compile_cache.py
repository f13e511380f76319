"""Where JAX's persistent compilation cache lives — decided from outside.

The cache directory is part of the cache key, so it must not move between
runs.  ``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself) wins: where it is
set, nothing here touches the setting.  Where it is not, the cache sits at
the fixed ``<checkout>/.jax_cache`` (git-ignored).  The Parrot executable
cache takes its directory from the same setting
(``ParrotAPI._aot_cache_path``).
"""

from __future__ import annotations

import os

#: the checkout that holds this package
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Point JAX at the persistent compilation cache and return its
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
