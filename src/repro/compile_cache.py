"""Where the entry scripts keep JAX's persistent compilation cache.

Called explicitly by ``chip_smoke.py`` and ``bench/run.py`` at start-up,
never on import of ``repro``: a library import must not redirect a caller's
cache.
"""

from __future__ import annotations

import os

import jax

CACHE_SUBDIR = ".jax_cache"


def use_persistent_compile_cache(checkout_root: str) -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, wins: JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout_root>/.jax_cache``
    (git-ignored) — one fixed path, because the path is part of what a later
    process must find again.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout_root), CACHE_SUBDIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
