"""Where compiled programs persist between processes.

Every process that compiles for the accelerator (``PartialState``,
``chip_smoke.py``, each ``bench.py`` child) calls :func:`enable_compile_cache`
before its first compile. The directory is part of the cache key's lookup,
so it must not move: either the operator places it with
``JAX_COMPILATION_CACHE_DIR`` — JAX reads that variable itself and nothing is
set here — or it is one fixed directory beside the package.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache, resolved from this file (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory."""
    import jax

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX skips programs that compiled in under 1 s by default — which is
    # most of the serving engine's page/scrub/insert programs, and a cold
    # process pays for every one of them again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
