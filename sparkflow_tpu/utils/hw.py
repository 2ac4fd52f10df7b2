"""Where compiled programs are kept between processes.

A machine that is thrown away after each run loses everything outside the
checkout, and the cache's path is part of its key — so the persistent XLA
compilation cache lives where ``JAX_COMPILATION_CACHE_DIR`` says or, without
it, at one fixed path inside the checkout. Never the home directory, never a
name made from a pid, a time or a temporary directory.
"""

from __future__ import annotations

import os
from typing import Optional

# <checkout>/.jax_cache — git-ignored, next to the package
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache(path: Optional[str] = None) -> str:
    """Turn on JAX's persistent XLA compilation cache; returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already has its directory
    from the environment and this sets none in code (``path`` yields to it).
    Otherwise the directory is ``path`` or ``<checkout>/.jax_cache``. Safe on
    any backend; library code never calls it implicitly.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        path = env_dir
    else:
        path = path or DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything (the default keeps only compilations over 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the cache object initializes at the process's FIRST compile; one made
    # before this call would keep ignoring the directory until it is reset
    compilation_cache.reset_cache()
    return path
