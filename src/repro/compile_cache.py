"""JAX's persistent compilation cache, configured in one place.

    from repro import compile_cache
    compile_cache.enable()

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
nothing is set here.  Otherwise the cache goes to ``<repo>/.jax-cache``: a
fixed path, whatever the working directory, so each run of a checkout finds
what the runs before it compiled.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax-cache"


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
