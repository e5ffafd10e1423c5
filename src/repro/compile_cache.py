"""Where JAX keeps its persistent compilation cache.

A program that compiles the same placement, diff and serving bodies run
after run should find them again.  ``JAX_COMPILATION_CACHE_DIR`` wins when
it is set: JAX reads that variable itself, and nothing else is configured.
Otherwise the cache lives at the FIXED path ``<root>/.jax_cache`` (listed
in ``.gitignore``).  The directory is part of what a cached entry is found
by, so a temporary, per-process or time-stamped path would never hit.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call before the first compile; ``root`` is the
    checkout the default ``.jax_cache`` belongs to."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    import jax

    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
