"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it lives at one fixed path: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads that
variable itself), else ``<repo>/.jax_cache``. Entry points call
:func:`use_compile_cache` once, before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
