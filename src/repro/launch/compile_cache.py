"""Where the launchers keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, that is the
cache and nothing here overrides it.  When it is not, the cache goes to one
fixed directory of the checkout, ``<repo>/.jax_cache`` (gitignored), so that
every process run from the checkout finds what an earlier one compiled: a
temporary or per-run directory would never be hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache lives in."""
    return os.environ.get(ENV) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and return
    it.  Call before the first compile."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
