"""JAX's persistent compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache directory and the
program sets no other.  Otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, ignored by git).  The directory is part of
what a later process must find again, so it is never derived from a temp
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and return
    it.  Call before the first compile."""
    path = Path(os.environ.get(ENV_VAR) or REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
