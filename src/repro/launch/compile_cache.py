"""JAX's persistent compilation cache, switched on by the entry points.

Call ``enable()`` once at the start of a program (never on import): a
second process that compiles the same programs then reads them back
instead of compiling again.  The directory is ``$JAX_COMPILATION_CACHE_DIR``
when that is set — JAX reads the variable itself and this module sets no
other — and otherwise the fixed ``<repo>/.jax_cache`` (git-ignored).  The
path is part of each entry's key, so it never depends on a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
