"""Persistent XLA compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there on
its own and nothing is configured here.  Otherwise the cache lives at one
fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored): the
directory is part of how a later run finds an entry, so it is never
derived from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent cache at :data:`CACHE_DIR`, unless the
    environment already chose a directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
