"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call ``enable_compilation_cache()`` before their first
compile; importing this module changes nothing.  The cache is keyed by its
path, so the directory never comes from a temporary name, a pid or the
time: a run finds what the last run on the same checkout compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``.jax_cache/`` at the repository root (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    JAX takes the directory from ``JAX_COMPILATION_CACHE_DIR`` when that
    is set, and this sets no other; otherwise it is ``DEFAULT_DIR``.
    Every compile is cached, however short: the tuner's per-signature
    kernel closures compile in well under a second each, and a sweep has
    many of them.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
