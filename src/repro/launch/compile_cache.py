"""Where JAX keeps its persistent compilation cache — one rule, set from
the entry points (`chip_smoke.py`, `repro.launch.serve`, `repro.launch.train`,
the `benchmarks/*.py` mains), never on import.

A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads the variable itself, so
nothing is set here.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache key, so
it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at ``<repo>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set.  Returns the directory
    this call set, or None when the environment variable decides."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
