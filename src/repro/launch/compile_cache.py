"""Where an entry point keeps JAX's persistent compilation cache.

Called by the entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/serve_bench.py``) before their first compile, never at
import.  A cache is found again only under the same directory, so the
fallback is one fixed path inside the checkout (git-ignored), never a
temp, pid or time-based one.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
    else is set.  Otherwise the cache goes to ``<repo>/.jax_cache``.  On
    the CPU backend (tests, CPU rehearsals) it stays off and this returns
    None: those compiles take seconds and would only litter the tree."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
