"""Where JAX's persistent compilation cache lives: one rule for every entry point.

The cache key includes the cache directory, so a directory that moves (a
temp dir, a per-run path) never hits.  ``configure_compile_cache`` is called
once by each entry point (``repro.launch.serve``, ``chip_smoke.py``) before
the first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, nothing is set
    here;
  * otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).

Processes started later (sched/worker.py) inherit the environment, not this
process's JAX config.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed home -> the path."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Pallas kernels compile in well under JAX's 1 s default threshold, and
    # those are exactly the executables a second run wants back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
