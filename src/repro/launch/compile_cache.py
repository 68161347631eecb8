"""Where JAX keeps its persistent compilation cache — one rule for every
entry point (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``
and the test suite's ``conftest.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
changes.  Otherwise the cache lives at one fixed directory inside the
checkout, :data:`CACHE_DIR` (gitignored), so that a second run of the same
programs from the same checkout finds the first run's executables: the
directory is part of the cache key, so it never moves with a temporary name,
a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Apply the rule above; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)
