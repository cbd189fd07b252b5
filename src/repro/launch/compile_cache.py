"""Placement of JAX's persistent compilation cache, shared by every entry
point (`chip_smoke.py`, `repro.launch.serve`, `repro.launch.train`,
`benchmarks/run.py`).  Each calls `use_compile_cache()` at start-up; no
library module does so at import.
"""
from __future__ import annotations

import os
from pathlib import Path

# the checkout's root: src/repro/launch/compile_cache.py -> ../../..
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the directory compiled programs are kept in.

    `JAX_COMPILATION_CACHE_DIR`, when set, is used as is: JAX reads it
    itself and nothing is set here.  Otherwise the cache goes to
    `.jax_cache/` at the root of the checkout — a fixed path, because the
    path is part of what a later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
