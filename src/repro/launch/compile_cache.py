"""JAX's persistent compilation cache for the program's entry points.

Called from each entry point's ``__main__`` block (the launch CLIs,
``benchmarks/*``, ``chip_smoke.py``), never at import and never from
tests: a test process compiles for the CPU, and a compile for a
described TPU is written to the cache but cannot be read back.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed path: the directory is part of each entry's key, so a cache
# that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs in ``JAX_COMPILATION_CACHE_DIR`` when it is
    set (JAX reads it itself), else in ``.jax_cache/`` at the repo root."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
