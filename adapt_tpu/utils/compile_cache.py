"""Where compiled programs persist between processes.

The directory is part of the persistent cache's key, so it must not
move: no temp name, pid or time in the path. Placement belongs to the
environment — ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself —
and only when that is unset does the program choose, once, a fixed
directory inside the checkout (``.jax_cache``, git-ignored).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

_CHECKOUT = Path(__file__).resolve().parents[2]


def ensure_compile_cache() -> str:
    """Enable JAX's persistent compilation cache and return its
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set this touches no
    JAX config (the environment already placed the cache, and its
    thresholds are the environment's too); otherwise it points
    ``jax_compilation_cache_dir`` at ``<checkout>/.jax_cache`` and
    keeps every program, not only those that took a second to compile:
    a serving start-up is a few big programs and several hundred small
    ones, and on a v5e a warm ``chip_smoke.py`` under JAX's default
    one-second write threshold still compiled for 108 s of a cold 234 s;
    keeping everything, 10 s of 190 s (PR 21, PERF.md). Call before the
    first compile; the entry
    points do (``chip_smoke.py``, ``bench.py``, ``python -m
    adapt_tpu.comm.remote``)."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
