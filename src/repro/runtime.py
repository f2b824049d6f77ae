"""Process-wide JAX state that belongs to entry points, not to imports.

Library modules never configure JAX's persistent compilation cache.
Entry points (`chip_smoke.py`, `benchmarks/run.py`, the benchmark
harness) call `use_compile_cache()` once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache directory (``src/repro/`` -> checkout root).
#: A fixed path: the directory is part of the cache key, so one that
#: moves between runs never hits.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory (JAX
    reads the variable itself, so no other directory is set here).
    Otherwise the cache lives in ``<checkout>/.jax_cache``.  Every
    program is cached, however short its compile: the service compiles
    one program per padded bucket shape, most of them in well under the
    default one-second threshold, and together they are its set-up
    time."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
