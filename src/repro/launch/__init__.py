"""Entry points: the density serving driver (``serve_kde``) and the
persistent compile cache every entry point turns on."""

from __future__ import annotations

import os
from pathlib import Path

#: The persistent compile cache's fixed home when the environment names
#: none: a stable path inside the checkout (the path is part of JAX's cache
#: key, so a directory that moves never hits).  Listed in ``.gitignore``.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Entry points call this (``chip_smoke.py``, ``serve_kde.main``,
    ``benchmarks/run.py``) — never a module at import.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
    set here; otherwise the cache goes to ``<repo>/.jax_cache``.  Either
    way every program is cached, not only slow compiles: a cold run
    compiles dozens of small kernel programs (shape buckets, tile probes).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
