"""The one place the persistent compilation cache is configured.

Entry points (``train_main``, ``serve_main``, the fleet worker,
``benchmarks/run.py``, ``chip_smoke.py``, the dry-run) call
:func:`use_compile_cache` when they start, never at import time. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
overrides it; otherwise the cache lives in a fixed directory of the
checkout (``artifacts/``, ignored by git). The path is part of the cache's
key, so it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
