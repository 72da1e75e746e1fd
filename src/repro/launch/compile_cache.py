"""Persistent XLA compilation cache for the entry points.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `benchmarks/run.py`,
the examples) call `enable_compile_cache()` once, before their first
compile; `import repro` does not, so library users and the test suite
keep JAX's own default.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed, checkout-relative: the path is part of the cache key, so a
# directory that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no
    other path is set here; otherwise the cache lives in `.jax_cache/`
    at the checkout root."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
