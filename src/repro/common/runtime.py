"""Process set-up shared by the entry points (launchers, benchmarks,
`chip_smoke.py`). Called from their `main()`, never at import and never
from tests, so importing the library changes no JAX configuration."""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/common/runtime.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def init_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache lives at the fixed `<checkout>/
    .jax_cache`: the directory is part of every entry's key, so a path
    built from a temp name, pid or time would never hit."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
