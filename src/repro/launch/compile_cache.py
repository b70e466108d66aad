"""JAX's persistent compilation cache at a stable place.

A persistent-cache entry is found again only at the path it was written
to, so the directory must not move between runs: a temp, pid or
time-stamped directory never hits.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins (JAX reads it itself); otherwise the cache lives in
``.jax_cache`` at the root of this checkout (listed in ``.gitignore``).
A copy of the package imported from outside a checkout (no
``chip_smoke.py`` beside ``src/``) places no cache of its own.

Entry points call ``enable_compile_cache()`` once at start-up, before the
first compile: ``chip_smoke.py``, ``repro.launch.train`` and
``benchmarks/run.py``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: Path = CHECKOUT_ROOT) -> str | None:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.  Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set, and
    nothing (returning None) when ``root`` is not a checkout of this repo."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    if not (root / "chip_smoke.py").is_file():
        return None
    cache_dir = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
