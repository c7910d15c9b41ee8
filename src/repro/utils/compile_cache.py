"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/train.py``, ``python -m repro.serve``, procs-mode
workers, ``chip_smoke.py``) call :func:`enable_compile_cache` once,
before their first compile. A cache is keyed on its path, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the caller set it
(JAX reads that variable itself, and this module then sets nothing),
otherwise ``.jax_cache/`` at the checkout root — never a temp dir, pid
or timestamp. ``JAX_ENABLE_COMPILATION_CACHE=false`` still turns the
cache off (the test suite does that).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]   # src/repro/utils/..


def compile_cache_dir() -> Path:
    """The directory the cache uses: the caller's, else the checkout's."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX at :func:`compile_cache_dir` and return it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
