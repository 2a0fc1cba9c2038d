"""Persistent XLA compilation cache for the repository's entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and the examples call
:func:`enable_compile_cache` first thing in ``main`` so a second run with
the same cache directory reuses the first run's compiled programs.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  configured here.
* unset: the cache lives at the fixed path ``<checkout>/.jax_cache``
  (listed in ``.gitignore``).  The path is part of what makes an entry
  hit, so it is never derived from a temporary name, a process id or the
  time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
