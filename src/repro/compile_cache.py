"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``chip_smoke.py``, ``repro.launch.solve``, ``benchmarks.run``
and the solve server).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  nothing here overrides it.
* unset: the cache goes to ``.jax_cache`` at the root of the checkout.
  The path is fixed (never built from a temporary name, a pid or the
  time) because it is part of what a cached executable is found by.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
