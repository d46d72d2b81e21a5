"""Persistent XLA compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``, ``examples/*``) call
:func:`use_compile_cache` first thing in ``main`` — never at import, so
tests and library users keep whatever cache setting their process has.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout (git-ignored).  The path is fixed on purpose: a
directory named after a temp dir, a pid or the time would never be
found again by the next run.

The same call keeps the TPU runtime's own logs from landing in its
default ``/tmp/tpu_logs``: unless ``TPU_LOG_DIR`` is set, they are
turned off.  The runtime reads the variable when it loads, which is at
the first device query, after this call.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and the TPU runtime's
    default log directory off; return the cache directory."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
