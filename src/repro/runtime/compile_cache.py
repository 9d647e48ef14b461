"""JAX's persistent compilation cache, placed from outside or in the checkout.

A cold run on the chip compiles every program again (the Pallas kernels,
the model's prefill and decode steps); the persistent cache lets the next
process with the same programs skip that. ``enable()`` is called by the
entry points (``chip_smoke.py``, ``python -m repro.launch.serve``) before
their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
  names another path;
* unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  directory is part of every entry's key, so it is fixed — never a temp
  name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on; returns the directory it lives in."""
    where = os.environ.get(ENV)
    if not where:
        where = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", where)
    return where
