"""Persistent XLA compilation cache for the entry points.

Called at start-up by the launchers (``repro.launch.train``,
``repro.launch.serve``) and by ``chip_smoke.py``, never at library import:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is set
    here, so the cache lives wherever the environment points.
  * unset — the cache goes to ``.jax_cache/`` at the root of the checkout.
    The path is fixed on purpose: a temporary, per-process or time-stamped
    directory would never be found again by the next run.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
