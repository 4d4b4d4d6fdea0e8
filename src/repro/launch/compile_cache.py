"""Persistent JAX compilation cache shared by every entry point.

A 28-layer serving or training program takes tens of seconds to compile,
and a fresh process compiles everything again.  ``enable()`` turns on JAX's
persistent cache so the next process with the same programs reads them
back instead:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and the
  cache lives there; no other directory is set in code.
* otherwise: ``.jax_cache/`` at the repository root.  The path is fixed
  because it is part of what the cache is keyed on; a directory that
  moved between runs would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation of the process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, however quick: the serving path compiles many
    # small eager ops whose compile times add up on a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
