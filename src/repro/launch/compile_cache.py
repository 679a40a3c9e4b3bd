"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``repro.launch.serve`` and the benchmark's
harness at start-up, never on library import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is set
here; otherwise the cache goes to ``<repo>/.jax_cache`` — a fixed path,
because the directory is part of what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory (git-ignored)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
