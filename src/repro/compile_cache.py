"""JAX's persistent compilation cache for the command-line entry points.

``python chip_smoke.py``, ``python -m benchmarks.run`` and ``python -m
repro.scenarios`` call :func:`enable` before they compile anything, so a
second run of the same programs loads them from disk instead of compiling
again.  Importing the library never calls it: tests and library users keep
JAX's own default (no persistent cache).

``JAX_COMPILATION_CACHE_DIR``, when set, wins — JAX reads it at start-up and
this module sets no directory.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (git-ignored): the directory is part of what a later
run must find again, so it is never derived from a temp name, a PID or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache directory used when JAX_COMPILATION_CACHE_DIR is not set
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
