"""JAX's persistent compilation cache, enabled by the entry points only.

Importing this module changes nothing; ``enable_compile_cache()`` is called
where a process starts: ``python -m repro`` (``repro/__main__.py`` and
pipeline/cli.py's ``__main__`` guard, not ``cli.main``, which tests call
in-process), the train and serve launchers' ``main`` and ``chip_smoke.py``.  A full-width step compiles for tens of
seconds, and a cache hit on the next run of the same program skips that.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Fixed path inside the checkout (listed in .gitignore): a cache directory
#: that moves between runs never hits, so it is not put under a temp dir.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing else; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
