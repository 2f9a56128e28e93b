"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call `enable_compile_cache` before their first compile; library modules
never do, so importing the package leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here. Otherwise the cache lives in ``<repo>/.jax_cache``:
    a fixed path, because the path is part of what a later run must find.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
