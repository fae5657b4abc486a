"""Persistent XLA compile cache for the entry-point scripts.

Called by ``chip_smoke.py``, ``examples/serve_hnn.py`` and
``launch/train_cli.py`` before their first compile — never at import, so
the test suite never writes a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout cache directory (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself
    and nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``, so a later run from the same checkout
    finds what an earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
