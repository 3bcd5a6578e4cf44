"""JAX's persistent compilation cache, kept at one fixed place.

``enable()`` is called by the entry points (the ``repro.launch`` mains and
``chip_smoke.py``); importing a module never turns the cache on.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
sets another directory.  Otherwise the cache lives in ``<checkout>/.jax_cache``
(listed in ``.gitignore``): a fixed path, because the directory is part of
what a later run has to find.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
