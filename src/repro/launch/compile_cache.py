"""JAX's persistent compilation cache for the entry points.

The cache is keyed by the directory it lives in, so it lives at a fixed
path: ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it
itself, and nothing else is set here), otherwise ``<checkout>/.jax_cache``.
Library modules never call this; ``launch.train``, ``launch.serve``,
``cnn.train`` and ``chip_smoke.py`` call it once at start-up.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
