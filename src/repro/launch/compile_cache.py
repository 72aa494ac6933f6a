"""Persistent JAX compilation cache for the entry points.

Called from ``main`` functions and scripts only, never at import.  With
``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps its cache there and
nothing is changed.  Otherwise the cache goes to ``.jax_cache/`` in the
checkout: a fixed path, so a later run from the same checkout finds the
programs an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(checkout: Path = CHECKOUT) -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
