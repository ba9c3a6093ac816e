"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``dsp_serve``, the benchmark scripts)
call :func:`enable` once, before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at import and this
module changes nothing.  Otherwise the cache goes to the fixed
``<checkout>/.jax_cache`` (git-ignored): the directory is part of each
entry's key, so a path that moved between runs would never hit.

Library code and tests never call this — importing the package leaves
JAX's cache settings as the environment made them.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


__all__ = ["CHECKOUT_CACHE_DIR", "enable"]
