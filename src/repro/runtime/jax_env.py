"""Process-level JAX setup shared by the launcher, the benches and the chip
smoke: the persistent compilation cache, and the device label every printed
result carries."""
from __future__ import annotations

import os
import pathlib

import jax

#: the cache's fixed in-checkout home when JAX_COMPILATION_CACHE_DIR is unset
#: (listed in .gitignore; the path is part of the cache key, so it never moves)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  Call it first, before anything compiles.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    return jax.config.jax_compilation_cache_dir


def device_info() -> dict:
    """The device a result was measured on, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
