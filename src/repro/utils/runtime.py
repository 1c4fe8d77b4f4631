"""Process set-up shared by the entry points: the device report and the
persistent compilation cache.

The cache key includes the directory, so a path that moves between runs
never hits: the directory is either the one the environment names in
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself) or one
fixed directory inside the checkout, ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation. With ``JAX_COMPILATION_CACHE_DIR``
    set, nothing is set in code and JAX uses that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def device_info() -> dict:
    """The device this process runs on, as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
