"""Process-level runtime setup shared by the entry points.

Called from ``serve.main``, ``benchmarks/bench_serving.main`` and
``chip_smoke.py`` — never at import time and never from tests, so that
importing any module leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
import pathlib
from typing import Dict

import jax

# <repo>/.jax_cache (listed in .gitignore): a fixed path, because the
# directory is part of what a later run must find again
_DEFAULT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE))
    return str(_DEFAULT_CACHE)


def device_info() -> Dict[str, object]:
    """The devices as JAX reports them: platform, kind and count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_label() -> str:
    d = device_info()
    return f"{d['platform']} {d['kind']} x{d['count']}"
