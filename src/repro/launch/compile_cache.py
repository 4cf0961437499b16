"""JAX's persistent compilation cache, placed in one place.

Entry points (``chip_smoke.py``, ``examples/serve_batched.py``,
``examples/train_lm.py``) call :func:`enable` before they compile;
importing this module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this module names no other directory.
Otherwise the cache lives at ``<repo>/.jax_cache`` (listed in
``.gitignore``): a fixed path, because a cache whose directory moves
between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"

_counts = {"hits": 0, "misses": 0, "compiles": 0, "compile_s": 0.0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == _HIT:
        _counts["hits"] += 1
    elif event == _MISS:
        _counts["misses"] += 1


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == _COMPILE:
        _counts["compiles"] += 1
        _counts["compile_s"] += secs


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache every program, not only those slower than a second to build
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return path


def stats() -> dict:
    """Cache hits and misses, and backend compiles with their seconds,
    counted since :func:`enable` was first called."""
    return dict(_counts)
