"""JAX's persistent compilation cache, in one place for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: the cache
lives there and nothing else is configured.  Otherwise the cache lives in
``.jax_cache/`` at the root of the checkout — a fixed path, so the next
run from the same checkout finds what this one compiled; it is listed in
``.gitignore``.

:func:`compile_stats` counts backend compiles (seconds) and persistent
cache hits and misses from JAX's monitoring events, so a run can show
how much of its start-up the cache saved.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

_stats = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
_watching = False


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _stats["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _stats["cache_misses"] += 1


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _stats["compile_s"] += duration


def compile_stats() -> dict:
    """Totals since the first call: backend compile seconds (a cache hit
    costs only its read) and persistent-cache hits / misses."""
    global _watching
    if not _watching:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True
    return dict(_stats)
