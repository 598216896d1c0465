"""One persistent compilation cache for every entry point.

A cold process recompiles every program it runs; at published widths that
is minutes per serving or training job.  :func:`enable_compile_cache` is
called first by ``launch/serve.py``, ``launch/train.py`` and
``chip_smoke.py``:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  nothing else is set here;
* otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
  because the directory is part of what a later run must find again.

It also counts the cache's hits and misses and the seconds spent in the
backend compiler (``jax.monitoring`` events), so a run can report whether
its programs came from the cache.
"""
from __future__ import annotations

import collections
import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT", "enable_compile_cache", "compile_stats"]

CHECKOUT = Path(__file__).resolve().parents[3]
_PREFIX = "/jax/compilation_cache/"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_EVENTS: collections.Counter = collections.Counter()
_LISTENING = False


def _count(event: str, **kwargs) -> None:
    if event.startswith(_PREFIX):
        _EVENTS[event[len(_PREFIX):]] += 1


def _time(event: str, seconds: float, **kwargs) -> None:
    if event == _COMPILE:
        _EVENTS["backend_compile_s"] += seconds


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    global _LISTENING
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if not _LISTENING:
        jax.monitoring.register_event_listener(_count)
        jax.monitoring.register_event_duration_secs_listener(_time)
        _LISTENING = True
    return path


def compile_stats() -> dict[str, float]:
    """Persistent-cache lookups, hits and misses seen by this process, and
    its seconds in the backend compiler."""
    return {"cache_requests": _EVENTS["compile_requests_use_cache"],
            "cache_hits": _EVENTS["cache_hits"],
            "cache_misses": _EVENTS["cache_misses"],
            "backend_compile_s": round(_EVENTS["backend_compile_s"], 3)}
