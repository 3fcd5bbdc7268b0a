"""Persistent XLA compilation cache for the entry points.

Each entry point (``launch.train``, ``launch.serve``, ``benchmarks.run``,
``chip_smoke.py``) calls ``enable_compile_cache()`` once, before its first
compile; importing the package never touches the cache. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
overrides it. Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache``, so that the next run finds it again: a
path derived from a temp name, pid or time would never hit.
"""
from __future__ import annotations

import collections
import os
import pathlib
from typing import Dict

import jax
import jax.monitoring

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
# XLA compile (or persistent-cache load) time of one jitted program
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# process-wide, like the JAX monitoring hooks that feed them
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_compile_s: Dict[str, float] = collections.defaultdict(float)
_listening = False


def _on_event(name: str, **_) -> None:
    if name in _EVENTS:
        _counts[_EVENTS[name]] += 1


def _on_duration(name: str, secs: float, fun_name: str = "?", **_) -> None:
    if name == _COMPILE_EVENT:
        _compile_s[fun_name] += secs


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory it uses."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return path


def cache_counts() -> Dict[str, int]:
    """Persistent-cache hits and misses seen since the cache was enabled."""
    return dict(_counts)


def compile_seconds() -> Dict[str, float]:
    """Seconds spent compiling (or loading from the cache) per jitted
    function name, since the cache was enabled."""
    return dict(_compile_s)
