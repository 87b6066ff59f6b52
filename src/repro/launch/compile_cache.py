"""JAX's persistent compilation cache for the launchers and the chip smoke,
and the process's compile counters.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
this sets nothing.  Otherwise the cache lives at a fixed path inside the
checkout (``<repo>/.jax_cache``, git-ignored): the directory is part of
each entry's key, so a path that moved between runs would never hit.

``count_compiles()`` registers one ``jax.monitoring`` listener per process
that counts into the ``repro.obs.metrics`` registry (and so honours its
kill switch, ``metrics.set_enabled(False)``):

  repro_jax_lowerings_total                 jaxpr -> MLIR lowerings
  repro_jax_backend_compiles_total          backend compiles, cache hits too
  repro_jax_compile_cache_total{result}     persistent cache "hit", or
                                            "miss" (an entry written)
  repro_jax_compile_seconds_total{phase}    "lowering"; "backend", the
                                            backend compile less any cache
                                            load inside it; "cache_load"

The three phases do not overlap, so their sum is the time spent making
executables.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

from ..obs import metrics

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_RESULTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
PHASES = ("lowering", "backend", "cache_load")

_installed = False
_install_lock = threading.Lock()
# cache-load seconds recorded inside the backend compile now running on
# this thread; JAX reports the load before the compile that contains it
_loading = threading.local()


# the families are asked of the registry on every event: a test may
# reset it
def _lowerings() -> metrics.Counter:
    return metrics.counter("repro_jax_lowerings_total",
                           "jaxpr to MLIR lowerings")


def _backend_compiles() -> metrics.Counter:
    return metrics.counter("repro_jax_backend_compiles_total",
                           "backend compiles, persistent cache hits included")


def _cache(result: str) -> metrics.Counter:
    return metrics.counter("repro_jax_compile_cache_total",
                           "persistent compile cache: hit, or miss and "
                           "written", result=result)


def _seconds(phase: str) -> metrics.Counter:
    return metrics.counter("repro_jax_compile_seconds_total",
                           "seconds spent making executables, by phase",
                           phase=phase)


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == LOWERING_EVENT:
        _lowerings().inc()
        _seconds("lowering").inc(secs)
    elif event == BACKEND_EVENT:
        _backend_compiles().inc()
        loaded, _loading.secs = getattr(_loading, "secs", 0.0), 0.0
        _seconds("backend").inc(max(secs - loaded, 0.0))
    elif event == CACHE_LOAD_EVENT:
        _loading.secs = getattr(_loading, "secs", 0.0) + secs
        _seconds("cache_load").inc(secs)


def _on_event(event: str, **_kw) -> None:
    result = CACHE_RESULTS.get(event)
    if result:
        _cache(result).inc()


def count_compiles() -> None:
    """Register the compile listeners, once per process, and make sure
    every series exists (at zero until a compile)."""
    global _installed
    with _install_lock:
        if not _installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _installed = True
    _lowerings(), _backend_compiles()
    for result in CACHE_RESULTS.values():
        _cache(result)
    for phase in PHASES:
        _seconds(phase)


def lowerings() -> float:
    """Lowerings counted so far in this process."""
    return _lowerings().value


def _reinit_after_fork_in_child() -> None:
    global _install_lock
    _install_lock = threading.Lock()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_reinit_after_fork_in_child)


def enable_compile_cache() -> str:
    """Turn the persistent cache and the compile counters on; returns the
    cache directory in use."""
    count_compiles()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
