"""Compile observability + persistent compilation cache.

Feeds three registry metrics from ``jax.monitoring`` listeners:

  * ``qn.compiles``           — XLA backend compiles actually performed;
  * ``qn.compile_ms``         — total milliseconds spent in them (integer
                                ms; the registry's counters are exact ints);
  * ``qn.compile_cache_hits`` — executables served by the persistent
                                compilation cache instead of compiled.

JAX fires ``/jax/compilation_cache/cache_hits`` immediately BEFORE the
matching ``/jax/core/compile/backend_compile_duration`` event (which then
measures retrieval, not compilation), both on the compiling thread — so a
thread-local flag marks the next duration event as a cache hit rather
than a real compile.

``install()`` (idempotent, called on ``repro.core.qn_sim`` import so every
entry point is covered) also turns on JAX's persistent compilation cache:
in ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and no other directory is set here), else in the fixed
``<checkout>/.jax-cache``.  The path is part of what makes a later run hit,
so it never moves.  Repeat runs then start warm — a warm second solve of a
same-class problem reports 0 new compiles (regression-tested in
``tests/test_shapes.py``; asserted by the CI compile-budget smoke).  See
docs/performance.md.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

from repro.obs import metrics as _obs_metrics

_REG = _obs_metrics.registry()
_COMPILES = _REG.counter("qn.compiles",
                         help="XLA backend compiles performed")
_COMPILE_MS = _REG.counter("qn.compile_ms",
                           help="total backend compile time [ms, int]")
_CACHE_HITS = _REG.counter("qn.compile_cache_hits",
                           help="persistent-compile-cache retrievals")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# <checkout>/.jax-cache: this file is <checkout>/src/repro/obs/compile.py
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax-cache")

_tls = threading.local()
_installed = False
_install_lock = threading.Lock()


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _tls.pending_cache_hit = True


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    hit = getattr(_tls, "pending_cache_hit", False)
    _tls.pending_cache_hit = False
    with _REG.lock:
        if hit:
            _CACHE_HITS.inc()
        else:
            _COMPILES.inc()
            _COMPILE_MS.inc(round(duration_secs * 1000))


def install() -> None:
    """Register the monitoring listeners and turn on the persistent
    compilation cache, once per process.  The cache drops JAX's
    min-time/min-size thresholds so every executable is kept (the
    simulator's programs are small; a cold run wants all of them)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax
        from jax import monitoring
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        _installed = True


def compile_stats() -> dict:
    """Consistent snapshot of the compile counters: ``compiles``,
    ``compile_ms``, ``cache_hits``.  Subtract two snapshots for a
    per-phase compile/execute split (``wall - compile_ms`` is execute +
    host time; ``RunReport.telemetry["compile"]`` and the BENCH files
    record the deltas)."""
    with _REG.lock:
        return {"compiles": _COMPILES.value,
                "compile_ms": _COMPILE_MS.value,
                "cache_hits": _CACHE_HITS.value}


def reset_compile_stats() -> None:
    with _REG.lock:
        _COMPILES.reset()
        _COMPILE_MS.reset()
        _CACHE_HITS.reset()
