"""Compile / retrace observability.

JIT recompiles are the #1 silent TPU perf killer (PAPERS.md: the MPK and
Gemma-on-TPU serving writeups both lead with it): a python scalar that
changes every step, or a dtype/shape drift between calls, silently turns
a sub-millisecond cached dispatch into a multi-second XLA compile.
Reference analog: the reference stack logs program-cache misses from
program_translator's ConcreteProgram cache; here the ground truth is
jax's own telemetry.

Two sources feed one thread-safe store:

1. `jax.monitoring` listeners (installed once, process-wide) on the
   backend-compile / jaxpr-trace duration events and the compilation
   cache hit/miss events — ground truth for "did XLA compile and for
   how long".
2. `record_trace(fn_name, ...)` calls from the `paddle_tpu.jit` entry
   points — per-function attribution: a StaticFunction that sees a new
   (treedef, static-leaf, shape, dtype) signature records one trace;
   every trace after the first is a retrace.

`stats()` snapshots everything; `Profiler.summary_table()` renders it as
the "Compilation" section. When `FLAGS_tpu_metrics` is on the same
events mirror into the metrics registry (`jit_compiles_total`,
`jit_compile_seconds_total`, `jit_retraces_total{fn=...}`).
"""
from __future__ import annotations

import threading
from typing import Dict

from . import metrics as _metrics

__all__ = ["install", "installed", "record_trace", "stats", "reset",
           "compile_count", "compile_seconds"]

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_totals = {
    "compile_count": 0,
    "compile_seconds": 0.0,
    "trace_count": 0,
    "trace_seconds": 0.0,
    "persistent_cache_hits": 0,
    "persistent_cache_misses": 0,
}
# fn name -> {"traces": n, "retraces": n}
_functions: Dict[str, Dict[str, int]] = {}
_installed = [False]


def _on_duration(event: str, duration: float, **kwargs):
    if event == _BACKEND_COMPILE_EVENT:
        with _lock:
            _totals["compile_count"] += 1
            _totals["compile_seconds"] += duration
        if _metrics.enabled():
            _metrics.counter(
                "jit_compiles_total",
                "XLA backend compiles in this process").inc()
            _metrics.counter(
                "jit_compile_seconds_total",
                "Cumulative XLA backend compile seconds").inc(duration)
    elif event == _JAXPR_TRACE_EVENT:
        with _lock:
            _totals["trace_count"] += 1
            _totals["trace_seconds"] += duration


def _on_event(event: str, **kwargs):
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _totals["persistent_cache_hits"] += 1
    elif event == _CACHE_MISS_EVENT:
        with _lock:
            _totals["persistent_cache_misses"] += 1


def install():
    """Register the jax.monitoring listeners (idempotent). Listener
    registration is append-only in jax, so this must run exactly once
    per process; the profiler package calls it at import."""
    if _installed[0]:
        return
    _installed[0] = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def installed() -> bool:
    return _installed[0]


def record_trace(fn_name: str):
    """One tracing-cache miss for `fn_name` (called by the jit entry
    points when a call signature is seen for the first time). The first
    trace of a function is its initial compile; later ones are
    retraces."""
    with _lock:
        entry = _functions.setdefault(fn_name,
                                      {"traces": 0, "retraces": 0})
        entry["traces"] += 1
        is_retrace = entry["traces"] > 1
        if is_retrace:
            entry["retraces"] += 1
    if _metrics.enabled():
        _metrics.counter("jit_traces_total",
                         "Traces per jitted function", fn=fn_name).inc()
        if is_retrace:
            _metrics.counter(
                "jit_retraces_total",
                "Tracing-cache misses after the first trace",
                fn=fn_name).inc()


def compile_count() -> int:
    return _totals["compile_count"]


def compile_seconds() -> float:
    return _totals["compile_seconds"]


def stats() -> dict:
    """Snapshot of compile totals + per-function trace attribution."""
    with _lock:
        out = dict(_totals)
        out["functions"] = {k: dict(v) for k, v in _functions.items()}
        out["retraces"] = sum(v["retraces"] for v in _functions.values())
    return out


def reset():
    """Zero all counters (tests / per-benchmark-case deltas)."""
    with _lock:
        for k in _totals:
            _totals[k] = 0 if isinstance(_totals[k], int) else 0.0
        _functions.clear()
