"""Executable-level memory & cost observability (xmem).

Every place the framework lowers a function to an XLA executable — the
`to_static` jit cache (jit/api.py), the static-graph Executor
(static/program.py), the inference Predictor, `Plan.compile` and
`serving.LLMEngine` — reports the
compiled executable's `memory_analysis()` (argument / output / temp /
generated-code bytes, and the derived per-device peak) and
`cost_analysis()` (flops, bytes accessed) into one process-global store.

Why this exists: host-side telemetry (metrics.py / compile_tracker.py)
says *when* and *how long* XLA compiled, but capacity planning needs
*what the executable costs in HBM and FLOPs* — the number that decides
whether a config can run at all. XLA computes it for every executable;
this module stops throwing it away.

Gating: capture costs one extra-cheap branch when off. When on (the
``FLAGS_tpu_xmem`` flag, or implicitly whenever ``FLAGS_tpu_metrics``
is on), the jit entry points switch to AOT compilation
(``fn.lower(...).compile()``) for NEW signatures so the analysis comes
from the same single compile that serves the call — capture never
double-compiles.

Surfaces:
  * ``stats()`` / ``profiles()``    — snapshot of captured executables
  * ``Profiler.summary_table()``    — renders the "Memory" section
  * ``paddle_tpu.device.memory_stats`` — merges the static peaks with
    the live PJRT allocator counters
  * ``tools/pod_report.py``         — pod-fit report on a virtual mesh
  * metrics registry                — ``xmem_peak_bytes{fn=}`` etc.
    whenever ``FLAGS_tpu_metrics`` is on
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ..core import flags as _flags
from . import metrics as _metrics

__all__ = ["enabled", "enable", "disable", "capture_compiled", "analyze",
           "aot_compile", "profiles", "stats", "reset", "max_static_peak",
           "total_generated_code", "summary_lines", "peak_bytes_of",
           "record_kernel_estimate", "kernel_estimates",
           "record_reservation", "reservations"]

_FLAG_DICT = _flags._REGISTRY
_FLAG_NAME = "FLAGS_tpu_xmem"

_lock = threading.Lock()
# (source, name, sig) -> profile dict; LRU-bounded so a shape-polymorphic
# serving loop cannot grow the store without bound
_STORE: "OrderedDict[tuple, Dict[str, Any]]" = OrderedDict()
_STORE_CAP = int(os.environ.get("PADDLE_TPU_XMEM_CAP", "256"))


def enabled() -> bool:
    """Capture is on when FLAGS_tpu_xmem is set, or implicitly whenever
    the metrics registry is on (the numbers must reach the exporter)."""
    return bool(_FLAG_DICT.get(_FLAG_NAME, False)) or _metrics.enabled()


def enable():
    _flags.set_flags({_FLAG_NAME: True})


def disable():
    _flags.set_flags({_FLAG_NAME: False})


def _cost_dict(compiled) -> Dict[str, float]:
    """Normalize compiled.cost_analysis() across jax versions: it has
    returned a bare dict, a list of per-computation dicts, and None."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if isinstance(ca, dict) else {}


def peak_bytes_of(mem) -> int:
    """Per-device peak HBM of one executable from CompiledMemoryStats:
    arguments + outputs + scratch + code, minus buffers aliased
    (donated) between argument and output — the set XLA reserves while
    the executable runs."""
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
               - mem.alias_size_in_bytes)


def capture_compiled(source: str, name: str, compiled,
                     sig: Any = None) -> Optional[Dict[str, Any]]:
    """Record one compiled executable's memory/cost analysis.

    `compiled` is a jax.stages.Compiled (or anything exposing
    memory_analysis()/cost_analysis()). Returns the stored profile, or
    None when the backend provides no analysis. Never raises: the
    observability layer must not cost the computation."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is None:
        return None
    cost = _cost_dict(compiled)
    profile = {
        "source": source,
        "name": name,
        "sig": repr(sig) if sig is not None else "",
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "generated_code_bytes": int(mem.generated_code_size_in_bytes),
        "peak_bytes": peak_bytes_of(mem),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    key = (source, name, profile["sig"])
    with _lock:
        _STORE[key] = profile
        _STORE.move_to_end(key)
        while len(_STORE) > _STORE_CAP:
            _STORE.popitem(last=False)
    if _metrics.enabled():
        label = name if not profile["sig"] else f"{name}|{profile['sig']}"
        label = label[:120]
        _metrics.gauge("xmem_peak_bytes",
                       "Per-device static peak HBM of the executable",
                       fn=label).set(profile["peak_bytes"])
        _metrics.gauge("xmem_temp_bytes",
                       "Scratch (temp) bytes of the executable",
                       fn=label).set(profile["temp_bytes"])
        _metrics.gauge("xmem_flops",
                       "Per-device FLOPs of one executable invocation",
                       fn=label).set(profile["flops"])
        _metrics.counter("xmem_captures_total",
                         "Executables captured by the xmem layer").inc()
    return profile


def aot_compile(source: str, name: str, jit_fn, args, kwargs=None,
                sig: Any = None):
    """Lower+compile `jit_fn` ahead of time, capture its analysis, and
    return the Compiled (callable with the same concrete arguments).
    Returns None on any failure — callers fall back to the traced path.

    This is THE way capture avoids double compiles: the jit entry
    points call this INSTEAD of letting the first traced call compile
    internally, then dispatch every same-signature call through the
    returned executable."""
    # every captured framework compile funnels through here: a compile
    # chokepoint, so the persistent XLA cache is on (core.compile_cache)
    from paddle_tpu.core import compile_cache
    compile_cache.ensure()
    try:
        lowered = jit_fn.lower(*args, **(kwargs or {}))
        compiled = lowered.compile()
    except Exception:
        return None
    capture_compiled(source, name, compiled, sig=sig)
    return compiled


def analyze(jit_fn, *abstract_args, source: str = "manual",
            name: Optional[str] = None, **abstract_kwargs):
    """One-shot AOT analysis of a jitted function against (possibly
    abstract jax.ShapeDtypeStruct) arguments: compiles, captures, and
    returns (profile, compiled). Raises on compile failure — the
    explicit-analysis path (pod_report) wants the real error."""
    from paddle_tpu.core import compile_cache
    compile_cache.ensure()
    lowered = jit_fn.lower(*abstract_args, **abstract_kwargs)
    compiled = lowered.compile()
    profile = capture_compiled(
        source, name or getattr(jit_fn, "__name__", "fn"), compiled)
    return profile, compiled


def profiles() -> List[Dict[str, Any]]:
    with _lock:
        return [dict(p) for p in _STORE.values()]


def stats() -> Dict[str, Any]:
    """Aggregate snapshot: executable count, max/total static peaks."""
    with _lock:
        vals = list(_STORE.values())
    return {
        "executables": len(vals),
        "max_peak_bytes": max((p["peak_bytes"] for p in vals), default=0),
        "total_temp_bytes": sum(p["temp_bytes"] for p in vals),
        "total_generated_code_bytes": sum(p["generated_code_bytes"]
                                          for p in vals),
        "profiles": [dict(p) for p in vals],
    }


def max_static_peak() -> int:
    """Largest per-device peak across captured executables — the
    analysis-derived lower bound on HBM high-water (any one of these
    executables running alone needs this much)."""
    with _lock:
        return max((p["peak_bytes"] for p in _STORE.values()), default=0)


def total_generated_code() -> int:
    with _lock:
        return sum(p["generated_code_bytes"] for p in _STORE.values())


# ---------------------------------------------------------------------------
# Pallas kernel VMEM estimates (fed by analysis/kernel_checks — the
# Level-3 verifier computes blocks+scratch per pallas_call site; this
# store makes the numbers visible to the Profiler and pod_report)
# ---------------------------------------------------------------------------

_KERNELS: "OrderedDict[tuple, Dict[str, Any]]" = OrderedDict()
_KERNELS_CAP = 256


def record_kernel_estimate(kernel: str, vmem_bytes: int, **detail) -> None:
    """Record one kernel's estimated per-invocation VMEM footprint.
    Keyed by (kernel, call site) so retracing the same site updates in
    place; LRU-bounded like the executable store."""
    entry = {"kernel": kernel, "vmem_bytes": int(vmem_bytes)}
    entry.update(detail)
    key = (kernel, entry.get("where", ""))
    with _lock:
        _KERNELS[key] = entry
        _KERNELS.move_to_end(key)
        while len(_KERNELS) > _KERNELS_CAP:
            _KERNELS.popitem(last=False)
    if _metrics.enabled():
        _metrics.gauge(
            "xmem_kernel_vmem_bytes",
            "Estimated per-invocation VMEM of a verified Pallas kernel",
            kernel=kernel[:120]).set(entry["vmem_bytes"])


def kernel_estimates() -> List[Dict[str, Any]]:
    """Snapshot of recorded kernel VMEM estimates, largest first."""
    with _lock:
        vals = [dict(v) for v in _KERNELS.values()]
    vals.sort(key=lambda e: -e["vmem_bytes"])
    return vals


# ---------------------------------------------------------------------------
# Long-lived HBM reservations (fed by serving/kv_cache — preallocated
# pools that memory_analysis() of any single executable cannot see; a
# capacity plan must add them to the static peaks)
# ---------------------------------------------------------------------------

_RESERVATIONS: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()


def record_reservation(name: str, nbytes: int, **detail) -> None:
    """Record (or update, keyed by name) one long-lived HBM reservation
    — e.g. the paged-KV pools.  ``nbytes <= 0`` drops the entry (the
    pool was released)."""
    with _lock:
        if nbytes <= 0:
            _RESERVATIONS.pop(name, None)
        else:
            entry = {"name": name, "bytes": int(nbytes)}
            entry.update(detail)
            _RESERVATIONS[name] = entry
    if _metrics.enabled():
        _metrics.gauge(
            "xmem_reserved_bytes",
            "Long-lived HBM reservation (paged-KV pools etc.)",
            pool=name[:120]).set(max(int(nbytes), 0))


def reservations() -> List[Dict[str, Any]]:
    """Snapshot of live reservations, largest first."""
    with _lock:
        vals = [dict(v) for v in _RESERVATIONS.values()]
    vals.sort(key=lambda e: -e["bytes"])
    return vals


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


def summary_lines(top: int = 8) -> List[str]:
    """The "Memory" block of Profiler.summary_table(): one row per
    captured executable, largest static peak first."""
    with _lock:
        vals = sorted(_STORE.values(), key=lambda p: -p["peak_bytes"])
        kernels = sorted(_KERNELS.values(),
                         key=lambda e: -e["vmem_bytes"])
    res_lines = [f"  reserved {r['name'][:34]:<34}"
                 f"{_fmt_bytes(r['bytes']):>12}"
                 for r in reservations()]
    lines = ["Memory"]
    if not vals:
        hint = ("  (no executables captured — set FLAGS_tpu_xmem or "
                "FLAGS_tpu_metrics before compiling)")
        lines.append(hint)
        return lines + _kernel_lines(kernels, top) + res_lines
    lines.append(f"  executables: {len(vals)}  "
                 f"(static peaks from compiled.memory_analysis)")
    header = (f"  {'Executable':<38}{'PeakHBM':>12}{'Temp':>12}"
              f"{'Args':>12}{'FLOPs':>12}")
    lines.append(header)
    for p in vals[:top]:
        label = f"{p['source']}:{p['name']}"
        lines.append(f"  {label[:38]:<38}"
                     f"{_fmt_bytes(p['peak_bytes']):>12}"
                     f"{_fmt_bytes(p['temp_bytes']):>12}"
                     f"{_fmt_bytes(p['argument_bytes']):>12}"
                     f"{p['flops']:>12.3g}")
    if len(vals) > top:
        lines.append(f"  ... {len(vals) - top} more "
                     f"(xmem.profiles() has all)")
    lines += _kernel_lines(kernels, top)
    lines += res_lines
    return lines


def _kernel_lines(kernels: List[Dict[str, Any]], top: int) -> List[str]:
    if not kernels:
        return []
    lines = [f"  Pallas kernels: {len(kernels)}  "
             f"(VMEM estimates from the Level-3 verifier)"]
    for e in kernels[:top]:
        budget = e.get("budget_bytes")
        verdict = ""
        if budget:
            verdict = (" OVER" if e["vmem_bytes"] > budget else " ok")
            verdict += f" (budget {_fmt_bytes(budget)})"
        lines.append(f"    {e['kernel'][:36]:<36}"
                     f"{_fmt_bytes(e['vmem_bytes']):>12}{verdict}")
    return lines


def reset():
    """Drop all captured profiles (tests / between benchmark cases)."""
    with _lock:
        _STORE.clear()
        _KERNELS.clear()
        _RESERVATIONS.clear()
