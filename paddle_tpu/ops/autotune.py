"""Kernel autotuning: measured config selection with a persistent cache.

Reference analog: paddle/phi/kernels/autotune/ (cache.h `KernelCallback`
result cache keyed by op + shape signature; switch_autotune.cc turns
tuning on/off globally) and the Python face
python/paddle/incubate/autotune.py::set_config.

TPU-native shape: tuning happens **eagerly, outside jit** — candidates are
compiled and timed as standalone executables, the winner is recorded in a
process-global cache, and jitted graphs read the cached choice at trace
time (a static Python value, so the compiled program bakes in the tuned
block sizes; re-tracing after tuning picks up new winners). This replaces
the reference's exhaustive-search-on-first-run flow, which cannot work
inside an XLA-compiled step.

The cache can be persisted to JSON (`save`/`load`, or automatically via
``PADDLE_TPU_AUTOTUNE_CACHE=<path>``) so a separate warmup job can ship
tuned configs to production runs, like the reference's autotune cache
serialization.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

__all__ = ["set_config", "enabled", "lookup", "lookup_chain", "record",
           "tune", "save", "load", "time_callable", "cache_stats",
           "context_key", "legal_candidates", "entries", "summary_lines",
           "mosaic_block_legal"]


def mosaic_block_legal(block_shape, array_shape, dtype_bits=32):
    """Re-export of ``pallas_ops.mosaic_block_legal`` — the single
    Mosaic tiling predicate shared by candidate filtering here and the
    Level-3 kernel verifier (analysis/kernel_checks). Lazy so importing
    autotune never pays the pallas_ops import."""
    from paddle_tpu.ops.pallas_ops import mosaic_block_legal as _legal
    return _legal(block_shape, array_shape, dtype_bits=dtype_bits)

# op_name -> {key(str): config(list|tuple)}
_CACHE: dict = {}
_HITS = 0
_MISSES = 0
_ENABLED = None  # tri-state: None = follow FLAGS_use_autotune


def _flag_default() -> bool:
    try:
        from paddle_tpu.core.flags import flag
        return bool(flag("FLAGS_use_autotune"))
    except Exception:
        return True


def enabled() -> bool:
    return _flag_default() if _ENABLED is None else _ENABLED


def set_config(config=None):
    """Mirror of paddle.incubate.autotune.set_config
    (python/paddle/incubate/autotune.py): accepts a dict (or a path to a
    JSON file) with a {"kernel": {"enable": bool}} section. Unknown
    sections are ignored, as in the reference."""
    global _ENABLED
    if config is None:
        _ENABLED = True
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    kernel = config.get("kernel", {})
    if "enable" in kernel:
        _ENABLED = bool(kernel["enable"])


def _key_str(key) -> str:
    return json.dumps(key, default=str) if not isinstance(key, str) else key


def context_key(dtype_str=None):
    """The execution-context suffix every new cache key carries:
    ``[dtype, device_kind, jaxlib_version]``. A cache tuned for bf16 on a
    v5e with one jaxlib never mis-seeds an f32 run, another topology, or
    a toolchain with different Mosaic lowering (each context tunes its
    own entry; `lookup_chain` still falls back to older key layouts)."""
    if dtype_str is None:
        dtype_str = "unknown"
    try:
        import jax
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    try:
        import jaxlib
        ver = jaxlib.__version__
    except Exception:
        ver = "unknown"
    return [str(dtype_str), str(kind), str(ver)]


def lookup(op_name: str, key):
    global _HITS, _MISSES
    cfg = _CACHE.get(op_name, {}).get(_key_str(key))
    if cfg is None:
        _MISSES += 1
    else:
        _HITS += 1
    return tuple(cfg) if isinstance(cfg, list) else cfg


def lookup_chain(op_name: str, keys):
    """Try ``keys`` most-specific-first; first hit wins. Counts exactly
    one hit or one miss total (not one per fallback probe), so the
    hit/miss gauges reflect op-level cache effectiveness."""
    global _HITS, _MISSES
    table = _CACHE.get(op_name, {})
    for key in keys:
        cfg = table.get(_key_str(key))
        if cfg is not None:
            _HITS += 1
            return tuple(cfg) if isinstance(cfg, list) else cfg
    _MISSES += 1
    return None


def legal_candidates(pool, spec_fn, dtype_bits=32):
    """Filter a candidate ``pool`` down to configs whose every BlockSpec
    is Mosaic-legal — the only path by which block-shape candidates enter
    a tuning search, making illegal shapes unrepresentable by
    construction (BENCH_r02's `(1, 256)` class of launch failure).

    ``spec_fn(candidate)`` returns the candidate's full list of
    ``(block_shape, array_shape)`` pairs, or None to disqualify it
    outright (shape mismatch, VMEM budget, ...). Every pair must satisfy
    ``pallas_ops.mosaic_block_legal`` at ``dtype_bits`` for the candidate
    to survive. Preserves pool order; deduplicates."""
    from paddle_tpu.ops.pallas_ops import mosaic_block_legal
    out, seen = [], set()
    for cand in pool:
        if cand in seen:
            continue
        seen.add(cand)
        pairs = spec_fn(cand)
        if pairs is None:
            continue
        if all(mosaic_block_legal(tuple(b), tuple(a), dtype_bits=dtype_bits)
               for b, a in pairs):
            out.append(cand)
    return out


def record(op_name: str, key, config):
    _CACHE.setdefault(op_name, {})[_key_str(key)] = (
        list(config) if isinstance(config, tuple) else config)
    _publish_metrics(op_name, key, config)
    path = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if path:
        try:
            save(path)
        except OSError:
            pass


def _publish_metrics(op_name=None, key=None, config=None):
    """Mirror cache state into the metrics registry (no-op when metrics
    are off): hit/miss/size gauges plus a per-entry chosen-config gauge
    family, so exported snapshots show *what* was tuned."""
    try:
        from paddle_tpu.profiler import metrics
    except ImportError:
        return
    if not metrics.enabled():
        return
    stats = cache_stats()
    metrics.gauge("autotune_cache_entries",
                  "Tuned configs in the autotune cache").set(stats["size"])
    metrics.gauge("autotune_cache_hits",
                  "Autotune cache hits (trace-time lookups)"
                  ).set(stats["hits"])
    metrics.gauge("autotune_cache_misses",
                  "Autotune cache misses").set(stats["misses"])
    if op_name is not None and config is not None:
        label = f"{op_name}|{_key_str(key)}"[:120]
        for i, v in enumerate(config if isinstance(config, (list, tuple))
                              else [config]):
            try:
                metrics.gauge("autotune_chosen_config",
                              "Chosen block config component",
                              op=label, dim=str(i)).set(float(v))
            except (TypeError, ValueError):
                continue


def cache_stats():
    n = sum(len(v) for v in _CACHE.values())
    return {"size": n, "hits": _HITS, "misses": _MISSES}


def entries():
    """Deep copy of the cache: {op: {key_str: config}} — for bench JSON
    detail and the Profiler section."""
    return {op: dict(table) for op, table in _CACHE.items()}


def summary_lines():
    """Autotune section for Profiler.summary_table()."""
    stats = cache_stats()
    lines = ["Autotune",
             f"  cache entries: {stats['size']}  "
             f"hits: {stats['hits']}  misses: {stats['misses']}"]
    for op in sorted(_CACHE):
        for key_str, cfg in sorted(_CACHE[op].items()):
            lines.append(f"  {op} {key_str} -> {cfg}")
    return lines


def save(path: str):
    """Persist the cache, MERGING with what's already on disk: entries
    for ops/keys not re-tuned in this process survive. (A clobbering
    save after a partial `load()` used to silently drop every entry the
    process never touched.) In-memory entries win on key conflicts."""
    merged: dict = {}
    try:
        with open(path) as f:
            on_disk = json.load(f)
        if isinstance(on_disk, dict):
            for op_name, table in on_disk.items():
                if isinstance(table, dict):
                    merged[op_name] = dict(table)
    except (OSError, ValueError):
        pass
    for op_name, table in _CACHE.items():
        merged.setdefault(op_name, {}).update(table)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True)


def load(path: str):
    """Merge a cache file into the in-memory cache. Deep-merge per op:
    a file entry for an op must not discard shape keys already tuned in
    this process (a shallow update would wholesale-replace the op's
    inner dict)."""
    with open(path) as f:
        for op_name, entries in json.load(f).items():
            _CACHE.setdefault(op_name, {}).update(entries)


def time_callable(fn, args, warmup=1, iters=5):
    """Median wall-time of ``fn(*args)`` in seconds, fenced with
    ``jax.block_until_ready`` on the outputs."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def tune(op_name: str, key, candidates, time_candidate, budget_s=None,
         verbose=False, verify_candidate=None):
    """Pick the fastest config from ``candidates`` by measurement.

    ``time_candidate(config) -> seconds`` (raise to disqualify — e.g. the
    config fails to compile or OOMs VMEM). The winner is recorded in the
    cache and returned; a prior cached winner short-circuits. ``budget_s``
    bounds total tuning time: remaining candidates are skipped once spent
    (the best seen so far still wins).

    ``verify_candidate(config) -> list of problems`` (empty/None = ok)
    runs the Level-3 kernel verifier BEFORE any compile: a refuted
    candidate is rejected at trace time instead of burning tuning budget
    on a Mosaic compile error (or worse, a kernel that compiles but
    reads out of bounds)."""
    cached = lookup(op_name, key)
    if cached is not None:
        return cached
    if not enabled():
        return None
    best, best_t = None, float("inf")
    t_start = time.perf_counter()
    for cand in candidates:
        if budget_s is not None and time.perf_counter() - t_start > budget_s:
            break
        if verify_candidate is not None:
            try:
                problems = verify_candidate(cand)
            except Exception as e:  # verifier itself failed: don't block
                problems = None
                if verbose:
                    sys.stderr.write(f"autotune[{op_name}] {cand}: "
                                     f"verifier error ({e})\n")
            if problems:
                if verbose:
                    sys.stderr.write(f"autotune[{op_name}] {cand}: refuted "
                                     f"by kernel verifier ({problems[0]})\n")
                continue
        try:
            t = time_candidate(cand)
        except Exception as e:  # disqualified: compile error / OOM
            if verbose:
                sys.stderr.write(f"autotune[{op_name}] {cand}: failed ({e})\n")
            continue
        if verbose:
            sys.stderr.write(f"autotune[{op_name}] {cand}: {t * 1e3:.3f} ms\n")
        if t < best_t:
            best, best_t = cand, t
    if best is not None:
        record(op_name, key, best)
    return best
