"""Numerics observability: NaN/Inf watchdog + first-bad-op localization.

Reference analog: paddle/fluid/framework/details/nan_inf_utils (the
FLAGS_check_nan_inf per-op output scan) and paddle.amp.debugging's
check_numerics / TensorCheckerConfig. On the TPU stack the failure mode
this exists for is bf16/fp16 divergence at scale: GradScaler can tell
you *that* a step produced non-finites, this module tells you *which
primitive* did, at which file:line.

Three layers:

1. **Watchdog sites** — `check_array`/`check_tree` host-side checks and
   the site registry (`sites()`): every named check point counts hits
   and non-finite hits, with a configurable action (warn/raise/collect).
   Gated by ``FLAGS_tpu_check_nan_inf`` with the same discipline as
   ``FLAGS_tpu_metrics``: the disabled path is one dict lookup plus a
   bool check (`enabled()`), nothing else.

2. **First-bad-op localization** — `localize(fn, *args)` traces ``fn``
   to a jaxpr and re-interprets it eqn-by-eqn on the same inputs,
   reporting the first primitive whose output goes non-finite (while
   its inputs were finite), with `source_info` file:line attribution.
   Recurses into nested pjit/custom-call sub-jaxprs so "the bad op is
   inside an inner jit" still resolves to the real primitive.

3. **Tensor-stats telemetry** — `note(name, value)` keeps the last
   value of named scalar stats (grad norms, loss scale, update ratio)
   for the Profiler "Numerics" section; the instrumented call sites
   (optimizer step, ClipGradByGlobalNorm, GradScaler, hapi train_batch)
   mirror the same numbers into the metrics registry.
"""
from __future__ import annotations

import math
import threading
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core import flags as _flags

__all__ = ["enabled", "check_array", "check_tree", "localize", "watch",
           "record_site", "sites", "note", "last_stats", "collected",
           "clear_collected", "reset", "summary_lines",
           "NonFiniteError"]

# disabled-path contract (see metrics.py): one dict lookup + bool check
_FLAG_DICT = _flags._REGISTRY
_FLAG_NAME = "FLAGS_tpu_check_nan_inf"


def enabled() -> bool:
    """Whether the numerics watchdog is on (the only check hot paths pay)."""
    return bool(_FLAG_DICT.get(_FLAG_NAME, False))


class NonFiniteError(FloatingPointError):
    """Raised by a check site with action='raise'. Carries the structured
    report (``.report``) when localization ran."""

    def __init__(self, msg, report=None):
        super().__init__(msg)
        self.report = report


# ---------------------------------------------------------------------------
# site registry + last-value stats + collect buffer
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
# name -> {"hits": int, "nonfinite": int, "last": summary-dict|None}
_SITES: Dict[str, Dict[str, Any]] = {}
# name -> last recorded scalar (grad norms, loss scale, ...)
_LAST: Dict[str, float] = {}
# action='collect' findings, oldest first (bounded)
_COLLECTED: List[dict] = []
_COLLECT_CAP = 10000


def record_site(name: str, nonfinite: bool, summary: Optional[dict] = None):
    """Count a watchdog check at ``name``; remember the last non-finite
    summary so the Numerics section can show what went wrong."""
    with _LOCK:
        s = _SITES.get(name)
        if s is None:
            s = _SITES[name] = {"hits": 0, "nonfinite": 0, "last": None}
        s["hits"] += 1
        if nonfinite:
            s["nonfinite"] += 1
            if summary is not None:
                s["last"] = summary


def sites() -> Dict[str, Dict[str, Any]]:
    """Snapshot of the per-site hit counters."""
    with _LOCK:
        return {k: dict(v) for k, v in _SITES.items()}


def note(name: str, value) -> None:
    """Record the last value of a named numerics stat (cheap: one dict
    store). Callers gate on metrics/watchdog enablement themselves."""
    try:
        _LAST[name] = float(value)
    except (TypeError, ValueError):
        pass


def last_stats() -> Dict[str, float]:
    return dict(_LAST)


def collected() -> List[dict]:
    """Findings recorded by action='collect' sites, oldest first."""
    with _LOCK:
        return list(_COLLECTED)


def clear_collected():
    with _LOCK:
        _COLLECTED.clear()


def reset():
    """Drop all watchdog state (tests)."""
    with _LOCK:
        _SITES.clear()
        _LAST.clear()
        _COLLECTED.clear()


# ---------------------------------------------------------------------------
# host-side checking
# ---------------------------------------------------------------------------

def _summarize_array(arr) -> Optional[dict]:
    """Count NaN/Inf in a concrete array; None when fully finite (or not
    a floating array). Host-side only — callers must not pass tracers."""
    import numpy as np

    a = np.asarray(arr)
    if not np.issubdtype(a.dtype, np.floating):
        return None
    finite = np.isfinite(a)
    if bool(finite.all()):
        return None
    nan = int(np.isnan(a).sum())
    inf = int((~finite).sum()) - nan
    return {"nan": nan, "inf": inf, "size": int(a.size),
            "shape": list(a.shape), "dtype": str(a.dtype)}


def _dispatch(name, summary, action, report=None):
    msg = (f"numerics: non-finite values in {name!r}: "
           f"{summary['nan']} NaN, {summary['inf']} Inf out of "
           f"{summary['size']} ({summary['dtype']}{summary['shape']})")
    if report is not None:
        msg += f"; first bad op: {report.get('where', '?')}"
    if action == "raise":
        raise NonFiniteError(msg, report=report)
    if action == "collect":
        with _LOCK:
            if len(_COLLECTED) < _COLLECT_CAP:
                _COLLECTED.append({"name": name, **summary,
                                   "report": report})
        return
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def check_array(arr, name: str, action: str = "warn") -> bool:
    """Check one concrete array at the watchdog site ``name``. Returns
    True when non-finite values were found (unless action='raise', which
    raises NonFiniteError instead). No-op (dict lookup only) when the
    watchdog flag is off."""
    if not enabled():
        return False
    summary = _summarize_array(arr)
    record_site(name, summary is not None, summary)
    if summary is None:
        return False
    _dispatch(name, summary, action)
    return True


def check_tree(tree, name: str, action: str = "warn") -> bool:
    """check_array over every floating leaf of a pytree (Tensors ok)."""
    if not enabled():
        return False
    import jax

    from ..core.tensor import Tensor

    leaves = jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, Tensor))
    found = False
    for i, leaf in enumerate(leaves):
        arr = leaf._array if isinstance(leaf, Tensor) else leaf
        if not hasattr(arr, "dtype"):
            continue
        if isinstance(arr, jax.core.Tracer):
            continue
        found = check_array(arr, f"{name}[{i}]" if len(leaves) > 1
                            else name, action) or found
    return found


# ---------------------------------------------------------------------------
# first-bad-op localization
# ---------------------------------------------------------------------------

def _is_float(x) -> bool:
    import numpy as np
    dt = getattr(x, "dtype", None)
    return dt is not None and np.issubdtype(dt, np.floating)


def _first_nonfinite(vals) -> Optional[Tuple[int, dict]]:
    for i, v in enumerate(vals):
        if not _is_float(v):
            continue
        s = _summarize_array(v)
        if s is not None:
            return i, s
    return None


_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                    "body_jaxpr")


def _sub_jaxprs(eqn):
    """(ClosedJaxpr-like) sub-jaxprs a higher-order eqn carries, for
    recursion into pjit / custom_jvp / remat / cond bodies."""
    out = []
    for k in _SUBJAXPR_PARAMS:
        j = eqn.params.get(k)
        if j is not None:
            out.append(j)
    j = eqn.params.get("branches")
    if j:
        out.extend(j)
    return out


def _interpret(jaxpr, consts, args, path: str):
    """Eval ``jaxpr`` one eqn at a time; return (outvals, report|None)
    where report names the first primitive producing non-finite outputs
    from finite inputs. Evaluation continues after a finding so callers
    still get the function's outputs."""
    from jax.extend.core import Literal

    env: Dict[Any, Any] = {}

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, args):
        env[v] = a

    report = None
    for idx, eqn in enumerate(jaxpr.eqns):
        invals = [read(v) for v in eqn.invars]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        outvals = eqn.primitive.bind(*subfuns, *invals, **bind_params)
        if not eqn.primitive.multiple_results:
            outvals = [outvals]
        for var, val in zip(eqn.outvars, outvals):
            env[var] = val
        if report is not None:
            continue
        inputs_bad = _first_nonfinite(invals) is not None
        bad = _first_nonfinite(outvals)
        if bad is None or inputs_bad:
            # blame the op that *introduced* the non-finites; ops that
            # merely propagate them are downstream noise
            continue
        out_i, summary = bad
        sub = _sub_jaxprs(eqn)
        inner = None
        for sj in sub:
            # higher-order op: descend to the real primitive
            inner_jaxpr = getattr(sj, "jaxpr", sj)
            inner_consts = getattr(sj, "consts", getattr(sj, "literals", ()))
            try:
                _, inner = _interpret(inner_jaxpr, inner_consts, invals,
                                      f"{path}{eqn.primitive.name}/")
            except Exception:
                inner = None
            if inner is not None:
                break
        if inner is not None:
            report = inner
        else:
            from ..analysis.core import eqn_site
            file_name, line, where = eqn_site(eqn)
            report = {
                "primitive": eqn.primitive.name,
                "where": where,
                "file": file_name,
                "line": line,
                "eqn_index": idx,
                "path": path + eqn.primitive.name,
                "eqn": str(eqn)[:200],
                "output_index": out_i,
                **summary,
            }
    return [read(v) for v in jaxpr.outvars], report


def localize(fn: Callable, *args, **kwargs) -> Optional[dict]:
    """Find the first primitive of ``fn(*args, **kwargs)`` whose output
    goes non-finite on these inputs.

    Re-interprets the function's jaxpr eqn-by-eqn (eagerly, un-jitted) —
    slow, but only ever run on demand after a watchdog tripped. Returns
    a report dict (primitive, where, file, line, nan/inf counts) or
    None when every intermediate stays finite. Non-finite *inputs* are
    reported as ``{"primitive": "<input>"}`` since no op is to blame.

    Accepts Tensors, jax arrays, or numpy arrays; ``fn`` may be a plain
    function, a to_static StaticFunction, or a bound method.
    """
    import jax

    from ..core.tensor import Tensor

    # unwrap to_static so we trace the underlying (converted) python fn
    inner = getattr(fn, "_converted_fn", None) or fn

    def array_fn(*arrs):
        t_args, t_kwargs = _rebuild(arrs)
        out = inner(*t_args, **t_kwargs)
        return tuple(
            x._array if isinstance(x, Tensor) else x
            for x in jax.tree_util.tree_leaves(
                out, is_leaf=lambda x: isinstance(x, Tensor)))

    flat, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    import numpy as np
    arrays = []
    for leaf in flat:
        if isinstance(leaf, Tensor):
            arrays.append(leaf._array)
        elif isinstance(leaf, (jax.Array, np.ndarray)):
            arrays.append(jax.numpy.asarray(leaf))
        else:
            arrays.append(leaf)

    dyn_idx = [i for i, a in enumerate(arrays) if hasattr(a, "dtype")]

    def _rebuild(dyn_arrays):
        full = list(arrays)
        for i, a in zip(dyn_idx, dyn_arrays):
            full[i] = Tensor(a) if isinstance(flat[i], Tensor) else a
        return jax.tree_util.tree_unflatten(treedef, full)

    dyn = [arrays[i] for i in dyn_idx]
    bad_in = _first_nonfinite(dyn)
    if bad_in is not None:
        i, summary = bad_in
        return {"primitive": "<input>", "where": f"input[{bad_in[0]}]",
                "file": None, "line": None, "eqn_index": -1,
                "path": "<input>", "eqn": "", "output_index": i, **summary}

    closed = jax.make_jaxpr(array_fn)(*dyn)
    _, report = _interpret(closed.jaxpr, closed.consts, dyn, "")
    return report


def watch(fn: Callable, name: Optional[str] = None,
          action: str = "raise") -> Callable:
    """Wrap ``fn`` so its outputs are watchdog-checked after every call;
    on non-finite outputs the jaxpr is re-interpreted to localize the
    first bad op, and the action fires with the report attached. With
    the flag off the wrapper costs one dict lookup per call."""
    import functools

    site = name or getattr(fn, "__qualname__",
                           getattr(fn, "__name__", "watched"))

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        out = fn(*args, **kwargs)
        if not enabled():
            return out
        summary = _tree_summary(out)
        record_site(site, summary is not None, summary)
        if summary is not None:
            report = None
            try:
                report = localize(fn, *args, **kwargs)
            except (TypeError, ValueError, RuntimeError, KeyError,
                    AttributeError) as e:
                # localization re-interprets the jaxpr and can fail on
                # inputs the original call handled — the finding must
                # still be dispatched, just without a culprit
                import logging
                logging.getLogger(__name__).debug(
                    "numerics localization failed at %s: %s", site, e)
            _dispatch(site, summary, action, report=report)
        return out

    return wrapper


def _tree_summary(tree) -> Optional[dict]:
    """First non-finite leaf summary of a pytree of concrete outputs."""
    import jax

    from ..core.tensor import Tensor

    for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, Tensor)):
        arr = leaf._array if isinstance(leaf, Tensor) else leaf
        if not hasattr(arr, "dtype") or isinstance(arr, jax.core.Tracer):
            continue
        if not _is_float(arr):
            continue
        s = _summarize_array(arr)
        if s is not None:
            return s
    return None


# ---------------------------------------------------------------------------
# Profiler "Numerics" section
# ---------------------------------------------------------------------------

_STAT_ORDER = ("grad_global_norm", "grad_global_norm_preclip",
               "grad_global_norm_postclip", "param_global_norm",
               "weight_update_ratio", "loss_scale", "train_loss")


def summary_lines() -> List[str]:
    lines = [f"Numerics  (FLAGS_tpu_check_nan_inf="
             f"{'on' if enabled() else 'off'})"]
    with _LOCK:
        site_items = sorted(_SITES.items())
        stats = dict(_LAST)
        n_collected = len(_COLLECTED)
    # quantization-error gauges (quant_err_* from quantize_params /
    # convert_to_mixed_precision) group under their own sub-block so a
    # bad scale is localized like a NaN
    quant = [k for k in sorted(stats) if k.startswith("quant_err_")]
    shown = [k for k in _STAT_ORDER if k in stats]
    shown += [k for k in sorted(stats)
              if k not in _STAT_ORDER and k not in quant]
    for k in shown:
        v = stats[k]
        mark = "  <-- NON-FINITE" if not math.isfinite(v) else ""
        lines.append(f"  {k:<28} {v:.6g}{mark}")
    if quant:
        lines.append("  Quantization")
        for k in quant:
            v = stats[k]
            mark = "  <-- NON-FINITE" if not math.isfinite(v) else ""
            lines.append(f"    {k:<28} {v:.6g}{mark}")
    if site_items:
        lines.append(f"  check sites: {len(site_items)}")
        for nm, s in site_items[:10]:
            mark = "  <-- NON-FINITE" if s["nonfinite"] else ""
            lines.append(f"    {nm[:44]:<44} {s['hits']:>7} hits "
                         f"{s['nonfinite']:>5} bad{mark}")
    if n_collected:
        lines.append(f"  collected findings: {n_collected} "
                     f"(numerics.collected())")
    return lines
