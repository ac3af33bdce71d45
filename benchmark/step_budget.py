"""What the step-budget readers of PR 35 share: a serve step's device time
split by the names the program itself wrote on each operation's scope path,
so that the named parts and one remainder add up to the slice's busy time.

A device event's scope path (``spans.scope_of``) reads, for example,
``jit(serve_step_tc16)/layers/while/body/closed_call/mamba/step_layout/
jit(_take)/gather:``.  Some of its components are JAX's own and say nothing
about the model: anything with a parenthesis (``jit(..)``, ``transpose(..)``),
``while``, ``body``, ``cond`` and its ``branch_<n>_fun``, ``closed_call``,
``checkpoint``, an ``einsum``'s subscripts (``td,vd->tv``), and the last
component, the primitive.  What is left are the
scopes the program opened (``jax.named_scope``), outermost first:
``["layers", "mamba", "step_layout"]`` (:func:`scopes_of`).  Where XLA
fused operations of two paths it writes both, joined by ``;``: the first one
counts, so that an operation has one path and one place in the budget.

* An operation is **unnamed** when nothing is left, or ``layers`` alone: it
  lies under no scope that a model opens below ``layers`` and under none of
  its top-level ones (``embed``, ``lm_head``, ``sample``, and the
  ``step_layout`` of a ``StepLayout`` built outside the layers).  That is
  the layer loops' own work (a layer's weights sliced out of their stack,
  the carries' copies, the ``while`` itself) plus whatever a model forgot
  to name.  The rule reads the path alone: no list of a model's scopes.
* An operation's **leaf** is the innermost scope of its path, a
  ``pallas/<kernel>`` pair counting as the scope ``<kernel>``; an unnamed
  operation's leaf is ``unnamed``.  Every operation has one leaf, so the
  leaves' self times add up to the busy time (:func:`budget`).

A reader here returns ``None`` on an untraced run and on a program that has
none of its names (the parent commit), as the readers of ``spans`` do.
"""
from __future__ import annotations

import functools
import json
import re

from benchmark import harness, spans, trace_reduce

LAYOUT, PROJ = "step_layout", "ssm_proj"
MAMBA_PARTS = (PROJ, "ssm_conv", "ssm_scan", LAYOUT)
HOST_SPANS = ("serve/schedule", "serve/batch", "serve/dispatch",
              "serve/commit", "serve/fetch")
UNNAMED = "unnamed"
_JAX_OWN = re.compile(r"while|body|cond|branch_\d+_fun|closed_call|"
                      r"checkpoint|.*\(.*|.*->.*")


@functools.lru_cache(maxsize=4096)      # a slice has a few hundred paths
def scopes_of(path: str) -> tuple:
    """The scopes the program opened on the scope path ``path``, outermost
    first: its components less JAX's own and less the final primitive (of
    paths joined by ``;``, the first)."""
    return tuple(p for p in path.partition(";")[0].split("/")[:-1]
                 if p and not _JAX_OWN.fullmatch(p))


def is_unnamed(path: str) -> bool:
    return all(s == "layers" for s in scopes_of(path))


def leaf_of(path: str) -> str:
    """The innermost scope (of ``pallas/<kernel>`` that is the kernel)."""
    return UNNAMED if is_unnamed(path) else scopes_of(path)[-1]


def mamba_part_of(path: str):
    """Which of the mixer's four names an operation under ``mamba`` has
    (``(rest of mamba)`` for none); ``None`` outside ``mamba``."""
    scopes = scopes_of(path)
    if "mamba" not in scopes:
        return None
    return next((p for p in MAMBA_PARTS if p in scopes), "(rest of mamba)")


def layout_site_of(path: str):
    """The scope that encloses ``step_layout`` on an operation's path (the
    mixer that moved between the layouts; ``(top)`` for none); ``None`` for
    an operation that is not under ``step_layout``."""
    scopes = scopes_of(path)
    if LAYOUT not in scopes:
        return None
    outer = [s for s in scopes[:scopes.index(LAYOUT)] if s != "layers"]
    return outer[-1] if outer else "(top)"


def _steps(run: dict):
    """The traced slice and the engine steps it is divided by, or
    ``(None, 0)``."""
    sl, steps = spans.traced(run), run["counters"].get("trace_steps")
    if sl is None or not steps:
        return None, 0
    spans.report(sl, steps)
    return sl, steps


def _say_ms(what: str, sl, steps: int, key) -> None:
    table = sl.self_ns_by(lambda e: key(spans.scope_of(e)))
    harness.log(f"{what} (ms a step, {steps} steps): " + json.dumps(
        {k: round(v / steps / 1e6, 3) for k, v in table.items()}))


def scope_ms_per_step(run: dict, scope: str, table: str, key):
    """Self time of the device operations under the scope ``scope``, in ms
    per engine step of the slice; where there is any, also the log line
    ``bench: <table>``: the slice's time by ``key(scope path)``."""
    sl, steps = _steps(run)
    if sl is None:
        return None
    ns = sl.self_ns_where(lambda e: scope in scopes_of(spans.scope_of(e)))
    if not ns:
        return None
    _say_ms(table, sl, steps, key)
    return ns / steps / 1e6


def budget(sl, steps: int) -> dict:
    """The slice's step budget in ms a step: the device's busy time, its
    parts by leaf (they add up to it), and the medians of the host's five
    spans of a step with their sum."""
    per = 1e6 * steps
    parts = sl.self_ns_by(lambda e: leaf_of(spans.scope_of(e)))
    host = {name.partition("/")[2]: sl.median_ms(name) for name in HOST_SPANS}
    host = {k: round(v, 3) for k, v in host.items() if v is not None}
    return {"device_busy": round(sum(sl.self_ns) / per, 3),
            "device": {k: round(v / per, 3) for k, v in parts.items()},
            "host_medians": host,
            "host_sum": round(sum(host.values()), 3)}


def unnamed_ms_per_step(run: dict):
    """Self time of the unnamed device operations (see the module's
    docstring) in ms per engine step of the slice, ``None`` where the slice
    holds no device operation with a scope path at all.  Logs ``bench:
    unnamed_ops``, its five dearest instructions (each with its scope path
    and how often it ran), and ``bench: step_budget``, the whole of
    :func:`budget`."""
    sl, steps = _steps(run)
    if sl is None or not any(map(spans.scope_of, sl.ops)):
        return None
    per = 1e6 * steps
    dear = {}
    for e, t in zip(sl.ops, sl.self_ns):
        path = spans.scope_of(e)
        if is_unnamed(path):
            # by path too: the two buckets' programs number their
            # instructions alike
            row = dear.setdefault((trace_reduce.short_name(e.name), path),
                                  [0.0, 0])
            row[0] += t
            row[1] += 1
    top = sorted(dear.items(), key=lambda kv: -kv[1][0])[:5]
    harness.log("unnamed_ops (the five dearest, ms a step, runs in the "
                f"slice, scope path; {steps} steps): " + json.dumps(
                    [[name, round(ns / per, 4), n, path]
                     for (name, path), (ns, n) in top]))
    harness.log(f"step_budget (ms a step, {steps} steps): "
                + json.dumps(budget(sl, steps)))
    return sum(ns for ns, _ in dear.values()) / per
