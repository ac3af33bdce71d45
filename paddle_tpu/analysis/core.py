"""Static-analysis core: findings, pragmas, the runtime registry, and
the lint baseline.

Reference analog: paddle/fluid/framework's ProgramDesc validation and IR
passes — the reference catches malformed static graphs *before* they
run; this package is the jax_graft equivalent for the hazards that have
actually bitten this repo (hidden host syncs, retraces, silent dtype
promotion, baked-in weights, collective divergence).

Two rule families share this core:

* ``jaxpr_checks`` walks a traced function's jaxpr (no execution) —
  see :func:`walk_eqns` for the shared recursive eqn iterator.
* ``ast_checks`` walks Python source — framework or user code — with
  the same :class:`Finding` shape, so the CLI, the baseline, and the
  Profiler "Lint" section present one stream.

Gating contract (same as ``FLAGS_tpu_metrics``): :func:`enabled` is one
dict lookup plus a bool check; with ``FLAGS_tpu_lint`` off and no
``to_static(..., lint=True)``, no per-call work happens at all — the
trace-time hook sits inside the new-signature branch, which steady-state
calls never enter.

This module is import-safe WITHOUT the paddle_tpu package (stdlib only):
``tools/tpu_lint.py`` loads ``analysis`` standalone so the CLI never
pays the jax import.
"""
from __future__ import annotations

import json
import os
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

try:  # inside the paddle_tpu package: share the real flag registry
    from ..core import flags as _flags
    _FLAG_DICT = _flags._REGISTRY
except ImportError:  # standalone load (tools/tpu_lint.py) — no flags, no jax
    _FLAG_DICT = {}

_FLAG_NAME = "FLAGS_tpu_lint"

ERROR = "error"
WARNING = "warning"

__all__ = ["Finding", "ERROR", "WARNING", "enabled", "record", "findings",
           "reset", "summary_lines", "walk_eqns", "eqn_site",
           "pragma_suppressed", "filter_pragmas", "filter_file_pragmas",
           "baseline_entries", "write_baseline", "load_baseline",
           "diff_baseline"]


def enabled() -> bool:
    """Whether trace-time lint is on (the only check hot paths pay)."""
    return bool(_FLAG_DICT.get(_FLAG_NAME, False))


@dataclass
class Finding:
    """One lint finding, from either rule family."""

    rule: str
    severity: str
    message: str
    file: Optional[str] = None
    line: Optional[int] = None
    function: Optional[str] = None      # traced function (jaxpr findings)
    source: str = "ast"                 # "ast" | "jaxpr"
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def where(self) -> str:
        return f"{self.file or '<unknown>'}:{self.line or 0}"

    def to_dict(self) -> dict:
        d = {"rule": self.rule, "severity": self.severity,
             "message": self.message, "file": self.file, "line": self.line,
             "source": self.source}
        if self.function:
            d["function"] = self.function
        if self.extra:
            d["extra"] = self.extra
        return d


# ---------------------------------------------------------------------------
# pragma suppression:  # tpu-lint: disable=<rule>[,<rule>...] | disable=all
# on the flagged line or the line directly above it
# ---------------------------------------------------------------------------

_PRAGMA_RE = re.compile(r"#\s*tpu-lint:\s*disable=([A-Za-z0-9_,\- ]+)")


def _pragma_rules(line_text: str) -> Optional[set]:
    m = _PRAGMA_RE.search(line_text)
    if not m:
        return None
    return {r.strip() for r in m.group(1).split(",") if r.strip()}


def pragma_suppressed(finding: Finding, lines: List[str]) -> bool:
    """Whether a ``# tpu-lint: disable=`` pragma on the finding's line
    (or the line above) covers this rule."""
    if finding.line is None:
        return False
    for ln in (finding.line, finding.line - 1):
        if 1 <= ln <= len(lines):
            rules = _pragma_rules(lines[ln - 1])
            if rules and ("all" in rules or finding.rule in rules):
                return True
    return False


def filter_pragmas(findings: Iterable[Finding],
                   lines: List[str]) -> List[Finding]:
    return [f for f in findings if not pragma_suppressed(f, lines)]


_FILE_LINES_LOCK = threading.Lock()
_FILE_LINES: Dict[str, List[str]] = {}
_FILE_LINES_CAP = 256


def _lines_of(path: str) -> List[str]:
    with _FILE_LINES_LOCK:
        cached = _FILE_LINES.get(path)
    if cached is not None:
        return cached
    try:
        with open(path, "r", errors="replace") as f:
            lines = f.read().splitlines()
    except OSError:
        lines = []
    with _FILE_LINES_LOCK:
        if len(_FILE_LINES) >= _FILE_LINES_CAP:
            _FILE_LINES.clear()
        _FILE_LINES[path] = lines
    return lines


def filter_file_pragmas(findings: Iterable[Finding]) -> List[Finding]:
    """Pragma-filter findings that carry a real file path (jaxpr findings
    attribute into user source; a pragma there must be honored too)."""
    out = []
    for f in findings:
        if f.file and f.line and os.path.isfile(f.file) \
                and pragma_suppressed(f, _lines_of(f.file)):
            continue
        out.append(f)
    return out


# ---------------------------------------------------------------------------
# runtime findings registry (trace-time jaxpr findings land here; the
# Profiler "Lint" section and lint_findings_total counters read it)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_FINDINGS: List[Finding] = []
_SEEN: set = set()
_FINDINGS_CAP = 10000


def record(new_findings: Iterable[Finding]) -> List[Finding]:
    """Deduplicate (rule, file, line, function) and append to the session
    registry; mirrors each *new* finding into the metrics registry as a
    ``lint_findings_total{rule=...}`` counter (no-op with metrics off).
    Returns the findings that were actually new."""
    added = []
    with _LOCK:
        for f in new_findings:
            key = (f.rule, f.file, f.line, f.function)
            if key in _SEEN or len(_FINDINGS) >= _FINDINGS_CAP:
                continue
            _SEEN.add(key)
            _FINDINGS.append(f)
            added.append(f)
    for f in added:
        _mirror_metric(f)
    return added


def _mirror_metric(f: Finding) -> None:
    try:
        from ..profiler import metrics as _metrics
    except ImportError:  # standalone load — no metrics registry
        return
    _metrics.counter(
        "lint_findings_total",
        "Static-analysis findings recorded at trace time, by rule.",
        rule=f.rule).inc()


def findings() -> List[Finding]:
    with _LOCK:
        return list(_FINDINGS)


def reset() -> None:
    """Drop all recorded findings (tests)."""
    with _LOCK:
        _FINDINGS.clear()
        _SEEN.clear()


def summary_lines() -> List[str]:
    """The Profiler "Lint" section."""
    lines = [f"Lint  (FLAGS_tpu_lint={'on' if enabled() else 'off'})"]
    with _LOCK:
        fs = list(_FINDINGS)
    if not fs:
        lines.append("  no findings recorded")
        return lines
    n_err = sum(1 for f in fs if f.severity == ERROR)
    lines.append(f"  findings: {len(fs)}  ({n_err} errors, "
                 f"{len(fs) - n_err} warnings)")
    by_rule: Dict[str, int] = {}
    for f in fs:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    for rule in sorted(by_rule):
        lines.append(f"    {rule:<32} {by_rule[rule]:>5}")
    for f in fs[:10]:
        fn = f" [{f.function}]" if f.function else ""
        lines.append(f"  {f.severity[:4].upper()} {f.rule} "
                     f"{f.where}{fn}: {f.message[:80]}")
    if len(fs) > 10:
        lines.append(f"  ... and {len(fs) - 10} more "
                     f"(paddle_tpu.analysis.findings())")
    return lines


# ---------------------------------------------------------------------------
# shared jaxpr walker (pattern from profiler/numerics._interpret, but
# abstract: no evaluation, just structure + loop context)
# ---------------------------------------------------------------------------

_SUB_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr", "body_jaxpr")
_LOOP_PRIMS = {"scan", "while"}


def sub_closed_jaxprs(eqn) -> list:
    """ClosedJaxpr-like sub-jaxprs a higher-order eqn carries (pjit /
    scan / while / cond / remat / custom_* bodies)."""
    out = []
    for k in _SUB_KEYS:
        j = eqn.params.get(k)
        if j is not None:
            out.append(j)
    branches = eqn.params.get("branches")
    if branches:
        out.extend(branches)
    return out


def walk_eqns(jaxpr, in_loop: bool = False, path: str = ""):
    """Yield ``(eqn, path, in_loop)`` for every eqn, recursing into
    nested pjit/cond/scan/while/remat sub-jaxprs. ``in_loop`` is True
    inside a scan or while body — the "this runs every iteration"
    context the host-callback rule cares about."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # accept ClosedJaxpr
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield eqn, path + name, in_loop
        child_in_loop = in_loop or name in _LOOP_PRIMS
        for sub in sub_closed_jaxprs(eqn):
            yield from walk_eqns(sub, in_loop=child_in_loop,
                                 path=f"{path}{name}/")


def eqn_site(eqn) -> Tuple[Optional[str], Optional[int], str]:
    """(file, line, "file:line (fn)") attribution of an eqn — the one
    place that reads jax's source info (profiler/numerics uses it too).
    jax exposes no public accessor; this is the 0.9.0 layout:
    ``user_frame`` takes the traceback, not the SourceInfo."""
    from jax._src import source_info_util
    where = source_info_util.summarize(eqn.source_info)
    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return None, None, where
    return fr.file_name, int(fr.start_line), where


# ---------------------------------------------------------------------------
# baseline: the checked-in backlog.  Entries are path-relative and
# sorted so --baseline-update is deterministic; comparison ratchets on
# per-(rule, path) counts, so edits that only move lines don't fail.
# ---------------------------------------------------------------------------

def _rel(path: Optional[str], root: str) -> str:
    if not path:
        return "<unknown>"
    try:
        rel = os.path.relpath(os.path.abspath(path), os.path.abspath(root))
    except ValueError:  # different drive (windows)
        rel = path
    return rel.replace(os.sep, "/")


def baseline_entries(findings: Iterable[Finding], root: str) -> List[dict]:
    entries = [{"rule": f.rule, "severity": f.severity,
                "path": _rel(f.file, root), "line": f.line or 0,
                "message": f.message}
               for f in findings]
    entries.sort(key=lambda e: (e["path"], e["line"], e["rule"]))
    return entries


def write_baseline(path: str, findings: Iterable[Finding],
                   root: str) -> dict:
    doc = {"version": 1, "tool": "tpu_lint",
           "entries": baseline_entries(findings, root)}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc


def load_baseline(path: str) -> dict:
    with open(path, "r") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "entries" not in doc:
        raise ValueError(f"{path}: not a tpu_lint baseline file")
    return doc


def diff_baseline(findings: List[Finding], baseline: dict,
                  root: str) -> Tuple[List[Finding], List[dict]]:
    """(new, fixed): ``new`` are findings beyond the baseline's
    per-(rule, path) count — matched by line first so unchanged code
    keeps its entries; ``fixed`` reports buckets that shrank (the
    baseline should be regenerated to claim the win)."""
    base_buckets: Dict[Tuple[str, str], List[int]] = {}
    for e in baseline.get("entries", []):
        base_buckets.setdefault((e["rule"], e["path"]), []).append(
            int(e.get("line", 0)))

    cur_buckets: Dict[Tuple[str, str], List[Finding]] = {}
    for f in findings:
        cur_buckets.setdefault((f.rule, _rel(f.file, root)), []).append(f)

    new: List[Finding] = []
    for key, flist in sorted(cur_buckets.items()):
        base_lines = list(base_buckets.get(key, []))
        extra_n = len(flist) - len(base_lines)
        if extra_n <= 0:
            continue
        remaining: Dict[int, int] = {}
        for ln in base_lines:
            remaining[ln] = remaining.get(ln, 0) + 1
        unmatched = []
        for f in sorted(flist, key=lambda f: f.line or 0):
            if remaining.get(f.line or 0, 0) > 0:
                remaining[f.line or 0] -= 1
            else:
                unmatched.append(f)
        new.extend(unmatched[:extra_n])

    fixed = []
    for key, base_lines in sorted(base_buckets.items()):
        n_cur = len(cur_buckets.get(key, []))
        if n_cur < len(base_lines):
            fixed.append({"rule": key[0], "path": key[1],
                          "removed": len(base_lines) - n_cur})
    return new, fixed
