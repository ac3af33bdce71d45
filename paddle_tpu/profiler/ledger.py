"""Provenance-stamped perf ledger: schema, registry, gate and report.

The repo's own tools append to one JSONL file (``runs/perf_ledger.jsonl`` by
default, untracked) where every artifact they emit — ``bench.py`` lines,
``bench_serve.py`` lines, ``fleet_sim`` reports and ``pod_report`` verdicts
— is normalized into a single schema-versioned row. (``PERF_LEDGER.jsonl``
at the repo root is the driver's record of every PR; nothing here writes
it.)

    {"schema": "paddle_tpu.perf_ledger.v1",
     "round": 6, "ts": null, "source": "bench.py --multichip",
     "kind": "measured",              # measured | proxy | error
     "label": "",                     # series separator within a source
     "metrics": {"multichip_step_ms": 144.84, ...},
     "provenance": {"git_sha": ..., "jax_version": ..., "device": ...,
                    "real_device": false, "flags": {...}, ...},
     "detail": {...}}                 # source-specific raw payload

Two properties make the ledger usable as a CI gate rather than a log:

* **Direction-aware metric registry.**  Every metric name that may appear
  in ``metrics`` is declared in :data:`METRICS` with a direction
  (``higher``/``lower`` is better) and whether it is a *proxy* (chip-free,
  derived from a model) or *measured* (came from a real run).  Unknown
  metric names are schema errors — the gate can therefore always tell
  whether a delta is a regression.

* **Provenance.**  Rows record the git sha, jax/jaxlib versions, device
  kind and whether it was a real accelerator or a CPU smoke run, a
  snapshot of ``FLAGS_tpu_*`` flags and the autotune ``context_key``.  The
  staleness verdict in :func:`check` keys off ``real_device`` — a CPU
  smoke number does not refresh the "when did we last measure on silicon"
  clock.

This module is **stdlib-only** and never imports jax or the rest of
``paddle_tpu`` at module scope, so ``tools/perf_ledger.py`` can load it as
a standalone file on machines with no accelerator stack installed.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

SCHEMA = "paddle_tpu.perf_ledger.v1"

KINDS = ("measured", "proxy", "error")


class LedgerSchemaError(ValueError):
    """A ledger row (or file) that does not conform to the v1 schema."""


@dataclass(frozen=True)
class MetricSpec:
    """Declaration of one ledger metric.

    direction: "higher" or "lower" — which way is better.
    proxy: True when the value is chip-free (model-derived), False when it
        can only come from actually running the workload.
    """

    direction: str
    unit: str
    proxy: bool
    help: str

    @property
    def higher_is_better(self) -> bool:
        return self.direction == "higher"


#: Every metric a ledger row may carry.  The gate refuses unknown names so
#: that a typo'd metric can never silently dodge regression checks.
METRICS: Dict[str, MetricSpec] = {
    # --- measured: training bench (bench.py) ---
    "mfu_percent": MetricSpec("higher", "percent_mfu", False,
                              "model FLOPs utilisation of the train step"),
    "tokens_per_sec_per_chip": MetricSpec("higher", "tokens/s/chip", False,
                                          "training throughput per chip"),
    "step_ms": MetricSpec("lower", "ms", False, "train step wall time"),
    # --- measured: multichip bench (bench.py --multichip) ---
    "multichip_step_ms": MetricSpec("lower", "ms", False,
                                    "overlap-schedule multichip step time"),
    "multichip_vs_lockstep": MetricSpec("higher", "ratio", False,
                                        "lockstep_ms / overlap_ms speedup"),
    # --- measured: serving bench (bench_serve.py) ---
    "serve_tokens_per_sec_chip": MetricSpec("higher", "tokens/s/chip", False,
                                            "serving decode throughput"),
    "serve_ttft_p95_ms": MetricSpec("lower", "ms", False,
                                    "p95 time-to-first-token"),
    "serve_latency_p95_ms": MetricSpec("lower", "ms", False,
                                       "p95 end-to-end request latency"),
    # --- proxies: chip-free, every PR gets a trajectory point ---
    "predicted_step_ms": MetricSpec("lower", "ms", True,
                                    "pod_report alpha-beta model step time"),
    "predicted_mfu": MetricSpec("higher", "percent_mfu", True,
                                "pod_report alpha-beta model MFU"),
    "plan_capacity": MetricSpec("higher", "requests", True,
                                "pod_report max concurrent requests"),
    "overlap_fraction": MetricSpec("higher", "fraction", True,
                                   "fraction of transfers overlapped"),
    "prefix_hit_rate": MetricSpec("higher", "fraction", True,
                                  "serving prefix-cache hit rate"),
    "kv_capacity_ratio_vs_bf16": MetricSpec("higher", "ratio", True,
                                            "KV capacity vs bf16 baseline"),
    "fleet_min_replicas": MetricSpec("lower", "replicas", True,
                                     "fleet_sim recommended replica count"),
    "multichip_parity": MetricSpec("higher", "bool", True,
                                   "multichip dryrun parity pass (1/0)"),
}


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

def _dist_version(name: str) -> Optional[str]:
    try:
        from importlib import metadata as _md
        return _md.version(name)
    except Exception:
        return None


def _git_sha(repo: Optional[str] = None) -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=repo or os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
            capture_output=True, text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        pass  # git missing / timed out — provenance degrades to null
    return None


_REAL_DEVICES = ("tpu", "gpu", "cuda", "rocm")


def is_real_device(device: Optional[str]) -> bool:
    """True when ``device`` names real silicon (not a CPU smoke run)."""
    if not device:
        return False
    d = str(device).lower()
    return any(tag in d for tag in _REAL_DEVICES)


def _flags_snapshot() -> Dict[str, Any]:
    """Snapshot FLAGS_tpu_* values *if* paddle_tpu.core.flags is loaded.

    Reads from sys.modules only — never imports, so ledger stays jax-free.
    """
    mod = sys.modules.get("paddle_tpu.core.flags")
    if mod is None:
        return {}
    reg = getattr(mod, "_REGISTRY", None)
    if not isinstance(reg, dict):
        return {}
    out = {}
    for k, v in sorted(reg.items()):
        if isinstance(v, (bool, int, float, str)) or v is None:
            out[k] = v
    return out


def _context_key() -> Optional[str]:
    mod = sys.modules.get("paddle_tpu.runtime.autotune")
    if mod is None:
        return None
    fn = getattr(mod, "context_key", None)
    if fn is None:
        return None
    try:
        return fn("bf16")
    except Exception:
        return None


def collect_provenance(device: Optional[str] = None,
                       cmd: Optional[str] = None,
                       note: Optional[str] = None,
                       repo: Optional[str] = None) -> Dict[str, Any]:
    """Build a provenance block for a freshly measured row."""
    return {
        "git_sha": _git_sha(repo),
        "jax_version": _dist_version("jax"),
        "jaxlib_version": _dist_version("jaxlib"),
        "device": device,
        "real_device": is_real_device(device),
        "flags": _flags_snapshot(),
        "context_key": _context_key(),
        "cmd": cmd,
        "note": note,
    }


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

def new_record(source: str,
               metrics: Dict[str, float],
               *,
               kind: str = "measured",
               label: str = "",
               round: Optional[int] = None,
               ts: Optional[float] = None,
               provenance: Optional[Dict[str, Any]] = None,
               detail: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Build and validate one ledger row."""
    rec = {
        "schema": SCHEMA,
        "round": round,
        "ts": ts,
        "source": source,
        "kind": kind,
        "label": label,
        "metrics": {k: (None if v is None else float(v))
                    for k, v in metrics.items()},
        "provenance": provenance or {},
        "detail": detail or {},
    }
    validate(rec)
    return rec


def validate(rec: Any) -> Dict[str, Any]:
    """Raise :class:`LedgerSchemaError` unless ``rec`` is a valid v1 row."""
    if not isinstance(rec, dict):
        raise LedgerSchemaError(f"row is not an object: {type(rec).__name__}")
    if rec.get("schema") != SCHEMA:
        raise LedgerSchemaError(
            f"unknown schema {rec.get('schema')!r} (want {SCHEMA!r})")
    if rec.get("kind") not in KINDS:
        raise LedgerSchemaError(f"unknown kind {rec.get('kind')!r}")
    if not isinstance(rec.get("source"), str) or not rec["source"]:
        raise LedgerSchemaError("source must be a non-empty string")
    if not isinstance(rec.get("label", ""), str):
        raise LedgerSchemaError("label must be a string")
    rnd = rec.get("round")
    if rnd is not None and not isinstance(rnd, int):
        raise LedgerSchemaError(f"round must be int or null, got {rnd!r}")
    m = rec.get("metrics")
    if not isinstance(m, dict):
        raise LedgerSchemaError("metrics must be an object")
    if rec["kind"] != "error" and not m:
        raise LedgerSchemaError(f"{rec['kind']} row has no metrics")
    for name, val in m.items():
        spec = METRICS.get(name)
        if spec is None:
            raise LedgerSchemaError(f"unknown metric {name!r}")
        if val is not None and not isinstance(val, (int, float)):
            raise LedgerSchemaError(f"metric {name!r} is not numeric: {val!r}")
        if rec["kind"] == "proxy" and not spec.proxy:
            raise LedgerSchemaError(
                f"metric {name!r} is measured-only but row kind is proxy")
    prov = rec.get("provenance")
    if prov is not None and not isinstance(prov, dict):
        raise LedgerSchemaError("provenance must be an object or null")
    return rec


def dumps(rec: Dict[str, Any]) -> str:
    return json.dumps(rec, sort_keys=True, default=_json_default)


def _json_default(o: Any) -> Any:
    # numpy scalars sneak into bench dicts; coerce without importing numpy.
    for attr in ("item",):
        fn = getattr(o, attr, None)
        if callable(fn):
            try:
                return fn()
            except Exception:  # tpu-lint: disable=except-pass — arbitrary .item()
                pass
    return str(o)


def append(path: str, rec: Dict[str, Any]) -> None:
    """Validate and append one row to the JSONL ledger at ``path``."""
    validate(rec)
    d = os.path.dirname(os.path.abspath(path))
    if d and not os.path.isdir(d):
        os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(dumps(rec) + "\n")


def load(path: str) -> List[Dict[str, Any]]:
    """Load and validate every row of a JSONL ledger."""
    records = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise LedgerSchemaError(f"{path}:{i}: invalid JSON: {e}")
            try:
                validate(rec)
            except LedgerSchemaError as e:
                raise LedgerSchemaError(f"{path}:{i}: {e}")
            records.append(rec)
    return records


# ---------------------------------------------------------------------------
# normalizers: bench result dicts -> ledger rows
# ---------------------------------------------------------------------------

def from_bench_result(result: Dict[str, Any],
                      *,
                      round: Optional[int] = None,
                      ts: Optional[float] = None,
                      cmd: Optional[str] = None,
                      provenance: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """Normalize a ``bench.py`` result line (single- or multi-chip)."""
    detail = result.get("detail") or {}
    metric = result.get("metric", "")
    if result.get("error"):
        prov = dict(provenance or {})
        prov.setdefault("cmd", cmd)
        prov.setdefault("note", result["error"])
        return new_record("bench.py", {}, kind="error", round=round, ts=ts,
                          provenance=prov,
                          detail={k: v for k, v in result.items()
                                  if k != "detail"})
    if metric == "llama_train_multichip_step":
        metrics = {"multichip_step_ms": result.get("value")}
        if result.get("vs_baseline"):
            metrics["multichip_vs_lockstep"] = result["vs_baseline"]
        ov = (detail.get("overlap") or {}).get("overlap_fraction")
        if ov is not None:
            metrics["overlap_fraction"] = ov
        device = detail.get("device")
        prov = dict(provenance or collect_provenance(device=device, cmd=cmd))
        prov.setdefault("device", device)
        prov.setdefault("real_device", is_real_device(device))
        return new_record("bench.py --multichip", metrics, kind="measured",
                          round=round, ts=ts, provenance=prov, detail=detail)
    # single-chip train MFU line
    metrics = {"mfu_percent": result.get("value")}
    if detail.get("tokens_per_sec_per_chip") is not None:
        metrics["tokens_per_sec_per_chip"] = detail["tokens_per_sec_per_chip"]
    if detail.get("step_ms") is not None:
        metrics["step_ms"] = detail["step_ms"]
    device = detail.get("device")
    prov = dict(provenance or collect_provenance(device=device, cmd=cmd))
    prov.setdefault("device", device)
    prov.setdefault("real_device", is_real_device(device))
    return new_record("bench.py", metrics, kind="measured", round=round,
                      ts=ts, provenance=prov, detail=detail)


def from_bench_serve_result(result: Dict[str, Any],
                            *,
                            round: Optional[int] = None,
                            ts: Optional[float] = None,
                            cmd: Optional[str] = None,
                            provenance: Optional[Dict[str, Any]] = None
                            ) -> Dict[str, Any]:
    """Normalize a ``bench_serve.py`` result line."""
    if result.get("error"):
        prov = dict(provenance or {})
        prov.setdefault("cmd", cmd)
        prov.setdefault("note", result["error"])
        return new_record("bench_serve.py", {}, kind="error", round=round,
                          ts=ts, provenance=prov, detail=result)
    metrics = {"serve_tokens_per_sec_chip": result.get("value")}
    if result.get("ttft_p95_ms") is not None:
        metrics["serve_ttft_p95_ms"] = result["ttft_p95_ms"]
    if result.get("latency_p95_ms") is not None:
        metrics["serve_latency_p95_ms"] = result["latency_p95_ms"]
    hit = (result.get("reuse") or {}).get("prefix_hit_rate")
    if hit is not None:
        metrics["prefix_hit_rate"] = hit
    kv_dtype = (result.get("kv") or {}).get("dtype", "bf16")
    label = ":".join([str(result.get("preset", "")),
                      str(result.get("workload", "")),
                      f"kv={kv_dtype}"])
    device = result.get("device")
    prov = dict(provenance or collect_provenance(device=device, cmd=cmd))
    prov.setdefault("device", device)
    prov.setdefault("real_device", is_real_device(device))
    return new_record("bench_serve.py", metrics, kind="measured",
                      label=label, round=round, ts=ts, provenance=prov,
                      detail={k: result.get(k) for k in
                              ("fleet", "resilience", "kv", "reuse",
                               "tokens", "requests", "steps")
                              if result.get(k) is not None})


def from_pod_report(report: Dict[str, Any],
                    *,
                    round: Optional[int] = None,
                    ts: Optional[float] = None,
                    cmd: Optional[str] = None,
                    provenance: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """Normalize a ``tools/pod_report.py`` verdict into a proxy row."""
    metrics: Dict[str, float] = {}
    pred = report.get("predicted") or {}
    if pred.get("step_time_ms") is not None:
        metrics["predicted_step_ms"] = pred["step_time_ms"]
    if pred.get("mfu") is not None:
        metrics["predicted_mfu"] = pred["mfu"]
    serving = report.get("serving") or {}
    if serving.get("max_concurrent_requests") is not None:
        metrics["plan_capacity"] = serving["max_concurrent_requests"]
    if serving.get("capacity_ratio_vs_bf16") is not None:
        metrics["kv_capacity_ratio_vs_bf16"] = serving[
            "capacity_ratio_vs_bf16"]
    fleet = serving.get("fleet") or {}
    if fleet.get("min_replicas") is not None:
        metrics["fleet_min_replicas"] = fleet["min_replicas"]
    if not metrics:
        raise LedgerSchemaError("pod_report payload has no proxy metrics")
    label = str(report.get("preset") or report.get("mode") or "")
    prov = dict(provenance or {"cmd": cmd, "git_sha": _git_sha()})
    return new_record("pod_report", metrics, kind="proxy", label=label,
                      round=round, ts=ts, provenance=prov,
                      detail={"mesh": report.get("mesh"),
                              "mode": report.get("mode")})


def from_fleet_report(report: Dict[str, Any],
                      *,
                      round: Optional[int] = None,
                      ts: Optional[float] = None,
                      provenance: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """Normalize a ``fleet_sim`` recommendation into a proxy row."""
    rec = report.get("recommended") or {}
    if rec.get("replicas") is None:
        raise LedgerSchemaError("fleet report has no recommended.replicas")
    metrics = {"fleet_min_replicas": float(rec["replicas"])}
    label = str(report.get("workload", ""))
    return new_record("fleet_sim", metrics, kind="proxy", label=label,
                      round=round, ts=ts, provenance=dict(provenance or {}),
                      detail={"recommended": rec,
                              "calibrated": report.get("calibrated")})


# ---------------------------------------------------------------------------
# gate: regression + staleness
# ---------------------------------------------------------------------------

def _series_key(rec: Dict[str, Any], metric: str) -> Tuple[str, str, str]:
    return (metric, rec["source"], rec.get("label", ""))


def check(records: List[Dict[str, Any]],
          *,
          tol: float = 0.05,
          stale_after: int = 3,
          proxies_only: bool = False) -> Dict[str, Any]:
    """Tolerance-banded regression gate + staleness verdict.

    For every (metric, source, label) series with >= 2 points, compare the
    newest value against the previous one: a higher-is-better metric
    regresses when ``new < prev * (1 - tol)``, a lower-is-better one when
    ``new > prev * (1 + tol)``.  Improvements and in-band noise pass.

    Staleness: when the newest *measured* row from a *real device* is
    ``stale_after`` or more rounds older than the newest round in the
    ledger, the ledger is stale — the number everyone quotes no longer
    describes HEAD.  ``proxies_only=True`` restricts the gate to proxy
    metrics and skips the staleness verdict (proxies exist precisely so
    chip-free PRs still get a gated trajectory point).
    """
    series: Dict[Tuple[str, str, str], List[Tuple[int, float]]] = {}
    order = 0
    max_round = None
    newest_real_measured = None
    for rec in records:
        order += 1
        rnd = rec.get("round")
        if rnd is not None:
            max_round = rnd if max_round is None else max(max_round, rnd)
            if (rec["kind"] == "measured"
                    and (rec.get("provenance") or {}).get("real_device")):
                if newest_real_measured is None or rnd > newest_real_measured:
                    newest_real_measured = rnd
        for name, val in rec.get("metrics", {}).items():
            if val is None:
                continue
            spec = METRICS[name]
            if proxies_only and not spec.proxy:
                continue
            series.setdefault(_series_key(rec, name), []).append(
                (order, float(val)))

    regressions = []
    comparisons = 0
    for (metric, source, label), pts in sorted(series.items()):
        if len(pts) < 2:
            continue
        pts.sort(key=lambda p: p[0])
        prev, new = pts[-2][1], pts[-1][1]
        spec = METRICS[metric]
        comparisons += 1
        if spec.higher_is_better:
            bad = new < prev * (1.0 - tol)
        else:
            bad = new > prev * (1.0 + tol)
        if bad:
            regressions.append({
                "metric": metric, "source": source, "label": label,
                "previous": prev, "latest": new,
                "direction": spec.direction, "tol": tol,
                "delta_pct": round(100.0 * (new - prev) / prev, 3)
                if prev else None,
            })

    stale = None
    if not proxies_only and max_round is not None:
        if newest_real_measured is None:
            stale = {"newest_measured_round": None, "max_round": max_round,
                     "age_rounds": None,
                     "reason": "no real-device measurement in ledger"}
        else:
            age = max_round - newest_real_measured
            if age >= stale_after:
                stale = {"newest_measured_round": newest_real_measured,
                         "max_round": max_round, "age_rounds": age,
                         "reason": f"newest real-device measurement is "
                                   f"{age} rounds old (limit "
                                   f"{stale_after})"}

    ok = not regressions and stale is None
    return {"ok": ok, "regressions": regressions, "stale": stale,
            "comparisons": comparisons, "series": len(series),
            "rows": len(records), "tol": tol, "stale_after": stale_after,
            "proxies_only": proxies_only}


# ---------------------------------------------------------------------------
# report: trajectory table
# ---------------------------------------------------------------------------

def report(records: List[Dict[str, Any]], *, fmt: str = "markdown") -> str:
    """Render the per-series trajectory with deltas.

    ``fmt``: "markdown" for a table, "json" for machine consumption.
    """
    series: Dict[Tuple[str, str, str], List[Dict[str, Any]]] = {}
    for i, rec in enumerate(records):
        for name, val in rec.get("metrics", {}).items():
            if val is None:
                continue
            series.setdefault(_series_key(rec, name), []).append({
                "round": rec.get("round"), "value": float(val),
                "kind": rec["kind"], "order": i,
                "device": (rec.get("provenance") or {}).get("device"),
            })
    out = []
    for (metric, source, label), pts in sorted(series.items()):
        pts.sort(key=lambda p: p["order"])
        spec = METRICS[metric]
        first, last = pts[0]["value"], pts[-1]["value"]
        delta = None
        if first:
            delta = 100.0 * (last - first) / first
        out.append({
            "metric": metric, "source": source, "label": label,
            "direction": spec.direction, "unit": spec.unit,
            "proxy": spec.proxy, "points": len(pts),
            "trajectory": [{"round": p["round"], "value": p["value"]}
                           for p in pts],
            "latest": last, "first": first,
            "delta_pct": None if delta is None else round(delta, 3),
        })
    if fmt == "json":
        return json.dumps({"schema": SCHEMA, "rows": len(records),
                           "series": out}, indent=2, sort_keys=True)
    lines = ["| metric | source | label | dir | n | trajectory | latest | Δ% |",
             "|---|---|---|---|---|---|---|---|"]
    for s in out:
        traj = " → ".join(
            f"{p['value']:g}" + (f" (r{p['round']})" if p["round"] is not None
                                 else "")
            for p in s["trajectory"][-4:])
        arrow = "↑" if s["direction"] == "higher" else "↓"
        delta = "" if s["delta_pct"] is None else f"{s['delta_pct']:+.1f}%"
        tag = " *(proxy)*" if s["proxy"] else ""
        lines.append(f"| {s['metric']}{tag} | {s['source']} | {s['label']} "
                     f"| {arrow} | {s['points']} | {traj} "
                     f"| {s['latest']:g} {s['unit']} | {delta} |")
    return "\n".join(lines)
