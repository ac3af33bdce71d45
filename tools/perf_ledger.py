#!/usr/bin/env python3
"""perf_ledger — append, gate and report the repo's own perf ledger.

Stdlib-only CLI over ``paddle_tpu/profiler/ledger.py``; loads that module
as a standalone file so it works on machines with no jax installed (same
convention as ``tpu_lint`` / ``trace_report``).

Subcommands:

  append  ARTIFACT.json [--ledger PATH] [--round N]
      Sniff an artifact (bench.py line, bench_serve.py line, pod_report
      verdict, fleet_sim report) and append its normalized row.

  check   [--ledger PATH] [--tol F] [--stale-after N] [--proxies-only]
      Regression + staleness gate over the ledger trajectory.

  report  [--ledger PATH] [--format markdown|json]
      Per-series trajectory table with deltas.

The default ledger is ``runs/perf_ledger.jsonl`` (untracked).
``PERF_LEDGER.jsonl`` at the repo root belongs to the driver; this tool
never writes it.

Exit codes: 0 ok · 1 regression or stale ledger · 2 schema/usage error.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEFAULT_LEDGER = os.path.join(_REPO, "runs", "perf_ledger.jsonl")


def _load_ledger_mod():
    path = os.path.join(_REPO, "paddle_tpu", "profiler", "ledger.py")
    spec = importlib.util.spec_from_file_location("perf_ledger_core", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolves __module__ here
    spec.loader.exec_module(mod)
    return mod


def _sniff_rows(L, payload, path, rnd):
    """Route one artifact JSON to the right normalizer."""
    if isinstance(payload, dict):
        if "recommended" in payload:
            return [L.from_fleet_report(payload, round=rnd)]
        if "predicted" in payload or payload.get("mode") == "serving":
            return [L.from_pod_report(payload, round=rnd)]
        metric = payload.get("metric", "")
        if metric.startswith("serve_"):
            return [L.from_bench_serve_result(payload, round=rnd)]
        if metric.startswith("llama_train"):
            return [L.from_bench_result(payload, round=rnd)]
    raise L.LedgerSchemaError(f"cannot determine artifact type of {path}")


def cmd_append(L, args) -> int:
    with open(args.artifact) as f:
        payload = json.load(f)
    rows = _sniff_rows(L, payload, args.artifact, args.round)
    for row in rows:
        L.append(args.ledger, row)
    print(f"perf_ledger: appended {len(rows)} row(s) to {args.ledger}")
    return 0


def cmd_check(L, args) -> int:
    records = L.load(args.ledger)
    verdict = L.check(records, tol=args.tol, stale_after=args.stale_after,
                      proxies_only=args.proxies_only)
    print(json.dumps(verdict, indent=2, sort_keys=True))
    return 0 if verdict["ok"] else 1


def cmd_report(L, args) -> int:
    records = L.load(args.ledger)
    print(L.report(records, fmt=args.format))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perf_ledger",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", default=_DEFAULT_LEDGER,
                    help="ledger JSONL path (default: "
                         "runs/perf_ledger.jsonl)")
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("append", help="normalize + append one artifact")
    p.add_argument("artifact")
    p.add_argument("--round", type=int, default=None)
    p.set_defaults(fn=cmd_append)

    p = sub.add_parser("check", help="regression + staleness gate")
    p.add_argument("--tol", type=float, default=0.05)
    p.add_argument("--stale-after", type=int, default=3)
    p.add_argument("--proxies-only", action="store_true",
                   help="gate only chip-free proxy metrics; skip staleness")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("report", help="trajectory table")
    p.add_argument("--format", choices=("markdown", "json"),
                   default="markdown")
    p.set_defaults(fn=cmd_report)

    args = ap.parse_args(argv)
    L = _load_ledger_mod()
    try:
        return args.fn(L, args)
    except L.LedgerSchemaError as e:
        print(f"perf_ledger: schema error: {e}", file=sys.stderr)
        return 2
    except FileNotFoundError as e:
        print(f"perf_ledger: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
