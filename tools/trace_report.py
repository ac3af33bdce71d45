#!/usr/bin/env python
"""trace_report: offline reader for paddle_tpu flight-recorder sidecars.

Loads the rank-tagged JSONL sidecars the ``profiler.trace`` flight
recorder writes (``trace_rank<N>.jsonl``, schema
``paddle_tpu.trace.v1``), aligns ranks on shared barrier events, and
prints one JSON report on stdout:

* **requests** — per-request serving lifecycle (queued -> admitted ->
  prefill chunks -> first token -> decode -> terminal) with a TTFT
  breakdown whose p95 components are taken from the *same* interpolated
  sample, so ``queue_p95_s + prefill_p95_s == ttft_p95_s`` exactly.
* **steps** — train/serve step-span stats per rank (count, mean, p95).
* **pipeline** — measured overlap from the recorded 1F1B schedule:
  the serialized-transfer rule is re-implemented here verbatim
  (``consumed_tick - produced_tick < 2``) so the report needs no
  paddle_tpu import, and the numbers match
  ``distributed.overlap.transfer_stats`` bit-for-bit.
* **incidents** — ``--incidents`` folds watchdog/health incident
  sidecars (schema ``paddle_tpu.incidents.v1``) into the report.

Usage:
    python tools/trace_report.py out_dir/                 # all sidecars
    python tools/trace_report.py trace_rank0.jsonl --chrome trace.json
    python tools/trace_report.py out/ --incidents out/ --black-box bb.zip
    python tools/trace_report.py out/ --request 17        # one timeline

``--chrome`` writes a Chrome/Perfetto-loadable trace (spans as "X"
slices, instants as "i", plus process/thread metadata); ``--black-box``
bundles every input sidecar, incident file, and the report itself into
one zip archive for post-mortem handoff.

Exit codes (tpu_lint convention): 0 clean, 1 warnings (e.g. an admitted
request without exactly one terminal event), 2 errors (missing,
corrupt, or wrong-schema input). Stdlib-only — starts in milliseconds.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import zipfile
from typing import Any, Dict, List, Optional, Tuple

TRACE_SCHEMA = "paddle_tpu.trace.v1"
INCIDENT_SCHEMA = "paddle_tpu.incidents.v1"
TERMINAL_PHASES = ("finish", "cancelled", "failed")


# ---------------------------------------------------------------------------
# sidecar loading + rank merge
# ---------------------------------------------------------------------------

def discover_sidecars(paths: List[str], pattern: str) -> List[str]:
    """Expand files/directories into a sorted sidecar file list."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(glob.glob(os.path.join(p, pattern))))
        else:
            out.append(p)
    # de-dup, keep order
    seen = set()
    uniq = []
    for p in out:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    return uniq


def read_sidecar(path: str, schema: str) -> Tuple[dict, List[dict]]:
    """(header, records) from one JSONL sidecar; raises ValueError on
    empty/corrupt/wrong-schema input."""
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    if not lines:
        raise ValueError(f"{path}: empty sidecar")
    try:
        header = json.loads(lines[0])
        records = [json.loads(ln) for ln in lines[1:]]
    except json.JSONDecodeError as exc:
        raise ValueError(f"{path}: corrupt JSONL ({exc})") from exc
    got = header.get("schema")
    if got != schema:
        raise ValueError(f"{path}: schema {got!r}, expected {schema!r}")
    return header, records


def merge_ranks(per_rank: Dict[int, List[dict]]) -> List[dict]:
    """Align per-rank event streams on the first barrier event name all
    ranks share (clocks are per-process monotonic — only barrier-relative
    time is comparable) and interleave. Mirrors
    ``profiler.trace.merge_ranks``."""
    if not per_rank:
        return []
    ref = min(per_rank)
    barriers: Dict[int, Dict[str, float]] = {}
    for r, evs in per_rank.items():
        b: Dict[str, float] = {}
        for e in evs:
            if e.get("kind") == "barrier" and e["name"] not in b:
                b[e["name"]] = e["t"]
        barriers[r] = b
    shared = None
    for e in per_rank[ref]:
        if e.get("kind") == "barrier" and all(
                e["name"] in barriers[r] for r in per_rank):
            shared = e["name"]
            break
    merged: List[dict] = []
    for r, evs in per_rank.items():
        off = 0.0
        if shared is not None:
            off = barriers[ref][shared] - barriers[r][shared]
        for e in evs:
            e2 = dict(e)
            e2["t"] = e["t"] + off
            e2["rank"] = r
            merged.append(e2)
    merged.sort(key=lambda e: (e["t"], e["rank"], e.get("seq", 0)))
    return merged


# ---------------------------------------------------------------------------
# per-request lifecycle
# ---------------------------------------------------------------------------

def request_rows(events: List[dict]) -> Tuple[List[dict], List[str]]:
    """One row per request id seen in kind=="request" events, plus
    lifecycle warnings (the invariant: every admitted request ends in
    exactly one terminal event)."""
    by_rid: Dict[int, List[dict]] = {}
    for e in events:
        if e.get("kind") != "request":
            continue
        rid = e.get("rid")
        if rid is None or rid < 0:  # rid -1: pre-admission shed
            continue
        by_rid.setdefault(rid, []).append(e)
    rows: List[dict] = []
    warnings: List[str] = []
    for rid in sorted(by_rid):
        evs = by_rid[rid]
        first_t: Dict[str, float] = {}
        counts: Dict[str, int] = {}
        terminal = []
        for e in evs:
            ph = e.get("phase", "")
            counts[ph] = counts.get(ph, 0) + 1
            if ph not in first_t:
                first_t[ph] = e["t"]
            if ph in TERMINAL_PHASES:
                terminal.append(ph)
        row: Dict[str, Any] = {
            "rid": rid,
            "events": len(evs),
            "terminal": terminal[0] if terminal else None,
            "prefill_chunks": counts.get("prefill", 0),
            "decode_steps": counts.get("decode", 0),
            "preemptions": counts.get("preempted", 0),
            "replays": counts.get("replay", 0),
        }
        q, a = first_t.get("queued"), first_t.get("admitted")
        ft = first_t.get("first_token")
        term_t = first_t.get(terminal[0]) if terminal else None
        if q is not None and a is not None:
            row["queue_s"] = a - q
        if a is not None and ft is not None:
            row["prefill_s"] = ft - a
            row["ttft_s"] = row.get("queue_s", 0.0) + (ft - a)
        if ft is not None and term_t is not None:
            row["decode_s"] = term_t - ft
        if q is not None and term_t is not None:
            row["total_s"] = term_t - q
        rows.append(row)
        admitted = "admitted" in first_t
        if admitted and len(terminal) != 1:
            warnings.append(
                f"request {rid}: admitted but {len(terminal)} terminal "
                f"event(s) {terminal} (want exactly 1)")
        if len(terminal) > 1:
            warnings.append(
                f"request {rid}: multiple terminal events {terminal}")
    return rows, warnings


def _p95_blend(rows: List[dict]) -> Optional[dict]:
    """TTFT p95 with a component breakdown that sums exactly.

    Uses numpy.percentile's linear interpolation (idx = (n-1)*q) on the
    rows sorted by ttft, then blends each row's queue/prefill components
    with the *same* two bracketing samples and weight — per-row
    queue_s + prefill_s == ttft_s, so the blended components sum to the
    blended ttft bit-for-bit."""
    rows = [r for r in rows if "ttft_s" in r and "queue_s" in r
            and "prefill_s" in r]
    if not rows:
        return None
    rows.sort(key=lambda r: r["ttft_s"])
    n = len(rows)
    idx = (n - 1) * 0.95
    lo, hi = math.floor(idx), math.ceil(idx)
    w = idx - lo

    def blend(key):
        return rows[lo][key] * (1.0 - w) + rows[hi][key] * w

    dec = [r["decode_s"] for r in rows if "decode_s" in r]
    out = {
        "queue_p95_s": blend("queue_s"),
        "prefill_p95_s": blend("prefill_s"),
        "queue_mean_s": sum(r["queue_s"] for r in rows) / n,
        "prefill_mean_s": sum(r["prefill_s"] for r in rows) / n,
        "samples": n,
    }
    # the headline p95 is defined as the sum of its blended components
    # (mathematically identical to blend("ttft_s") — per-row
    # ttft == queue + prefill — but summing AFTER the blend keeps the
    # invariant bit-exact instead of reassociating the float ops)
    out["ttft_p95_s"] = out["queue_p95_s"] + out["prefill_p95_s"]
    if dec:
        out["decode_p95_s"] = _p95(dec)
        out["decode_mean_s"] = sum(dec) / len(dec)
    return out


def _p95(vals: List[float]) -> float:
    vals = sorted(vals)
    idx = (len(vals) - 1) * 0.95
    lo, hi = math.floor(idx), math.ceil(idx)
    return vals[lo] * (1.0 - (idx - lo)) + vals[hi] * (idx - lo)


# ---------------------------------------------------------------------------
# step spans + measured pipeline overlap
# ---------------------------------------------------------------------------

def step_stats(events: List[dict]) -> Dict[str, Any]:
    """Duration stats for train/serve step spans, per rank.  A serve
    step's cost is the engine's own ``wall_s`` where the span carries it:
    with a step in flight the span itself is only the wait for what was
    left of the step, and the span of a call that only dispatched
    (``landed=0``) is no step at all."""
    out: Dict[str, Any] = {}
    for name in ("train/step", "serve/step"):
        spans = [e for e in events
                 if e.get("kind") == "span" and e.get("name") == name
                 and e.get("landed") != 0]
        if not spans:
            continue
        per_rank: Dict[int, List[float]] = {}
        for e in spans:
            per_rank.setdefault(e.get("rank", 0), []).append(
                e.get("wall_s", e["dur"]))
        durs = [d for ds in per_rank.values() for d in ds]
        out[name] = {
            "count": len(durs),
            "mean_s": sum(durs) / len(durs),
            "p95_s": _p95(durs),
            "ranks": {str(r): {"count": len(ds),
                               "mean_s": sum(ds) / len(ds)}
                      for r, ds in sorted(per_rank.items())},
        }
    return out


def _score_schedule(sched: List[dict]) -> Dict[str, Any]:
    """transfer/serialization stats for one recorded schedule, with the
    simulator's exact sort key and serialization rule re-implemented
    (``distributed.overlap.transfer_stats``): a stage-boundary transfer
    is *serialized* when its consumer runs on the tick right after its
    producer (< 2 ticks of slack)."""
    sched = sorted(sched, key=lambda e: (
        e["tick"], e["stage"] if "stage" in e else e["src"]))
    total = serialized = 0
    for e in sched:
        if e.get("kind") not in ("send_fwd", "send_bwd"):
            continue
        total += 1
        if e["consumed_tick"] - e["produced_tick"] < 2:
            serialized += 1
    return {
        "n_events": len(sched),
        "total_transfers": total,
        "serialized_transfers": serialized,
        "overlap_fraction": (1.0 if total == 0
                             else 1.0 - serialized / total),
        "schedule_events": sched,
    }


def pipeline_overlap(events: List[dict]) -> Optional[dict]:
    """Measured overlap from the recorded pipeline schedule(s).

    Each ``pipeline/schedule`` meta event opens a new recording; the
    following kind=="pipeline" events carry the scheduled units verbatim
    under their ``ev`` key. Reports one entry per recording plus the
    aggregate over all of them."""
    recordings: List[dict] = []
    current: Optional[dict] = None
    all_sched: List[dict] = []
    for e in events:
        if e.get("kind") == "pipeline_meta" and "pp" in e:
            current = {k: e[k] for k in ("pp", "n_micro", "overlap")
                       if k in e}
            current["sched"] = []
            recordings.append(current)
        elif e.get("kind") == "pipeline" and "ev" in e:
            ev = dict(e["ev"])
            all_sched.append(ev)
            if current is not None:
                current["sched"].append(ev)
    if not all_sched:
        return None
    out = _score_schedule(all_sched)
    if len(recordings) > 1:
        out["recordings"] = []
        for r in recordings:
            sc = _score_schedule(r.pop("sched"))
            sc.pop("schedule_events")
            r.update(sc)
            out["recordings"].append(r)
    elif recordings:
        out.update({k: v for k, v in recordings[0].items()
                    if k != "sched"})
    return out


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------

_META = ("name", "kind", "t", "rank", "seq", "dur", "depth", "parent")


def chrome_events(events: List[dict]) -> List[dict]:
    """trace_event JSON: spans -> "X" complete slices, everything else
    -> "i" instants; pid = rank, tid = nesting depth. Mirrors
    ``profiler.trace.chrome_events`` (kept stdlib-side so the report
    never imports paddle_tpu)."""
    out: List[dict] = []
    pids = []
    tids = []
    for e in events:
        pid = e.get("rank", 0)
        tid = e.get("depth", 0)
        if pid not in pids:
            pids.append(pid)
        if (pid, tid) not in tids:
            tids.append((pid, tid))
        args = {k: v for k, v in e.items() if k not in _META}
        base = {"name": e["name"], "pid": pid, "tid": tid,
                "ts": e["t"] * 1e6, "cat": e.get("kind", "event"),
                "args": args}
        if e.get("kind") == "span":
            base.update(ph="X", dur=e.get("dur", 0.0) * 1e6)
        else:
            base.update(ph="i", s="t")
        out.append(base)
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": f"rank {pid}"}} for pid in sorted(pids)]
    meta += [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
              "args": {"name": f"depth {tid}"}}
             for pid, tid in sorted(tids)]
    return meta + out


# ---------------------------------------------------------------------------
# incidents + black box
# ---------------------------------------------------------------------------

def load_incidents(paths: List[str]) -> Tuple[List[str], List[dict],
                                              List[str]]:
    """(files, records, errors) for incident sidecars."""
    files = discover_sidecars(paths, "incidents_rank*.jsonl")
    records: List[dict] = []
    errors: List[str] = []
    for p in files:
        try:
            _, recs = read_sidecar(p, INCIDENT_SCHEMA)
        except (OSError, ValueError) as exc:
            errors.append(str(exc))
            continue
        records.extend(recs)
    return files, records, errors


def write_black_box(out_path: str, trace_files: List[str],
                    incident_files: List[str], report: dict) -> None:
    """One zip: every input sidecar + the report + a manifest."""
    manifest = {
        "schema": "paddle_tpu.blackbox.v1",
        "trace_files": [os.path.basename(p) for p in trace_files],
        "incident_files": [os.path.basename(p) for p in incident_files],
        "n_events": report.get("n_events", 0),
        "n_incidents": report.get("incidents", {}).get("count", 0),
    }
    with zipfile.ZipFile(out_path, "w",
                         compression=zipfile.ZIP_DEFLATED) as z:
        for p in trace_files + incident_files:
            z.write(p, arcname=os.path.basename(p))
        z.writestr("report.json",
                   json.dumps(report, indent=2, sort_keys=True,
                              default=str))
        z.writestr("manifest.json",
                   json.dumps(manifest, indent=2, sort_keys=True))


# ---------------------------------------------------------------------------
# gang verdict (--gang)
# ---------------------------------------------------------------------------

GANG_TERMINAL_BARRIER = "gang/exit"


def _f_tick(s: int, m: int, overlap: bool) -> int:
    return (2 * s if overlap else s) + m


def _b_tick(s: int, m: int, pp: int, overlap: bool) -> int:
    if overlap:
        return 4 * (pp - 1) + 1 - 2 * s + m
    return 2 * pp - 1 - s + m


def static_schedule(pp: int, n_micro: int, overlap: bool) -> List[dict]:
    """The 1F1B static schedule model, re-implemented verbatim from
    ``distributed.overlap.schedule_events`` (F/B tick arithmetic, edge
    ticks, and the simulator's sort key) so the gang verdict needs no
    paddle_tpu import and the comparison is bit-equal dict-for-dict."""
    events: List[dict] = []
    for m in range(n_micro):
        for s in range(pp):
            tf = _f_tick(s, m, overlap)
            tb = _b_tick(s, m, pp, overlap)
            events.append({"kind": "fwd", "tick": tf, "stage": s,
                           "micro": m})
            events.append({"kind": "bwd", "tick": tb, "stage": s,
                           "micro": m})
            if s < pp - 1:
                events.append({
                    "kind": "send_fwd", "micro": m, "src": s, "dst": s + 1,
                    "tick": tf + 1 if overlap else tf,
                    "produced_tick": tf,
                    "consumed_tick": _f_tick(s + 1, m, overlap)})
            if s > 0:
                events.append({
                    "kind": "send_bwd", "micro": m, "src": s, "dst": s - 1,
                    "tick": tb + 1 if overlap else tb,
                    "produced_tick": tb,
                    "consumed_tick": _b_tick(s - 1, m, pp, overlap)})
    events.sort(key=lambda e: (e["tick"], e["stage"] if "stage" in e
                               else e["src"]))
    return events


def _rank_schedule_verdict(events: List[dict]) -> Optional[dict]:
    """Compare every pipeline-schedule recording in one rank's event
    stream against the static model. None when the rank recorded no
    schedule (pp == 1 runs legitimately record none)."""
    recordings: List[dict] = []
    current: Optional[dict] = None
    for e in events:
        if e.get("kind") == "pipeline_meta" and "pp" in e:
            current = {"pp": int(e["pp"]), "n_micro": int(e["n_micro"]),
                       "overlap": bool(e["overlap"]), "sched": []}
            recordings.append(current)
        elif e.get("kind") == "pipeline" and "ev" in e:
            if current is not None:
                current["sched"].append(dict(e["ev"]))
    if not recordings:
        return None
    out = {"recordings": len(recordings), "matches_static": True}
    for i, rec in enumerate(recordings):
        recorded = sorted(rec["sched"],
                          key=lambda e: (e["tick"],
                                         e["stage"] if "stage" in e
                                         else e["src"]))
        static = static_schedule(rec["pp"], rec["n_micro"],
                                 rec["overlap"])
        out.setdefault("pp", rec["pp"])
        out.setdefault("n_micro", rec["n_micro"])
        out.setdefault("overlap", rec["overlap"])
        if recorded == static:
            continue
        out["matches_static"] = False
        div = {"recording": i, "recorded_events": len(recorded),
               "static_events": len(static)}
        for j, (a, b) in enumerate(zip(recorded, static)):
            if a != b:
                div.update(index=j, recorded=a, static=b)
                break
        else:
            # same prefix, different length: point at the first extra
            j = min(len(recorded), len(static))
            div.update(index=j,
                       recorded=recorded[j] if j < len(recorded) else None,
                       static=static[j] if j < len(static) else None)
        out.setdefault("divergence", div)
    return out


def gang_report(gang_dir: str) -> Tuple[dict, List[str], List[str]]:
    """Merged multi-rank verdict for one gang run's trace sidecar dir.

    Checks, per the flight-recorder contract ``distributed.gang``
    guarantees on every exit path:

    * every rank ``0..world_size-1`` (world size from the sidecar
      headers) wrote a sidecar — a missing file means that rank died
      without flushing, i.e. outside every guaranteed path;
    * each sidecar's event stream contains the ``gang/exit`` terminal
      barrier (finalize ran);
    * every recorded 1F1B pipeline schedule is bit-identical to the
      static model for its (pp, n_micro, overlap).

    Returns (report, failures, errors): ``failures`` → exit 1,
    ``errors`` (unreadable/corrupt input) → exit 2.
    """
    failures: List[str] = []
    errors: List[str] = []
    files = discover_sidecars([gang_dir], "trace_rank*.jsonl")
    ranks: Dict[int, dict] = {}
    for p in files:
        try:
            header, evs = read_sidecar(p, TRACE_SCHEMA)
        except (OSError, ValueError) as exc:
            errors.append(str(exc))
            continue
        rank = int(header.get("rank", 0))
        terminal = next((e for e in evs
                         if e.get("kind") == "barrier"
                         and e.get("name") == GANG_TERMINAL_BARRIER),
                        None)
        row: Dict[str, Any] = {
            "rank": rank,
            "file": p,
            "n_events": len(evs),
            "world_size": header.get("world_size"),
            "restart": header.get("restart"),
            "status": header.get("status"),
            "terminal_barrier": terminal is not None,
        }
        if terminal is not None:
            row["terminal_status"] = terminal.get("status")
            row["terminal_step"] = terminal.get("step")
        else:
            failures.append(
                f"rank {rank}: no {GANG_TERMINAL_BARRIER!r} terminal "
                f"barrier in {p} (finalize never ran)")
        sched = _rank_schedule_verdict(evs)
        row["schedule"] = sched
        if sched is not None and not sched["matches_static"]:
            failures.append(
                f"rank {rank}: recorded 1F1B schedule diverges from the "
                f"static model (pp={sched.get('pp')}, "
                f"n_micro={sched.get('n_micro')}, "
                f"overlap={sched.get('overlap')}) at event "
                f"{sched['divergence'].get('index')}")
        ranks[rank] = row
    if not files:
        errors.append(f"no trace sidecars found under {gang_dir} "
                      "(looked for trace_rank*.jsonl)")
    worlds = sorted({r["world_size"] for r in ranks.values()
                     if r["world_size"] is not None})
    if len(worlds) > 1:
        failures.append(
            f"sidecar headers disagree on world_size: {worlds}")
    expected = worlds[-1] if worlds else len(ranks)
    missing = [r for r in range(expected) if r not in ranks]
    if missing:
        failures.append(
            f"missing sidecar(s) for rank(s) {missing}: expected "
            f"{expected} ranks, found {sorted(ranks)}")
    report = {
        "tool": "trace_report",
        "mode": "gang",
        "version": 1,
        "dir": gang_dir,
        "files": files,
        "world_size": expected,
        "ranks_found": sorted(ranks),
        "missing_ranks": missing,
        "per_rank": [ranks[r] for r in sorted(ranks)],
        "verdict": "pass" if not (failures or errors) else "fail",
        "failures": failures,
        "errors": errors,
    }
    return report, failures, errors


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="trace_report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*", default=None,
                    help="trace sidecar files or directories holding "
                         "trace_rank*.jsonl (default: .)")
    ap.add_argument("--chrome", metavar="OUT",
                    help="write a Chrome/Perfetto trace JSON here")
    ap.add_argument("--incidents", action="append", default=[],
                    metavar="PATH",
                    help="incident sidecar file/dir (repeatable)")
    ap.add_argument("--black-box", metavar="OUT",
                    help="bundle sidecars + incidents + report into "
                         "one zip archive")
    ap.add_argument("--gang", metavar="DIR", default=None,
                    help="gang-run verdict mode: merge the dir's "
                         "trace_rank*.jsonl sidecars, require every "
                         "rank present with a gang/exit terminal "
                         "barrier, and check each recorded 1F1B "
                         "schedule against the static model; exit 1 "
                         "on any failure")
    ap.add_argument("--request", type=int, default=None, metavar="RID",
                    help="include this request's full event timeline")
    ap.add_argument("--max-requests", type=int, default=50,
                    help="cap the per_request rows in the report "
                         "(default 50; stats use all rows)")
    args = ap.parse_args(argv)

    if args.gang is not None:
        report, failures, gang_errors = gang_report(args.gang)
        json.dump(report, sys.stdout, indent=2, sort_keys=True,
                  default=str)
        sys.stdout.write("\n")
        if gang_errors:
            return 2
        if failures:
            return 1
        return 0

    errors: List[str] = []
    warnings: List[str] = []

    files = discover_sidecars(args.paths or ["."], "trace_rank*.jsonl")
    per_rank: Dict[int, List[dict]] = {}
    for p in files:
        try:
            header, evs = read_sidecar(p, TRACE_SCHEMA)
        except (OSError, ValueError) as exc:
            errors.append(str(exc))
            continue
        rank = int(header.get("rank", 0))
        per_rank.setdefault(rank, []).extend(evs)
        if header.get("dropped"):
            warnings.append(
                f"{p}: ring buffer dropped {header['dropped']} "
                "event(s) before the dump")
    if not files:
        errors.append("no trace sidecars found (looked for "
                      "trace_rank*.jsonl under: "
                      + ", ".join(args.paths or ["."]) + ")")

    events = merge_ranks(per_rank)
    rows, req_warnings = request_rows(events)
    warnings.extend(req_warnings)

    report: Dict[str, Any] = {
        "tool": "trace_report",
        "version": 1,
        "files": files,
        "ranks": sorted(per_rank),
        "n_events": len(events),
    }
    if rows:
        breakdown = _p95_blend(rows)
        terminal = sum(1 for r in rows if r["terminal"] is not None)
        report["requests"] = {
            "count": len(rows),
            "terminal": terminal,
            "breakdown": breakdown,
            "per_request": rows[:args.max_requests],
        }
    steps = step_stats(events)
    if steps:
        report["steps"] = steps
    pipe = pipeline_overlap(events)
    if pipe is not None:
        report["pipeline"] = {k: v for k, v in pipe.items()
                              if k != "schedule_events"}
    if args.request is not None:
        report["request_timeline"] = [
            e for e in events
            if e.get("kind") == "request"
            and e.get("rid") == args.request]

    inc_files: List[str] = []
    if args.incidents:
        inc_files, inc_records, inc_errors = load_incidents(
            args.incidents)
        errors.extend(inc_errors)
        kinds: Dict[str, int] = {}
        for r in inc_records:
            kinds[r.get("kind", "?")] = kinds.get(r.get("kind", "?"),
                                                  0) + 1
        report["incidents"] = {
            "files": inc_files,
            "count": len(inc_records),
            "by_kind": dict(sorted(kinds.items())),
            "last": inc_records[-5:],
        }

    # warnings/errors are live references: anything appended below
    # (e.g. an unwritable --chrome path) still lands in the report
    report["warnings"] = warnings
    report["errors"] = errors

    if args.chrome:
        try:
            with open(args.chrome, "w") as f:
                json.dump(
                    {"traceEvents": chrome_events(events),
                     "displayTimeUnit": "ms",
                     "metadata": {"producer": "tools/trace_report"}},
                    f, default=str)
            report["chrome_out"] = args.chrome
        except OSError as exc:
            errors.append(f"--chrome {args.chrome}: {exc}")
    if args.black_box:
        try:
            write_black_box(args.black_box, files, inc_files, report)
            report["black_box_out"] = args.black_box
        except OSError as exc:
            errors.append(f"--black-box {args.black_box}: {exc}")

    json.dump(report, sys.stdout, indent=2, sort_keys=True,
              default=str)
    sys.stdout.write("\n")
    if errors:
        return 2
    if warnings:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
