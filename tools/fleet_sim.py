#!/usr/bin/env python
"""fleet_sim: trace-driven discrete-event fleet simulator.

Replays a serving workload — recorded PR-14 flight-recorder sidecars
(``trace_rank<N>.jsonl``) or a synthesized arrival process from
``paddle_tpu/serving/workloads.py`` — through R simulated replicas
behind the REAL ``Router`` (placement, failover, drain, autoscaling
are the shipped code, not a model of it).  Each replica is the real
``Scheduler`` + ``PagedKVCache`` + ``AdmissionGate`` host state; the
only thing modelled is time: the two compiled step costs (Tc=1
decode, Tc=chunk prefill), calibrated from trace-measured
``serve/step`` spans when a trace is given, else the shared defaults
in ``serving/autoscale.py``.  Because admission, batching, paging and
preemption run the live code paths, admitted/shed counts match a
live run over the same workload *exactly*; latency is as good as the
calibration.

Sweeps (replicas x kv_dtype x page budget) and reports the
minimum-chip configuration meeting a TTFT/latency SLO, with
per-window SLO burn-rate timelines.  ``--autoscale`` closes the loop:
an ``AutoscalePolicy`` drives the router on virtual time, scale-ups
provision fresh simulated replicas, scale-downs drain real ones.

Stdlib-only and jax-free: the needed paddle_tpu modules are loaded
standalone (same trick as tools/tpu_lint.py), so this starts in
milliseconds on any machine.  Output is deterministic for a fixed
seed — no wall-clock anywhere.

Usage:
    python tools/fleet_sim.py --workload flash-crowd --requests 200 \
        --horizon-s 60 --replicas 1-4 --slo-ttft-s 0.5 --out FLEET.json
    python tools/fleet_sim.py --trace-dir /tmp/serve_run --replicas 2
    python tools/fleet_sim.py --workload diurnal \
        --capacity-json cap.json --replicas 1-8 --autoscale

Exit codes (tpu_lint convention): 0 = some swept configuration meets
the SLO, 1 = none does, 2 = bad input (unknown sidecar schema,
corrupt trace, bad arguments).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import types
from typing import Dict, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the jax-free slice of paddle_tpu the simulator runs on; loaded as a
# synthetic package so relative imports resolve without executing any
# __init__.py (those import jax)
_PKG = "_fleet_sim_pt"
_SUBPKGS = ("core", "profiler", "runtime", "testing", "serving")
_MODULES = ("core.flags", "profiler.metrics", "profiler.trace",
            "runtime.watchdog", "runtime.health", "testing.chaos",
            "serving.errors", "serving.stats", "serving.kv_cache",
            "serving.prefix_cache", "serving.scheduler",
            "serving.workloads", "serving.autoscale",
            "serving.router")


class _Paddle:
    """Namespace over the standalone-loaded paddle_tpu modules."""


def load_paddle(root: str = REPO_ROOT) -> _Paddle:
    """Load the stdlib-only paddle_tpu modules WITHOUT importing
    paddle_tpu (or jax): synthetic parent packages whose ``__path__``
    points at the real source tree let every relative import inside
    the modules resolve normally, while the real ``__init__.py``
    chain (which imports jax) never runs."""
    base = os.path.join(root, "paddle_tpu")
    if _PKG not in sys.modules:
        pkg = types.ModuleType(_PKG)
        pkg.__path__ = [base]
        sys.modules[_PKG] = pkg
        for sub in _SUBPKGS:
            m = types.ModuleType(f"{_PKG}.{sub}")
            m.__path__ = [os.path.join(base, sub)]
            sys.modules[f"{_PKG}.{sub}"] = m
    mods = {name: importlib.import_module(f"{_PKG}.{name}")
            for name in _MODULES}
    pt = _Paddle()
    pt.flags = mods["core.flags"]
    pt.metrics = mods["profiler.metrics"]
    pt.trace = mods["profiler.trace"]
    pt.errors = mods["serving.errors"]
    pt.kv_cache = mods["serving.kv_cache"]
    pt.scheduler = mods["serving.scheduler"]
    pt.stats = mods["serving.stats"]
    pt.workloads = mods["serving.workloads"]
    pt.autoscale = mods["serving.autoscale"]
    pt.router = mods["serving.router"]
    return pt


# -- virtual time ---------------------------------------------------------
class SimClock:
    """Virtual time for the fleet.  ``serial`` mode sums every
    replica's step cost (matches an in-process Router stepping its
    replicas one after another — the sim-vs-live cross-check);
    parallel mode (default) gives each replica its own lane within a
    router iteration and commits the max — real fleets step replicas
    concurrently."""

    def __init__(self, serial: bool = False):
        self.serial = serial
        self.t = 0.0
        self._base = 0.0
        self._lanes: Dict[str, float] = {}
        self._cur: Optional[str] = None

    def now(self) -> float:
        return self.t

    def jump_to(self, t: float) -> None:
        self.t = max(self.t, float(t))

    def begin_iteration(self) -> None:
        self._base = self.t
        self._lanes.clear()

    def enter(self, name: str) -> float:
        if not self.serial:
            self._cur = name
            self._lanes.setdefault(name, 0.0)
            self.t = self._base + self._lanes[name]
        return self.t

    def advance(self, dur: float) -> float:
        if self.serial:
            self.t += dur
        else:
            self._lanes[self._cur] += dur
            self.t = self._base + self._lanes[self._cur]
        return self.t

    def commit_iteration(self) -> None:
        if not self.serial:
            self.t = self._base + (max(self._lanes.values())
                                   if self._lanes else 0.0)


# -- the simulated replica -----------------------------------------------
class SimEngine:
    """Duck-types the LLMEngine surface the Router drives
    (``add_request/step/state_of/error_of/cancel/scheduler``) on the
    real host-side machinery — Scheduler, PagedKVCache,
    AdmissionGate — so admission, batching, paging and preemption
    behave exactly like a live engine, in the live engine's order: one
    step in flight, the next planned on the scheduler's counts and
    dispatched before the one before it completes.  The device forward
    is replaced by a clock advance: one ServiceModel step cost per
    scheduled step, bucket-dependent, paid when the step completes."""

    def __init__(self, pt: _Paddle, model, clock: SimClock,
                 name: str = "sim0"):
        self.pt = pt
        self.model = model
        self.clock = clock
        self.name = name
        blocks = model.blocks_per_request
        self.kv = pt.kv_cache.PagedKVCache(model.num_pages,
                                           model.page_size, blocks)
        self.scheduler = pt.scheduler.Scheduler(
            self.kv, max_running=model.max_running, chunk=model.chunk,
            max_model_len=model.max_model_len)
        self.max_queue = model.max_queue
        self._gate = pt.scheduler.AdmissionGate(self.max_queue)
        self._requests: Dict[int, object] = {}
        self.shed = 0
        self.steps = 0
        self.busy_s = 0.0
        self._flight = None     # the plan dispatched and not completed

    # engine surface ------------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int,
                    eos_token_id: Optional[int] = None,
                    on_token=None,
                    deadline_s: Optional[float] = None) -> int:
        depth = self.scheduler.num_waiting
        if self._gate.check(depth):
            self.shed += 1
            raise self.pt.errors.AdmissionRejected(
                f"admission queue at {depth}/{self.max_queue}; "
                f"shedding until it drains below "
                f"{self._gate.recover_below} — retry with backoff")
        now = self.clock.now()
        req = self.pt.scheduler.Request(
            prompt=[int(t) for t in prompt],
            max_new_tokens=int(max_new_tokens),
            eos_token_id=eos_token_id, on_token=on_token,
            arrival_s=now,
            deadline_s=(None if deadline_s is None
                        else now + float(deadline_s)))
        self.scheduler.add(req)
        self._requests[req.rid] = req
        return req.rid

    def state_of(self, rid: int):
        return self._requests[rid].state

    def error_of(self, rid: int):
        return self._requests[rid].error

    def has_work(self) -> bool:
        return self.scheduler.has_work() or self._flight is not None

    def cancel(self, rid: int) -> bool:
        RequestState = self.pt.scheduler.RequestState
        req = self._requests.get(rid)
        if req is None or req.state not in (RequestState.WAITING,
                                            RequestState.RUNNING):
            return False
        self.scheduler.remove(req, now_s=self.clock.now(),
                              state=RequestState.CANCELLED)
        return True

    def _expire_deadlines(self, now: float) -> None:
        RequestState = self.pt.scheduler.RequestState
        active = [r for r in self.scheduler.slots if r is not None]
        active.extend(self.scheduler.waiting)
        for req in active:
            if req.deadline_s is None or now <= req.deadline_s:
                continue
            self.scheduler.remove(
                req, now_s=now, state=RequestState.FAILED,
                error=self.pt.errors.DeadlineExceeded(
                    f"request {req.rid} missed its deadline by "
                    f"{now - req.deadline_s:.3f}s"))

    def step(self) -> List[int]:
        self.clock.enter(self.name)
        self._expire_deadlines(self.clock.now())
        plan = self.scheduler.schedule()
        self.kv.drain_copies()
        landing, self._flight = self._flight, None
        if plan.seqs:       # none where the plan waits for ``landing``
            self.scheduler.dispatch(plan)
            self._flight = plan
        if landing is None:
            return []
        dur = (self.model.prefill_chunk_s if landing.bucket > 1
               else self.model.decode_step_s)
        now = self.clock.advance(dur)
        self.steps += 1
        self.busy_s += dur
        out = {s.slot: 1 for s in landing.seqs if s.produces}
        finished = self.scheduler.complete(landing, out, now_s=now)
        return [r.rid for r in finished]


# -- trace ingestion ------------------------------------------------------
def die(code: int, msg: str) -> None:
    print(f"fleet_sim: error: {msg}", file=sys.stderr)
    raise SystemExit(code)


def find_sidecars(trace_dir: str) -> List[str]:
    paths = sorted(
        os.path.join(trace_dir, f) for f in os.listdir(trace_dir)
        if f.startswith("trace_rank") and f.endswith(".jsonl"))
    if not paths:
        die(2, f"no trace_rank<N>.jsonl sidecars in {trace_dir!r}")
    return paths


def check_sidecar_schema(pt: _Paddle, path: str) -> None:
    """Reject unknown/corrupt sidecars up front with a clear
    diagnostic (exit 2), instead of crashing mid-replay."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            first = f.readline()
    except OSError as exc:
        die(2, f"{path}: unreadable sidecar: {exc}")
    if not first.strip():
        die(2, f"{path}: empty file — not a trace sidecar")
    try:
        header = json.loads(first)
    except json.JSONDecodeError:
        die(2, f"{path}: first line is not JSON — not a trace "
               f"sidecar (expected a {pt.trace.SCHEMA} header)")
    schema = header.get("schema") if isinstance(header, dict) else None
    if schema != pt.trace.SCHEMA:
        die(2, f"{path}: unknown trace schema {schema!r} "
               f"(this build reads {pt.trace.SCHEMA!r}; re-record "
               f"the trace or use a matching fleet_sim)")


def load_trace(pt: _Paddle, trace_dir: str):
    """Workload + calibration samples from recorded sidecars:
    arrivals from ``serve/queued`` request events, per-bucket step
    costs from ``serve/step`` span durations."""
    paths = find_sidecars(trace_dir)
    for p in paths:
        check_sidecar_schema(pt, p)
    try:
        events = pt.trace.merge_sidecars(paths)
    except ValueError as exc:
        die(2, f"{trace_dir}: corrupt trace: {exc}")
    queued = [e for e in events
              if e.get("kind") == "request"
              and e.get("name") == "serve/queued"]
    steps: Dict[int, List[float]] = {}
    for e in events:
        if (e.get("kind") == "span" and e.get("name") == "serve/step"
                and "dur" in e and "bucket" in e):
            # a step's cost to the service (``wall_s``, the engine's own
            # sample) where the span carries it: with a step in flight the
            # span itself is only the wait for what was left of the step,
            # and the span of a call that only dispatched names no bucket
            steps.setdefault(int(e["bucket"]), []).append(
                float(e.get("wall_s", e["dur"])))
    if not queued:
        die(2, f"{trace_dir}: trace holds no serve/queued request "
               f"events — record with FLAGS_tpu_trace=1 while "
               f"serving, then profiler.trace.write_sidecar("
               f"trace.sidecar_path(DIR))")
    t0 = min(float(e["t"]) for e in queued)
    arrivals = []
    for i, e in enumerate(sorted(queued, key=lambda e: float(e["t"]))):
        plen = int(e.get("prompt_len", 16) or 16)
        arrivals.append(pt.workloads.Arrival(
            t_s=float(e["t"]) - t0,
            prompt=tuple(1 + (i + j) % 97 for j in range(plen)),
            max_new_tokens=int(e.get("max_new_tokens", 8) or 8)))
    return arrivals, steps


# -- one simulation run ---------------------------------------------------
def simulate(pt: _Paddle, model, arrivals, n_replicas: int, *,
             slo_ttft_s: Optional[float] = None,
             slo_latency_s: Optional[float] = None,
             serial: bool = False, burn_window_s: float = 5.0,
             budget: float = 0.05, autoscale: bool = False,
             autoscale_apply: bool = False,
             max_wall_s: float = 3600.0) -> Dict[str, object]:
    """Drive the real Router over virtual time; returns the run
    report (counts, latency percentiles, burn timeline, scale
    events)."""
    clock = SimClock(serial=serial)
    engines = [SimEngine(pt, model, clock, name=f"sim{i}")
               for i in range(int(n_replicas))]
    policy = None
    if autoscale:
        p_nom = max((len(a.prompt) for a in arrivals), default=16)
        n_nom = max((a.max_new_tokens for a in arrivals), default=8)
        policy = pt.autoscale.AutoscalePolicy(
            model, slo_ttft_s=slo_ttft_s, prompt_len=p_nom,
            new_tokens=n_nom, budget=budget,
            windows_s=(burn_window_s, 4 * burn_window_s),
            horizon_s=2 * burn_window_s, cooldown_s=4 * burn_window_s,
            # simulated provisioning is instant, so a fast forecaster
            # can buy capacity within ~1s of a spike's onset — before
            # the queue turns into TTFT violations
            forecast_tau_s=max(burn_window_s / 5.0, 1.0),
            clock=clock.now)
    router = pt.router.Router(
        [(e.name, e) for e in engines], clock=clock.now,
        heartbeat_timeout=1e12, autoscaler=policy,
        autoscale_apply=autoscale_apply)

    pending = sorted(arrivals, key=lambda a: (a.t_s, a.prompt))
    recs: Dict[int, Dict[str, Optional[float]]] = {}
    scale_events: List[Dict[str, object]] = []
    shed = 0
    i = 0

    def cb(gid, token, finished):
        r = recs[gid]
        if r["first_token_s"] is None:
            r["first_token_s"] = clock.now()
        if finished:
            r["finish_s"] = clock.now()

    n_added = 0
    while True:
        now = clock.now()
        while i < len(pending) and pending[i].t_s <= now:
            a = pending[i]
            i += 1
            try:
                gid = router.submit(list(a.prompt), a.max_new_tokens,
                                    on_token=cb)
            except (pt.errors.AdmissionRejected,
                    pt.errors.ReplicaUnavailable):
                shed += 1
                continue
            recs[gid] = {"arrival_s": a.t_s, "first_token_s": None,
                         "finish_s": None}
        if not router.has_work():
            if i >= len(pending):
                break
            clock.jump_to(pending[i].t_s)
            continue
        before = clock.now()
        clock.begin_iteration()
        router.step()
        clock.commit_iteration()
        rec = router.last_recommendation
        if rec is not None and rec.action != "hold" and (
                not scale_events
                or scale_events[-1]["t_s"] != rec.at_s
                or scale_events[-1]["action"] != rec.action):
            scale_events.append({
                "t_s": round(rec.at_s, 6), "action": rec.action,
                "target": rec.target_replicas,
                "live": rec.live_replicas,
                "applied": rec.applied})
            if (autoscale and rec.action == "scale_up"
                    and autoscale_apply):
                # the simulator CAN provision hardware: attach fresh
                # replicas up to the recommended target (live apply
                # only drains — scale-up stays a recommendation
                # there)
                live = len(router.live_replicas())
                while live < rec.target_replicas:
                    n_added += 1
                    eng = SimEngine(pt, model, clock,
                                    name=f"sim-up{n_added}")
                    engines.append(eng)
                    router.add_replica(eng.name, eng)
                    live += 1
                if policy is not None:
                    policy.mark_applied(rec)
                scale_events[-1]["applied"] = True
        if clock.now() <= before and not any(e._flight for e in engines):
            # no replica made progress (e.g. orphans waiting; a replica that
            # only dispatched pays for the step when it completes it): let
            # virtual time flow to the next arrival or one decode
            if i < len(pending):
                clock.jump_to(pending[i].t_s)
            else:
                clock.jump_to(before + model.decode_step_s)
        if clock.now() > max_wall_s:
            break

    ttft = sorted(r["first_token_s"] - r["arrival_s"] for r in
                  recs.values() if r["first_token_s"] is not None)
    latency = sorted(r["finish_s"] - r["arrival_s"] for r in
                     recs.values() if r["finish_s"] is not None)
    end_s = clock.now()

    first_violation_s = None
    n_violations = 0
    if slo_ttft_s is not None:
        viol_at = [r["first_token_s"] for r in recs.values()
                   if r["first_token_s"] is not None
                   and r["first_token_s"] - r["arrival_s"] > slo_ttft_s]
        n_violations = len(viol_at)
        if viol_at:
            first_violation_s = round(min(viol_at), 6)
    first_scale_up_s = next(
        (e["t_s"] for e in scale_events if e["action"] == "scale_up"),
        None)

    # per-window burn timeline over the TTFT SLO
    timeline: List[Dict[str, object]] = []
    if slo_ttft_s is not None and burn_window_s > 0:
        n_win = int(end_s / burn_window_s) + 1
        for w in range(n_win):
            lo, hi = w * burn_window_s, (w + 1) * burn_window_s
            xs = [r for r in recs.values()
                  if r["first_token_s"] is not None
                  and lo <= r["first_token_s"] < hi]
            if not xs:
                continue
            viol = sum(1 for r in xs
                       if r["first_token_s"] - r["arrival_s"]
                       > slo_ttft_s)
            frac = viol / len(xs)
            timeline.append({
                "window_s": [round(lo, 6), round(hi, 6)],
                "requests": len(xs), "violations": viol,
                "burn_rate": round(frac / budget, 4) if budget
                else None})

    report: Dict[str, object] = {
        "replicas": int(n_replicas),
        "replicas_final": len(router.live_replicas()),
        "offered": len(pending),
        "admitted": len(recs),
        "shed": shed,
        "finished": len(latency),
        "sim_end_s": round(end_s, 6),
        "engine_steps": sum(e.steps for e in engines),
        "ttft_p50_s": _pct(ttft, 50), "ttft_p95_s": _pct(ttft, 95),
        "latency_p50_s": _pct(latency, 50),
        "latency_p95_s": _pct(latency, 95),
        "burn_timeline": timeline,
        "scale_events": scale_events,
        "ttft_violations": n_violations,
        "first_violation_s": first_violation_s,
        "first_scale_up_s": first_scale_up_s,
    }
    slo_ok = True
    if slo_ttft_s is not None:
        ok = (report["ttft_p95_s"] is not None
              and report["ttft_p95_s"] <= slo_ttft_s)
        report["ttft_ok"] = ok
        slo_ok = slo_ok and ok
    if slo_latency_s is not None:
        ok = (report["latency_p95_s"] is not None
              and report["latency_p95_s"] <= slo_latency_s)
        report["latency_ok"] = ok
        slo_ok = slo_ok and ok
    report["slo_ok"] = slo_ok if (slo_ttft_s is not None or
                                  slo_latency_s is not None) else None
    return report


def _pct(sorted_xs: Sequence[float], q: float) -> Optional[float]:
    """numpy.percentile(interpolation='linear') on a pre-sorted list
    — keeps the report numerically comparable with slo_report()."""
    if not sorted_xs:
        return None
    if len(sorted_xs) == 1:
        return round(float(sorted_xs[0]), 6)
    pos = (len(sorted_xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(sorted_xs) - 1)
    frac = pos - lo
    return round(sorted_xs[lo] * (1 - frac) + sorted_xs[hi] * frac, 6)


# -- configuration sweep --------------------------------------------------
def parse_int_list(spec: str) -> List[int]:
    """``"1-4"`` -> [1,2,3,4]; ``"1,2,8"`` -> [1,2,8]; ``"2"`` -> [2]."""
    out: List[int] = []
    for part in spec.split(","):
        part = part.strip()
        if "-" in part[1:]:
            lo, hi = part.split("-", 1)
            out.extend(range(int(lo), int(hi) + 1))
        else:
            out.append(int(part))
    if not out or any(v <= 0 for v in out):
        raise ValueError(f"bad int list {spec!r}")
    return sorted(set(out))


def capacity_variants(pt: _Paddle, args,
                      base_model) -> List[Tuple[str, int, object]]:
    """(kv_dtype label, num_pages, ServiceModel) variants to sweep.
    ``--capacity-json`` takes them from a ``pod_report serving``
    report (which owns the HBM arithmetic, int8 page scales
    included); ``--pages`` sweeps explicit page budgets; default is
    the base model alone."""
    variants: List[Tuple[str, int, object]] = []
    if args.capacity_json:
        try:
            with open(args.capacity_json, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as exc:
            die(2, f"--capacity-json {args.capacity_json}: {exc}")
        serving = doc.get("serving", doc)
        blocks = []
        if isinstance(serving.get("baseline_bf16"), dict):
            blocks.append(("bf16", serving["baseline_bf16"]))
            blocks.append((serving.get("kv_dtype", "int8"), serving))
        else:
            blocks.append((serving.get("kv_dtype", "bf16"), serving))
        for label, blk in blocks:
            pages = blk.get("num_pages")
            if pages is None:
                die(2, f"--capacity-json {args.capacity_json}: no "
                       f"num_pages in serving block — generate with "
                       f"tools/pod_report.py serving")
            m = _with_pages(base_model, int(pages),
                            page_size=int(blk.get("page_size",
                                          base_model.page_size)))
            variants.append((label, int(pages), m))
    elif args.pages:
        for pages in parse_int_list(args.pages):
            variants.append(
                (args.kv_dtype, pages,
                 _with_pages(base_model, pages)))
    else:
        variants.append((args.kv_dtype, base_model.num_pages,
                         base_model))
    return variants


def _with_pages(model, num_pages: int, page_size: Optional[int] = None):
    import dataclasses as _dc
    changes = {"num_pages": int(num_pages)}
    if page_size is not None:
        changes["page_size"] = int(page_size)
    return _dc.replace(model, **changes)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="fleet_sim", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    src = ap.add_argument_group("workload")
    src.add_argument("--workload", default=None,
                     help="synthesized arrival preset "
                          "(see serving/workloads.py)")
    src.add_argument("--trace-dir", default=None,
                     help="replay trace_rank<N>.jsonl sidecars from "
                          "this directory (also calibrates step "
                          "costs from serve/step spans)")
    src.add_argument("--requests", type=int, default=200)
    src.add_argument("--horizon-s", type=float, default=60.0)
    src.add_argument("--seed", type=int, default=0)
    src.add_argument("--prompt-len", type=int, default=12)
    src.add_argument("--max-new-tokens", type=int, default=8)
    eng = ap.add_argument_group("service model (per replica)")
    eng.add_argument("--max-running", type=int, default=8)
    eng.add_argument("--chunk", type=int, default=16)
    eng.add_argument("--page-size", type=int, default=16)
    eng.add_argument("--max-model-len", type=int, default=64)
    eng.add_argument("--max-queue", type=int, default=None,
                     help="admission queue bound "
                          "(default 8*max_running, like the engine)")
    eng.add_argument("--prefill-chunk-s", type=float, default=None,
                     help="override the prefill-bucket step cost")
    eng.add_argument("--decode-step-s", type=float, default=None,
                     help="override the decode-bucket step cost")
    eng.add_argument("--capacity-json", default=None,
                     help="pod_report serving JSON: sweep its "
                          "num_pages/kv_dtype variants")
    eng.add_argument("--pages", default=None,
                     help="page budgets to sweep, e.g. 33,65,129")
    eng.add_argument("--kv-dtype", default="bf16",
                     help="label for --pages variants (capacity "
                          "arithmetic comes from pod_report)")
    sweep = ap.add_argument_group("sweep / SLO")
    sweep.add_argument("--replicas", default="1-4",
                       help="replica counts to sweep: N, lo-hi or "
                            "comma list")
    sweep.add_argument("--slo-ttft-s", type=float, default=None)
    sweep.add_argument("--slo-latency-s", type=float, default=None)
    sweep.add_argument("--budget", type=float, default=0.05,
                       help="SLO error budget (violation fraction)")
    sweep.add_argument("--burn-window-s", type=float, default=5.0)
    sweep.add_argument("--serial", action="store_true",
                       help="sum replica step costs per iteration "
                            "(matches an in-process router stepping "
                            "replicas serially) instead of max "
                            "(a real parallel fleet)")
    auto = ap.add_argument_group("autoscaling")
    auto.add_argument("--autoscale", action="store_true",
                      help="attach an AutoscalePolicy to the router")
    auto.add_argument("--autoscale-apply", action="store_true",
                      help="apply recommendations in the sim: "
                           "scale-ups provision replicas, "
                           "scale-downs drain")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--repo-root", default=REPO_ROOT,
                    help=argparse.SUPPRESS)
    return ap


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    pt = load_paddle(args.repo_root)

    calib_steps: Dict[int, List[float]] = {}
    if args.trace_dir:
        arrivals, calib_steps = load_trace(pt, args.trace_dir)
        workload_label = f"trace:{os.path.basename(args.trace_dir)}"
    else:
        preset = args.workload or "uniform"
        try:
            pt.workloads.validate(preset)
        except ValueError as exc:
            die(2, str(exc))
        arrivals = pt.workloads.generate(
            preset, args.requests, seed=args.seed,
            horizon_s=args.horizon_s, prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens)
        workload_label = preset

    max_queue = (args.max_queue if args.max_queue is not None
                 else 8 * args.max_running)
    model = pt.autoscale.ServiceModel.from_step_samples(
        calib_steps, max_running=args.max_running, chunk=args.chunk,
        page_size=args.page_size,
        num_pages=args.max_running * (
            -(-args.max_model_len // args.page_size)) + 1,
        max_model_len=args.max_model_len, max_queue=max_queue)
    overrides = {}
    if args.prefill_chunk_s is not None:
        overrides["prefill_chunk_s"] = args.prefill_chunk_s
    if args.decode_step_s is not None:
        overrides["decode_step_s"] = args.decode_step_s
    if overrides:
        import dataclasses as _dc
        model = _dc.replace(model, **overrides)

    try:
        replica_counts = parse_int_list(args.replicas)
        variants = capacity_variants(pt, args, model)
    except ValueError as exc:
        die(2, str(exc))

    runs: List[Dict[str, object]] = []
    for kv_label, pages, m in variants:
        analytic = pt.autoscale.recommend_fleet(
            m, arrivals, peak_window_s=args.burn_window_s)
        for n in replica_counts:
            rep = simulate(
                pt, m, arrivals, n, slo_ttft_s=args.slo_ttft_s,
                slo_latency_s=args.slo_latency_s, serial=args.serial,
                burn_window_s=args.burn_window_s, budget=args.budget,
                autoscale=args.autoscale,
                autoscale_apply=args.autoscale_apply)
            rep["kv_dtype"] = kv_label
            rep["num_pages"] = pages
            rep["analytic_min_replicas"] = analytic["min_replicas"]
            rep["offered_rps_peak"] = analytic["offered_rps_peak"]
            rep["capacity_rps_per_replica"] = (
                analytic["capacity_rps_per_replica"])
            runs.append(rep)

    meeting = [r for r in runs if r["slo_ok"]]
    recommended = None
    if meeting:
        # minimum chips first (1 chip per replica), then the leaner
        # page budget
        best = min(meeting, key=lambda r: (r["replicas"],
                                           r["num_pages"]))
        recommended = {k: best[k] for k in
                       ("replicas", "kv_dtype", "num_pages",
                        "ttft_p95_s", "latency_p95_s", "admitted",
                        "shed")}
    doc = {
        "tool": "fleet_sim",
        "workload": workload_label,
        "requests": len(arrivals),
        "seed": args.seed,
        "serial_clock": bool(args.serial),
        "calibrated": model.calibrated,
        "service_model": model.to_dict(),
        "slo": {"ttft_p95_s": args.slo_ttft_s,
                "latency_p95_s": args.slo_latency_s,
                "budget": args.budget,
                "burn_window_s": args.burn_window_s},
        "sweep": runs,
        "recommended": recommended,
    }
    text = json.dumps(doc, indent=2, sort_keys=True)
    print(text)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    if (args.slo_ttft_s is None and args.slo_latency_s is None):
        return 0
    return 0 if recommended is not None else 1


if __name__ == "__main__":
    sys.exit(main())
