"""Flight-recorder tracing (ISSUE 14): span API + ring bounds +
disabled path, per-request serving timelines (preemption, replay,
crash recovery, deadlines — every admitted request ends in exactly one
terminal event), multi-rank sidecar merge with an injectable clock,
measured-vs-simulated pipeline overlap (bit-equal, tolerance 0),
incident persistence, per-replica router stats, Chrome-export
metadata, and the stdlib-only ``tools/trace_report.py`` CLI.
"""
import json
import os
import subprocess
import sys
import time
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import distributed as dist
from paddle_tpu import profiler as prof
from paddle_tpu import serving
from paddle_tpu.distributed import overlap as ov
from paddle_tpu.distributed import plan as plan_mod
from paddle_tpu.models import llama
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import metrics, trace
from paddle_tpu.runtime import watchdog as wdog
from paddle_tpu.runtime.health import HealthMonitor, RELAUNCH_EXIT_CODE
from paddle_tpu.serving import router as router_mod
from paddle_tpu.testing import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


@pytest.fixture
def trace_on():
    """Enable FLAGS_tpu_trace on a clean ring; restore after."""
    trace.clear()
    paddle.set_flags({"FLAGS_tpu_trace": True})
    yield
    paddle.set_flags({"FLAGS_tpu_trace": False})
    trace.set_clock(time.monotonic)
    trace.clear()


@pytest.fixture
def metrics_on():
    metrics.reset()
    paddle.set_flags({"FLAGS_tpu_metrics": True})
    yield
    paddle.set_flags({"FLAGS_tpu_metrics": False})
    metrics.reset()


@pytest.fixture
def replica_stats():
    router_mod.reset_replica_stats()
    yield
    router_mod.reset_replica_stats()


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def _tiny_cfg():
    return llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, dtype=jnp.float32, use_remat=False)


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_running", 4)
    kw.setdefault("chunk", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_model_len", 32)
    return serving.LLMEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# disabled path: one dict lookup, nothing recorded; a span is the
# profiler's annotation alone
# ---------------------------------------------------------------------------

class TestDisabledPath:
    def test_disabled_by_default_records_nothing(self):
        trace.clear()
        assert not trace.enabled()
        assert trace.event("x", foo=1) is None
        assert trace.barrier("b") is None
        assert trace.request_event("queued", 7) is None
        assert trace.record_pipeline_schedule(2, 4, overlap=True) is None
        with trace.span("s", step=0):
            pass
        assert trace.events() == []

    def test_disabled_span_is_the_profiler_annotation(self):
        # the off path keeps nothing in the ring: span() hands back the
        # jax.profiler annotation itself (inert without a profiler
        # session), which takes the fields a call site learns later
        import jax
        trace.clear()
        s = trace.span("a", k=1)
        assert type(s) is jax.profiler.TraceAnnotation
        with s:
            s.set_metadata(rows=3)
        assert trace.events() == []


# ---------------------------------------------------------------------------
# recorder: nesting, injectable clock, ring bounds
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_span_nesting_depth_parent_duration(self):
        clk = _FakeClock(10.0)
        rec = trace.TraceRecorder(capacity=16, clock=clk, rank=3)
        with rec.span("outer", step=1):
            clk.advance(1.0)
            with rec.span("inner"):
                clk.advance(0.25)
            clk.advance(1.0)
        inner, outer = rec.events()  # inner exits (records) first
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["depth"] == 1 and inner["parent"] == "outer"
        assert outer["depth"] == 0 and outer["parent"] is None
        assert inner["t"] == 11.0 and inner["dur"] == 0.25
        assert outer["t"] == 10.0 and outer["dur"] == 2.25
        assert outer["step"] == 1
        assert inner["rank"] == 3 and outer["rank"] == 3
        assert inner["seq"] < outer["seq"]

    def test_event_timestamp_override(self):
        rec = trace.TraceRecorder(clock=_FakeClock(50.0))
        assert rec.event("a")["t"] == 50.0
        assert rec.event("b", t=7.5)["t"] == 7.5

    def test_ring_keeps_newest_and_counts_drops(self):
        rec = trace.TraceRecorder(capacity=4, clock=_FakeClock())
        for i in range(6):
            rec.event(f"e{i}")
        assert [e["name"] for e in rec.events()] == ["e2", "e3", "e4",
                                                     "e5"]
        assert rec.dropped() == 2
        rec.clear()
        assert rec.events() == [] and rec.dropped() == 0

    def test_set_capacity_shrinks_to_newest_and_validates(self):
        rec = trace.TraceRecorder(capacity=8, clock=_FakeClock())
        for i in range(6):
            rec.event(f"e{i}")
        rec.set_capacity(2)
        assert [e["name"] for e in rec.events()] == ["e4", "e5"]
        with pytest.raises(ValueError, match="ring capacity"):
            rec.set_capacity(0)

    def test_module_ring_capacity_roundtrip(self, trace_on):
        old = trace.ring_capacity()
        try:
            trace.set_ring_capacity(8)
            assert trace.ring_capacity() == 8
        finally:
            trace.set_ring_capacity(old)


# ---------------------------------------------------------------------------
# serving request timelines
# ---------------------------------------------------------------------------

def _terminals(timeline):
    return [e["phase"] for e in timeline
            if e["phase"] in trace.TERMINAL_PHASES]


class TestRequestTimelines:
    def test_full_lifecycle_single_terminal(self, model, trace_on):
        cfg, params = model
        eng = _engine(cfg, params)
        rids = [eng.add_request([1, 2, 3, 4, 5], 4),
                eng.add_request([7, 8, 9], 3)]
        while eng.has_work():
            eng.step()
        for rid in rids:
            tl = eng.request_timeline(rid)
            phases = [e["phase"] for e in tl]
            assert phases[0] == "queued"
            assert "admitted" in phases
            assert "prefill" in phases
            assert "first_token" in phases
            assert _terminals(tl) == ["finish"]
            ts = [e["t"] for e in tl]
            assert ts == sorted(ts)  # record order is time order

    def test_queue_prefill_sum_to_ttft(self, model, trace_on):
        cfg, params = model
        clk = _FakeClock(50.0)
        eng = _engine(cfg, params, clock=clk)
        rid = eng.add_request([1, 2, 3, 4, 5, 6], 4)
        while eng.has_work():
            clk.advance(0.01)
            eng.step()
        first = {}
        for e in eng.request_timeline(rid):
            first.setdefault(e["phase"], e)
        queue_s = first["admitted"]["t"] - first["queued"]["t"]
        prefill_s = first["first_token"]["t"] - first["admitted"]["t"]
        rep = eng.slo_report()
        bd = rep["breakdown"]
        assert bd["samples"] == 1
        assert bd["queue_p95_s"] == pytest.approx(queue_s)
        assert bd["prefill_p95_s"] == pytest.approx(prefill_s)
        assert bd["queue_p95_s"] + bd["prefill_p95_s"] == pytest.approx(
            rep["ttft_p95_s"])

    def test_preemption_readmission_timeline(self, model, trace_on):
        # chaos steals every free page mid-decode: the victim's
        # timeline shows preempted -> admitted(readmission) and still
        # exactly one terminal event
        cfg, params = model
        eng = _engine(cfg, params, max_running=2)
        rids = [eng.add_request(list(range(1, 8)), 6) for _ in range(2)]
        with chaos.installed(
                chaos.Chaos("exhaust@serve.step:step=2,times=1")) as c:
            for _ in range(7):
                eng.step()
            c.release_exhausted()
            while eng.has_work():
                eng.step()
        timelines = [eng.request_timeline(r) for r in rids]
        assert any("preempted" in [e["phase"] for e in tl]
                   for tl in timelines)
        for tl in timelines:
            assert _terminals(tl) == ["finish"]
            readmits = [e for e in tl if e["phase"] == "admitted"
                        and e.get("readmission")]
            if "preempted" in [e["phase"] for e in tl]:
                assert readmits

    def test_crash_recovery_replay_timeline(self, model, trace_on):
        cfg, params = model
        eng = _engine(cfg, params)
        rids = [eng.add_request([1 + i, 2, 3], 4) for i in range(3)]
        with chaos.installed(
                chaos.Chaos("fail@serve.step:step=2,times=1")):
            while eng.has_work():
                eng.step()
        evs = trace.events()
        assert any(e["name"] == "serve/recovery" for e in evs)
        assert {e["rid"] for e in evs if e.get("phase") == "replay"}
        for rid in rids:
            assert _terminals(eng.request_timeline(rid)) == ["finish"]

    def test_deadline_expiry_dumps_timeline_incident(self, model,
                                                     trace_on):
        cfg, params = model
        wdog.clear_incidents()
        clk = _FakeClock(0.0)
        eng = _engine(cfg, params, clock=clk)
        rid = eng.add_request([1, 2, 3, 4], 8, deadline_s=0.5)
        clk.advance(1.0)
        eng.step()  # expires at the step boundary
        tl = eng.request_timeline(rid)
        phases = [e["phase"] for e in tl]
        assert "deadline_expired" in phases
        assert _terminals(tl) == ["failed"]
        assert not eng.has_work()
        recs = [r for r in wdog.incidents()
                if r["kind"] == "serve_deadline_expired"]
        assert recs and recs[-1]["rid"] == rid
        # the post-mortem incident carries the request's own timeline
        assert [e["phase"] for e in recs[-1]["timeline"]] == phases
        wdog.clear_incidents()


# ---------------------------------------------------------------------------
# multi-rank merge + sidecars
# ---------------------------------------------------------------------------

def _two_rank_events(skew=100.0):
    per_rank = {}
    for r in (0, 1):
        clk = _FakeClock(10.0 + r * skew)
        rec = trace.TraceRecorder(clock=clk, rank=r)
        rec.event("warm")
        clk.advance(0.5)
        rec.barrier("train/step0")
        clk.advance(0.1 * (r + 1))
        rec.event("work")
        per_rank[r] = rec.events()
    return per_rank


class TestMultiRankMerge:
    def test_merge_aligns_on_shared_barrier(self):
        merged = trace.merge_ranks(_two_rank_events(skew=100.0))
        bar = {e["rank"]: e["t"] for e in merged
               if e["kind"] == "barrier"}
        # rank 1's clock ran 100s ahead; alignment lands both barriers
        # at the reference (rank 0) timestamp
        assert bar[0] == bar[1] == pytest.approx(10.5)
        works = sorted((e["t"], e["rank"]) for e in merged
                       if e["name"] == "work")
        assert works == [(pytest.approx(10.6), 0),
                         (pytest.approx(10.7), 1)]

    def test_merge_without_shared_barrier_keeps_clocks(self):
        per_rank = _two_rank_events(skew=100.0)
        per_rank[1] = [e for e in per_rank[1]
                       if e.get("kind") != "barrier"]
        merged = trace.merge_ranks(per_rank)
        w1 = [e for e in merged if e["name"] == "work"
              and e["rank"] == 1]
        assert w1[0]["t"] == pytest.approx(110.7)  # unshifted

    def test_sidecar_roundtrip_and_merge(self, tmp_path):
        per_rank = _two_rank_events()
        paths = []
        for r, evs in per_rank.items():
            p = trace.sidecar_path(str(tmp_path), rank=r)
            assert trace.write_sidecar(p, evs=evs, rank=r,
                                       extra={"job": "t"}) == p
            paths.append(p)
        header, evs = trace.read_sidecar(paths[1])
        assert header["schema"] == trace.SCHEMA
        assert header["rank"] == 1 and header["job"] == "t"
        assert [e["name"] for e in evs] == ["warm", "train/step0",
                                            "work"]
        merged = trace.merge_sidecars(paths)
        assert merged == trace.merge_ranks(per_rank)

    def test_read_sidecar_rejects_bad_input(self, tmp_path):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        with pytest.raises(ValueError, match="empty"):
            trace.read_sidecar(str(empty))
        corrupt = tmp_path / "corrupt.jsonl"
        corrupt.write_text("{not json\n")
        with pytest.raises(ValueError, match="corrupt"):
            trace.read_sidecar(str(corrupt))
        wrong = tmp_path / "wrong.jsonl"
        wrong.write_text(json.dumps({"schema": "other.v9"}) + "\n")
        with pytest.raises(ValueError, match="not a"):
            trace.read_sidecar(str(wrong))


# ---------------------------------------------------------------------------
# measured overlap == static simulator (bit-equal, tolerance 0)
# ---------------------------------------------------------------------------

class TestMeasuredOverlap:
    @pytest.mark.parametrize("pp,n_micro,overlap", [
        (2, 4, True), (2, 4, False), (4, 8, True), (4, 8, False)])
    def test_recorded_schedule_matches_simulator(self, pp, n_micro,
                                                 overlap, trace_on):
        n = trace.record_pipeline_schedule(pp, n_micro,
                                           overlap=overlap, step=0)
        static = ov.schedule_events(pp, n_micro, overlap=overlap)
        assert n == len(static)
        measured = trace.pipeline_schedule_events()
        # the ISSUE acceptance: bit-equal including ordering, no
        # tolerance — the recorder stores the scheduled units verbatim
        assert measured == static
        rep = ov.measured_overlap(measured)
        assert rep["transfer_stats"] == ov.transfer_stats(static)
        assert rep["overlap_fraction"] == ov.overlap_fraction(static)
        assert rep["overlap_fraction"] == (1.0 if overlap else 0.0)
        meta = [e for e in trace.events()
                if e["kind"] == "pipeline_meta"]
        assert len(meta) == 1
        assert meta[0]["pp"] == pp and meta[0]["n_micro"] == n_micro
        assert meta[0]["overlap"] is overlap and meta[0]["n_events"] == n

    def test_step_filter_separates_recordings(self, trace_on):
        trace.record_pipeline_schedule(2, 2, overlap=True, step=0)
        trace.record_pipeline_schedule(2, 2, overlap=False, step=1)
        s0 = trace.pipeline_schedule_events(step=0)
        s1 = trace.pipeline_schedule_events(step=1)
        assert s0 == ov.schedule_events(2, 2, overlap=True)
        assert s1 == ov.schedule_events(2, 2, overlap=False)


# ---------------------------------------------------------------------------
# train-step spans + collective spans
# ---------------------------------------------------------------------------

class TestTrainStepSpans:
    class _P:
        dp, pp, schedule, overlap, n_microbatches = 1, 2, "1f1b", True, 4

    def test_wrapped_step_emits_span_barrier_and_schedule(self,
                                                          trace_on):
        calls = []

        def step_fn(params, opt_state, batch):
            calls.append(batch)
            return params
        step_fn.jitted = "sentinel"
        traced = plan_mod._wrap_step_tracing(self._P(), step_fn)
        assert traced.jitted == "sentinel"  # Plan attrs survive wrap
        assert traced(1, 2, 3) == 1
        assert traced(1, 2, 4) == 1
        assert calls == [3, 4]
        evs = trace.events()
        meta = [e for e in evs if e["kind"] == "pipeline_meta"]
        assert len(meta) == 1  # schedule recorded once, on step 0
        assert meta[0]["pp"] == 2 and meta[0]["overlap"] is True
        barriers = [e["name"] for e in evs if e["kind"] == "barrier"]
        assert barriers == ["train/step0", "train/step1"]
        spans = [e for e in evs if e["name"] == "train/step"]
        assert [s["step"] for s in spans] == [0, 1]
        assert spans[0]["pp"] == 2 and spans[0]["schedule"] == "1f1b"

    def test_wrapped_step_is_passthrough_when_disabled(self):
        trace.clear()

        def step_fn(params, opt_state, batch):
            return batch
        traced = plan_mod._wrap_step_tracing(self._P(), step_fn)
        assert traced(1, 2, 9) == 9
        assert trace.events() == []

    def test_collective_records_span(self, trace_on):
        dist.all_reduce(paddle.to_tensor(np.ones(4, np.float32)))
        spans = [e for e in trace.events() if e["kind"] == "span"]
        assert any(e["name"] == "collective/all_reduce" for e in spans)


# ---------------------------------------------------------------------------
# incident persistence (watchdog/health black-box sidecars)
# ---------------------------------------------------------------------------

class TestIncidentPersistence:
    def test_persist_roundtrip(self, tmp_path):
        wdog.clear_incidents()
        wdog.record_incident("unit_test_kind", detail="x")
        assert wdog._PERSIST_REGISTERED  # atexit flush armed
        out = tmp_path / "incidents_rank0.jsonl"
        assert wdog.persist_incidents(str(out)) == str(out)
        lines = [json.loads(ln)
                 for ln in out.read_text().splitlines()]
        assert lines[0]["schema"] == wdog.INCIDENT_SCHEMA
        assert lines[1]["kind"] == "unit_test_kind"
        assert lines[1]["detail"] == "x"
        wdog.clear_incidents()

    def test_persist_noop_when_empty(self, tmp_path):
        wdog.clear_incidents()
        out = tmp_path / "none.jsonl"
        assert wdog.persist_incidents(str(out)) is None
        assert not out.exists()

    def test_sidecar_path_env_overrides(self, monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_TPU_INCIDENTS_OUT",
                           str(tmp_path / "explicit.jsonl"))
        assert wdog.incident_sidecar_path() == str(
            tmp_path / "explicit.jsonl")
        monkeypatch.delenv("PADDLE_TPU_INCIDENTS_OUT")
        monkeypatch.setenv("PADDLE_TPU_INCIDENT_DIR", str(tmp_path))
        assert wdog.incident_sidecar_path() == str(
            tmp_path / "incidents_rank0.jsonl")

    def test_health_exit_persists_before_exit_fn(self, monkeypatch,
                                                 tmp_path):
        out = tmp_path / "incidents_rank0.jsonl"
        monkeypatch.setenv("PADDLE_TPU_INCIDENTS_OUT", str(out))
        wdog.clear_incidents()
        codes = []
        mon = HealthMonitor(None, 0, 1, heartbeat_interval=1e6,
                            heartbeat_timeout=1e6,
                            collective_deadline=1e6,
                            exit_fn=codes.append, dump=False)
        mon._convert("unit-test failure", propagate=False)
        assert codes == [RELAUNCH_EXIT_CODE]
        # the sidecar landed BEFORE exit (os._exit skips atexit)
        lines = [json.loads(ln)
                 for ln in out.read_text().splitlines()]
        assert lines[0]["schema"] == wdog.INCIDENT_SCHEMA
        kinds = [r["kind"] for r in lines[1:]]
        assert "health_exit" in kinds
        wdog.clear_incidents()


# ---------------------------------------------------------------------------
# per-replica router stats (metrics labels + Profiler summary rows)
# ---------------------------------------------------------------------------

class TestPerReplica:
    def test_placement_counts_and_summary_rows(self, model, trace_on,
                                               metrics_on,
                                               replica_stats):
        cfg, params = model
        a, b = _engine(cfg, params), _engine(cfg, params)
        router = serving.Router([("a", a), ("b", b)],
                                heartbeat_timeout=1e6)
        gids = [router.submit([1, 2, 3], 3) for _ in range(4)]
        router.run(max_steps=500)
        assert len(gids) == 4
        stats = router_mod._REPLICA_STATS
        assert sum(s["placed"] for s in stats.values()) == 4
        lines = router_mod.replica_summary_lines()
        assert any("replica a:" in ln for ln in lines)
        # the engine summary (Profiler "Serving" section) carries the
        # per-replica rows
        assert any("replica" in ln for ln in
                   serving.engine.summary_lines())
        snap = metrics.snapshot()
        placed = [k for k in snap
                  if k.startswith("serve_router_placed_total{")
                  and 'replica="' in k]
        assert placed and sum(snap[k] for k in placed) == 4
        assert any(e["name"] == "route/place"
                   for e in trace.events())

    def test_dead_replica_failover_counts(self, model, trace_on,
                                          metrics_on, replica_stats):
        cfg, params = model
        clk = _FakeClock()
        a, b = _engine(cfg, params), _engine(cfg, params)
        router = serving.Router([("a", a), ("b", b)], clock=clk,
                                heartbeat_timeout=5.0)
        gid = router.submit([1, 2, 3], 4)
        victim = router._requests[gid].replica
        other = "b" if victim == "a" else "a"
        router.check_health()
        clk.advance(3.0)
        router.observe_beat(other)
        clk.advance(3.0)
        assert router.check_health() == [victim]
        stats = router_mod._REPLICA_STATS
        assert stats[victim]["dead"] == 1
        assert stats[victim]["failovers"] == 1
        names = [e["name"] for e in trace.events()]
        assert "route/replica_dead" in names
        assert "route/failover" in names
        snap = metrics.snapshot()
        assert any(k.startswith("serve_failovers_total{")
                   and f'replica="{victim}"' in k for k in snap)


# ---------------------------------------------------------------------------
# Profiler.export: merged trace + process/thread metadata
# ---------------------------------------------------------------------------

class TestChromeExport:
    def test_export_merges_trace_and_names_tracks(self, tmp_path,
                                                  trace_on):
        p = prof.Profiler(timer_only=True)
        p._log_dir = str(tmp_path)
        p.start()
        with prof.RecordEvent("host_span"):
            pass
        p.stop()
        with trace.span("traced_span", step=0):
            pass
        path = p.export()
        with open(path) as f:
            evs = json.load(f)["traceEvents"]
        meta = [e for e in evs if e.get("ph") == "M"]
        procs = {e["args"]["name"] for e in meta
                 if e["name"] == "process_name"}
        # host spans keep the real pid, flight-recorder events use the
        # rank as pid — both tracks get named
        assert f"host {os.getpid()}" in procs
        assert "rank 0" in procs
        assert any(e["name"] == "thread_name" for e in meta)
        assert any(e["name"] == "traced_span" and e["ph"] == "X"
                   for e in evs)
        assert any(e["name"] == "host_span" and e["ph"] == "X"
                   for e in evs)

    def test_module_chrome_events_shapes(self):
        clk = _FakeClock(1.0)
        rec = trace.TraceRecorder(clock=clk, rank=2)
        with rec.span("s", step=3):
            clk.advance(0.5)
        rec.event("i", rid=9)
        ch = trace.chrome_events(rec.events())
        x = [e for e in ch if e["ph"] == "X"]
        i = [e for e in ch if e["ph"] == "i"]
        assert x[0]["name"] == "s" and x[0]["pid"] == 2
        assert x[0]["dur"] == pytest.approx(0.5e6)
        assert x[0]["args"]["step"] == 3  # extra fields ride in args
        assert i[0]["name"] == "i" and i[0]["args"]["rid"] == 9


# ---------------------------------------------------------------------------
# trace_report CLI (subprocess acceptance; tpu_lint exit-code contract)
# ---------------------------------------------------------------------------

def _synthetic_sidecar(path, *, drop_terminal_for=(), rank=0):
    """Two-request serving trace with exact 0.1/0.2/0.3s phase gaps
    plus one serve/step span, written as a rank sidecar."""
    clk = _FakeClock(0.0)
    rec = trace.TraceRecorder(clock=clk, rank=rank)
    rec.barrier("train/step0")
    for rid in (0, 1):
        def req(phase, **f):
            rec.event(f"serve/{phase}", kind="request", rid=rid,
                      phase=phase, **f)
        req("queued")
        clk.advance(0.1)
        req("admitted", slot=rid)
        clk.advance(0.2)
        req("prefill", tokens=4)
        req("first_token")
        clk.advance(0.3)
        req("decode", tokens=1)
        if rid not in drop_terminal_for:
            req("finish", tokens=2)
    with rec.span("serve/step", step=0):
        clk.advance(0.01)
    trace.write_sidecar(path, evs=rec.events(), rank=rank)
    return path


def _run_report(*argv):
    return subprocess.run(
        [sys.executable, TRACE_REPORT, *argv],
        capture_output=True, text=True, timeout=120)


class TestTraceReportCLI:
    def test_clean_report_exit0_breakdown_sums(self, tmp_path):
        _synthetic_sidecar(str(tmp_path / "trace_rank0.jsonl"))
        proc = _run_report(str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["n_events"] > 0 and doc["ranks"] == [0]
        req = doc["requests"]
        assert req["count"] == 2 and req["terminal"] == 2
        bd = req["breakdown"]
        assert bd["samples"] == 2
        # the acceptance invariant: components blend from the same
        # interpolated sample, so the sum is exact — not approximate
        assert bd["queue_p95_s"] + bd["prefill_p95_s"] \
            == bd["ttft_p95_s"]
        assert bd["queue_p95_s"] == pytest.approx(0.1)
        assert bd["prefill_p95_s"] == pytest.approx(0.2)
        assert "serve/step" in doc["steps"]
        assert doc["warnings"] == [] and doc["errors"] == []

    def test_missing_terminal_warns_exit1(self, tmp_path):
        _synthetic_sidecar(str(tmp_path / "trace_rank0.jsonl"),
                           drop_terminal_for=(1,))
        proc = _run_report(str(tmp_path))
        assert proc.returncode == 1, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert any("want exactly 1" in w for w in doc["warnings"])

    def test_corrupt_sidecar_exit2(self, tmp_path):
        (tmp_path / "trace_rank0.jsonl").write_text("{broken\n")
        proc = _run_report(str(tmp_path))
        assert proc.returncode == 2
        doc = json.loads(proc.stdout)
        assert doc["errors"]

    def test_no_input_exit2(self, tmp_path):
        proc = _run_report(str(tmp_path))
        assert proc.returncode == 2

    def test_chrome_export_and_request_timeline(self, tmp_path):
        _synthetic_sidecar(str(tmp_path / "trace_rank0.jsonl"))
        chrome = tmp_path / "chrome.json"
        proc = _run_report(str(tmp_path), "--chrome", str(chrome),
                           "--request", "1")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["chrome_out"] == str(chrome)
        tl = doc["request_timeline"]
        assert [e["phase"] for e in tl] == [
            "queued", "admitted", "prefill", "first_token", "decode",
            "finish"]
        with open(chrome) as f:
            ch = json.load(f)["traceEvents"]
        phs = {e["ph"] for e in ch}
        assert {"M", "X", "i"} <= phs
        assert any(e["name"] == "process_name" for e in ch)

    def test_pipeline_overlap_in_report(self, tmp_path, trace_on):
        trace.record_pipeline_schedule(2, 4, overlap=True, step=0)
        trace.write_sidecar(str(tmp_path / "trace_rank0.jsonl"),
                            rank=0)
        proc = _run_report(str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        pipe = json.loads(proc.stdout)["pipeline"]
        st = ov.transfer_stats(
            ov.schedule_events(2, 4, overlap=True))
        assert pipe["overlap_fraction"] == 1.0
        assert pipe["total_transfers"] == st["total_transfers"]
        assert pipe["serialized_transfers"] \
            == st["serialized_transfers"]
        assert pipe["pp"] == 2 and pipe["overlap"] is True

    def test_black_box_bundle(self, tmp_path):
        _synthetic_sidecar(str(tmp_path / "trace_rank0.jsonl"))
        wdog.clear_incidents()
        wdog.record_incident("bb_kind", note="n")
        inc = tmp_path / "incidents_rank0.jsonl"
        wdog.persist_incidents(str(inc))
        wdog.clear_incidents()
        bb = tmp_path / "blackbox.zip"
        proc = _run_report(str(tmp_path), "--incidents", str(inc),
                           "--black-box", str(bb))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["incidents"]["count"] == 1
        assert doc["incidents"]["by_kind"] == {"bb_kind": 1}
        with zipfile.ZipFile(bb) as z:
            names = set(z.namelist())
            assert {"report.json", "manifest.json",
                    "trace_rank0.jsonl",
                    "incidents_rank0.jsonl"} <= names
            manifest = json.loads(z.read("manifest.json"))
            assert manifest["schema"] == "paddle_tpu.blackbox.v1"
            assert manifest["n_incidents"] == 1
            inner = json.loads(z.read("report.json"))
            assert inner["requests"]["count"] == 2

    def test_multi_rank_merge_alignment(self, tmp_path):
        # rank 1's clock runs 100s ahead; the shared train/step0
        # barrier realigns it, so both ranks' steps interleave
        _synthetic_sidecar(str(tmp_path / "trace_rank0.jsonl"), rank=0)
        clk = _FakeClock(100.0)
        rec = trace.TraceRecorder(clock=clk, rank=1)
        rec.barrier("train/step0")
        with rec.span("serve/step", step=0):
            clk.advance(0.02)
        trace.write_sidecar(str(tmp_path / "trace_rank1.jsonl"),
                            evs=rec.events(), rank=1)
        proc = _run_report(str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ranks"] == [0, 1]
        steps = doc["steps"]["serve/step"]
        assert steps["count"] == 2
        assert set(steps["ranks"]) == {"0", "1"}


# ---------------------------------------------------------------------------
# the new tool stays lint-clean (tier-1 ratchet covers paddle_tpu/;
# tools/ needs its own sweep)
# ---------------------------------------------------------------------------

def test_trace_report_tool_is_lint_clean():
    from paddle_tpu.analysis import ast_checks
    findings = list(ast_checks.check_paths([TRACE_REPORT]))
    assert findings == [], [f"{f.rule} {f.where}: {f.message}"
                            for f in findings]
