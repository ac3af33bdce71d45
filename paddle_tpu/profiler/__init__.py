"""Profiler.

Reference analog: python/paddle/profiler/profiler.py:344 (Profiler with
make_scheduler state machine, chrome-trace export) over the C++ HostTracer/
CudaTracer (paddle/fluid/platform/profiler/). TPU-native: jax.profiler
(xprof) captures device traces; RecordEvent instruments host spans into the
same trace via jax.profiler.TraceAnnotation AND into a self-contained
host-span buffer that `export_chrome_tracing` serializes as Chrome
`trace_event` JSON — so traces work on CPU CI with no xprof attached.

Telemetry siblings in this package:
  metrics.py          — Counter/Gauge/Histogram registry (FLAGS_tpu_metrics)
  compile_tracker.py  — jax.monitoring compile/retrace accounting
  xmem.py             — per-executable memory/cost analysis capture
  numerics.py         — NaN/Inf watchdog + first-bad-op localization
                        (FLAGS_tpu_check_nan_inf)
  trace.py            — structured event/span flight recorder with
                        JSONL sidecars (FLAGS_tpu_trace)
  exporter.py         — live HTTP observability endpoint: /metrics,
                        /healthz, /slo, /incidents, /trace/tail
                        (FLAGS_tpu_metrics_port)
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Optional

import jax

from . import metrics
from . import compile_tracker
from . import xmem
from . import numerics
from . import trace
from . import exporter

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "make_scheduler",
           "RecordEvent", "export_chrome_tracing", "benchmark", "metrics",
           "compile_tracker", "xmem", "numerics", "trace", "exporter"]

# host-span aggregation for the summary stats table (reference:
# profiler/profiler_statistic.py — EventSummary/statistic_data tables).
# RecordEvent feeds every ACTIVE profiler's own stats dict, so
# concurrent Profiler instances don't clobber each other.
_ACTIVE_PROFILERS: list = []

# jax.monitoring listeners live for the whole process; install once here
# so compiles are counted even before the first Profiler is constructed.
compile_tracker.install()


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """reference: profiler.py:117 — step-indexed state machine."""
    if closed < 0 or ready < 0 or skip_first < 0:
        raise ValueError(
            f"make_scheduler: closed/ready/skip_first must be >= 0, got "
            f"closed={closed}, ready={ready}, skip_first={skip_first}")
    if record < 1:
        raise ValueError(
            f"make_scheduler: record must be >= 1 (a period that never "
            f"records profiles nothing), got record={record}")
    if repeat < 0:
        raise ValueError(
            f"make_scheduler: repeat must be >= 0 (0 = repeat forever), "
            f"got repeat={repeat}")
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler that writes the profiler's host-span buffer
    as a Chrome trace_event JSON file under `dir_name` (reference:
    profiler.py export_chrome_tracing). Self-contained: works with no
    xprof/TPU attached — chrome://tracing and Perfetto load the file."""

    def handler(prof):
        prof._log_dir = dir_name
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.pt.trace.json")
        prof.export(path)
    return handler


class RecordEvent:
    """Host-span annotation visible in the xprof trace
    (reference: paddle/fluid/platform/profiler/event_tracing.h) and
    buffered into every RECORD-state profiler for chrome-trace export."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ctx = None
        self._t0 = None

    def begin(self):
        self._ctx = jax.profiler.TraceAnnotation(self.name)
        self._ctx.__enter__()
        self._t0 = time.perf_counter()

    def end(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        if self._t0 is not None and _ACTIVE_PROFILERS:
            t1 = time.perf_counter()
            dt = t1 - self._t0
            event = None
            for p in _ACTIVE_PROFILERS:
                stats = p._span_stats
                calls, total, mx = stats.get(self.name, (0, 0.0, 0.0))
                stats[self.name] = (calls + 1, total + dt, max(mx, dt))
                if p._state in (ProfilerState.RECORD,
                                ProfilerState.RECORD_AND_RETURN) \
                        and len(p._trace_events) < p._trace_buffer_cap:
                    if event is None:
                        # complete ("X") event: one dict carries the
                        # begin/end pair; ts/dur are microseconds
                        event = {"name": self.name, "ph": "X",
                                 "cat": "host",
                                 "ts": self._t0 * 1e6, "dur": dt * 1e6,
                                 "pid": os.getpid(),
                                 "tid": threading.get_ident()}
                    p._trace_events.append(event)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def _record_span(name: str):
    """RecordEvent when any profiler is live, else a no-op context —
    the zero-cost guard hot paths (optimizer/collectives/io/inference)
    use so an un-profiled step pays one list truthiness check."""
    if _ACTIVE_PROFILERS:
        return RecordEvent(name)
    return contextlib.nullcontext()


def _metadata_events(events: list) -> list:
    """Chrome "M" metadata events naming every pid/tid seen in
    `events`: the host process keeps its real pid ("host <pid>"),
    structured-trace events use the rank as pid ("rank <N>"), so a
    merged multi-rank trace groups into legible Perfetto tracks."""
    host_pid = os.getpid()
    pids = []
    tids = []
    for e in events:
        pid, tid = e.get("pid"), e.get("tid")
        if pid is not None and pid not in pids:
            pids.append(pid)
        if pid is not None and tid is not None and (pid, tid) not in tids:
            tids.append((pid, tid))
    meta = []
    for pid in pids:
        name = f"host {pid}" if pid == host_pid else f"rank {pid}"
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": name}})
    for pid, tid in tids:
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": f"thread {tid}"}})
    return meta


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self._scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, ready=0,
                                             record=hi - lo, repeat=1)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._log_dir = os.environ.get("PADDLE_TPU_PROFILE_DIR",
                                       "/tmp/paddle_tpu_profile")
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._active = False
        self._step_times = []
        self._span_stats: dict = {}
        self._trace_events: list = []
        self._trace_buffer_cap = int(os.environ.get(
            "PADDLE_TPU_TRACE_BUFFER_CAP", "1000000"))
        self._last = None

    def start(self):
        self._state = self._scheduler(self._step) if self._scheduler \
            else ProfilerState.RECORD
        if self._state in (ProfilerState.RECORD,
                           ProfilerState.RECORD_AND_RETURN) \
                and not self._timer_only:
            jax.profiler.start_trace(self._log_dir)
            self._active = True
        self._span_stats.clear()
        self._trace_events.clear()
        if self not in _ACTIVE_PROFILERS:
            _ACTIVE_PROFILERS.append(self)
        self._last = time.perf_counter()

    def stop(self):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
        if self in _ACTIVE_PROFILERS:
            _ACTIVE_PROFILERS.remove(self)
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self._step_times.append(now - self._last)
        self._last = now
        self._step += 1
        if self._scheduler is None:
            return
        new_state = self._scheduler(self._step)
        if new_state != self._state:
            recording = self._state in (ProfilerState.RECORD,
                                        ProfilerState.RECORD_AND_RETURN)
            will_record = new_state in (ProfilerState.RECORD,
                                        ProfilerState.RECORD_AND_RETURN)
            if will_record and not self._active and not self._timer_only:
                jax.profiler.start_trace(self._log_dir)
                self._active = True
            if recording and not will_record and self._active:
                jax.profiler.stop_trace()
                self._active = False
            self._state = new_state

    def export(self, path: Optional[str] = None):
        """Write the buffered host spans as a Chrome trace_event file
        (the `{"traceEvents": [...]}` object form). Structured events
        from `profiler.trace` (when FLAGS_tpu_trace is on) are merged
        into the same file, and process_name/thread_name metadata
        events label every pid/tid so multi-rank merged traces read as
        named tracks in Perfetto. Returns the path."""
        if path is None:
            path = os.path.join(self._log_dir,
                                f"host_{os.getpid()}.pt.trace.json")
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        events = list(self._trace_events)
        if trace.enabled():
            events.extend(trace.chrome_events())
        payload = {
            "traceEvents": _metadata_events(events) + events,
            "displayTimeUnit": "ms",
            "metadata": {"producer": "paddle_tpu.profiler",
                         "steps": self._step},
        }
        with open(path, "w") as f:
            json.dump(payload, f)
        return path

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        units = {"s": 1.0, "ms": 1e3, "us": 1e6, "ns": 1e9}
        u = unit if unit in units else "ms"
        scale = units[u]
        arr = np.asarray(self._step_times[-100:])
        return (f"avg step: {arr.mean() * scale:.2f} {u}, "
                f"ips: {1.0 / max(arr.mean(), 1e-9):.2f} steps/s")

    def _compilation_section(self) -> list:
        """The "Compilation" block of summary_table: backend compiles,
        cumulative compile seconds, per-function retrace attribution."""
        st = compile_tracker.stats()
        lines = ["Compilation",
                 f"  backend compiles: {st['compile_count']}  "
                 f"(cumulative {st['compile_seconds']:.3f} s)",
                 f"  jaxpr traces: {st['trace_count']}  "
                 f"(cumulative {st['trace_seconds']:.3f} s)"]
        if st["persistent_cache_hits"] or st["persistent_cache_misses"]:
            lines.append(
                f"  persistent cache: {st['persistent_cache_hits']} hits / "
                f"{st['persistent_cache_misses']} misses")
        fns = st["functions"]
        if fns:
            lines.append(f"  traced functions: {len(fns)}, "
                         f"retraces: {st['retraces']}")
            worst = sorted(fns.items(), key=lambda kv: -kv[1]["traces"])[:5]
            for name, e in worst:
                mark = "  <-- RETRACING" if e["retraces"] else ""
                lines.append(f"    {name[:48]:<48} {e['traces']:>4} traces "
                             f"({e['retraces']} retraces){mark}")
        return lines

    def summary_table(self, sorted_by="total", time_unit="ms") -> str:
        """Host-span stats table (reference:
        profiler_statistic.py _build_table): name / calls / total / avg /
        max / % of wall, plus the Compilation section."""
        units = {"s": 1.0, "ms": 1e3, "us": 1e6, "ns": 1e9}
        unit = units.get(time_unit, 1e3)
        if time_unit not in units:
            time_unit = "ms"
        wall = sum(self._step_times) or sum(
            t for _, t, _ in self._span_stats.values()) or 1e-12
        rows = [(name, c, tot, tot / c, mx)
                for name, (c, tot, mx) in self._span_stats.items()]
        key = {"total": 2, "calls": 1, "avg": 3, "max": 4}.get(sorted_by, 2)
        rows.sort(key=lambda r: -r[key])
        header = (f"{'Name':<32}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                  f"{'Avg(' + time_unit + ')':>12}"
                  f"{'Max(' + time_unit + ')':>12}{'Ratio%':>8}")
        lines = ["-" * len(header), header, "-" * len(header)]
        for name, c, tot, avg, mx in rows:
            lines.append(
                f"{name[:32]:<32}{c:>8}{tot * unit:>14.3f}"
                f"{avg * unit:>12.3f}{mx * unit:>12.3f}"
                f"{100.0 * tot / wall:>8.1f}")
        lines.append("-" * len(header))
        lines.extend(self._compilation_section())
        lines.append("-" * len(header))
        lines.extend(xmem.summary_lines())
        lines.append("-" * len(header))
        lines.extend(numerics.summary_lines())
        lines.append("-" * len(header))
        from ..ops import autotune as _autotune
        lines.extend(_autotune.summary_lines())
        lines.append("-" * len(header))
        from ..analysis import core as _lint_core
        lines.extend(_lint_core.summary_lines())
        lines.append("-" * len(header))
        from ..distributed import fault_tolerance as _ft
        lines.extend(_ft.summary_lines())
        lines.append("-" * len(header))
        from .. import runtime as _runtime
        lines.extend(_runtime.summary_lines())
        lines.append("-" * len(header))
        from ..serving import engine as _serving
        lines.extend(_serving.summary_lines())
        lines.append("-" * len(header))
        from ..serving import autoscale as _autoscale
        lines.extend(_autoscale.fleet_summary_lines())
        lines.append("-" * len(header))
        if self._step_times:
            lines.append(self.step_info(time_unit))
        return "\n".join(lines)

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms"):
        print(self.summary_table(sorted_by=sorted_by if isinstance(
            sorted_by, str) else "total", time_unit=time_unit), flush=True)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class benchmark:
    """reference: profiler/timer.py (Benchmark.step_info — reader-cost +
    ips over a moving window)."""

    def __init__(self):
        self._times = []
        self._samples = []
        self._last = None

    def begin(self):
        self._last = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            self._times.append(now - self._last)
            self._samples.append(num_samples or 1)
        self._last = now

    def end(self):
        pass

    def report(self):
        import numpy as np
        arr = np.asarray(self._times or [0.0])
        n = float(np.sum(self._samples)) if self._samples else 0.0
        total = float(np.sum(arr)) or 1e-12
        return {"avg_s": float(arr.mean()), "steps": len(self._times),
                "p50_s": float(np.percentile(arr, 50)),
                "p95_s": float(np.percentile(arr, 95)),
                "max_s": float(arr.max()),
                "ips": n / total,
                "steps_per_sec": len(self._times) / total}
