"""What the per-layer readers of PR 25 share: the traced slice read again
WITH each event's stats, so that the program's own names are on it.

``trace_reduce`` gives the slice's busy time and its operations by HLO
instruction name. The program also writes names of its own into the same
profile (``paddle_tpu/profiler/trace.py``, ``ops/pallas_ops.py``,
``models/llama.py``):

* host spans: ``jax.profiler.TraceAnnotation`` events on the host plane's
  line of the main thread, named ``serve/engine_step``, ``serve/schedule``,
  ``serve/batch``, ``serve/step``, ``serve/dispatch``, ``serve/fetch``,
  ``serve/commit`` and ``train/step``; their arguments are the event's
  stats (``fed_tokens``, ``slot_tokens``, ``kv_tokens``, ``qk_pairs``...);
* device scopes: every event of the device plane's ``XLA Ops`` line carries
  the ``jax.named_scope`` path of its HLO instruction in the stat ``tf_op``
  of the event's metadata (``jit(serve_step_tc16)/layers/while/body/
  closed_call/attn/pallas/_rpa_kernel/pallas_call:``; read on the first
  real trace of this PR, and by ``benchmark/xplane.py`` because
  ``jax.profiler.ProfileData`` leaves an event's metadata stats out); a
  Pallas kernel is under ``pallas/<kernel function name>``, forward work
  run again in the backward pass under ``rematted_computation``.

The run dict that a reader is handed carries neither the checkout's root
nor the profile directory, so :func:`traced` takes the root as ``run.py``
does (the directory above this one) and finds the newest ``*.xplane.pb``
under its ``.bench_trace/`` that was written after this process began; a
profile that is not there is an error, not a default. The functions below
it take plain data or a directory, so that the tests hand them one. On a
program that has no such span or scope (the parent commit) a reader finds
nothing and returns ``None``.

The two clocks are not one. The device line of a profile leads the host
line by an offset that is constant within a profile and differs between
profiles (1.25 ms and 0.28 ms in the first two of this PR): every program
"starts" on the device that long BEFORE the host thread enqueued it
(``DoEnqueueProgram``, matched by ``run_id`` to the program's event on the
line ``XLA Modules``), and "ends" 1.7-1.9 ms before the host's
``CompleteCallbacks``. :attr:`Slice.lead_ns` is the least shift under which
no program starts before it was enqueued; the attribution of idle time
moves the host's spans back by it. Sums of device time need no shift.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import re
import statistics
import sys

import psutil

from benchmark import trace_reduce, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_PREFIXES = ("serve/", "train/")
FORWARD_SPAN = "serve/step"          # the guarded forward
ENGINE_SPAN = "serve/engine_step"    # the whole of LLMEngine.step
MODULES_LINE = "XLA Modules"         # one event per program run, by run_id
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"   # host, run_id
REMAT = "rematted_computation"       # forward work run again in backward
SCOPE_STAT = "tf_op"                 # of a device event's metadata
_KERNEL = re.compile(r"pallas/(\w+)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # ns on the profiler's clock
    end: float
    stats: dict

    @property
    def ns(self) -> float:
        return self.end - self.start


def read_planes(path: str) -> dict:
    """``{plane: {line: [Event]}}`` of the ``*.xplane.pb`` file ``path``."""
    return {plane: {line: [Event(name, start, start + dur, stats)
                           for name, start, dur, stats in events]
                    for line, events in lines.items()}
            for plane, lines in xplane.read(path).items()}


def newest_trace(profile_root: str, since: float) -> str:
    """The newest ``*.xplane.pb`` under ``profile_root`` written at or
    after ``since`` (seconds of the epoch)."""
    found = [p for p in glob.glob(os.path.join(
        profile_root, "**", "*.xplane.pb"), recursive=True)
        if os.path.getmtime(p) >= since]
    if not found:
        raise trace_reduce.TraceError(
            f"no *.xplane.pb under {profile_root} was written since this "
            "process began")
    return max(found, key=os.path.getmtime)


@functools.lru_cache(maxsize=2)
def _parsed(path: str, mtime: float) -> "Slice":
    return Slice.of(read_planes(path))


def traced(run: dict):
    """The :class:`Slice` of the profile this process wrote, parsed once;
    ``None`` for a run that was not traced."""
    if not run.get("trace"):
        return None
    path = newest_trace(os.path.join(ROOT, ".bench_trace"),
                        psutil.Process().create_time())
    return _parsed(path, os.path.getmtime(path))


def in_dir(profile_dir: str) -> "Slice":
    """The :class:`Slice` of the one profile under ``profile_dir``."""
    return Slice.of(read_planes(newest_trace(profile_dir, 0.0)))


def _clip(events, lo, hi):
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in events if e.start < hi and e.end > lo and e.end > e.start]


def merged(intervals) -> list:
    """``intervals`` (``(start, end)`` pairs) as disjoint ones, in order."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def complement(intervals, lo, hi) -> list:
    """The stretches of ``lo..hi`` that none of ``intervals`` covers."""
    gaps, end = [], lo
    for a, b in merged(intervals):
        if a > end:
            gaps.append((end, min(a, hi)))
        end = max(end, b)
    if hi > end:
        gaps.append((end, hi))
    return [(a, b) for a, b in gaps if b > a]


def overlap_ns(gaps, spans) -> float:
    """Length of ``gaps`` (disjoint) covered by at least one of ``spans``
    (``(start, end)`` pairs, which may overlap each other)."""
    spans = merged(spans)
    return sum(max(0.0, min(b, d) - max(a, c))
               for a, b in gaps for c, d in spans)


def scope_of(event: Event) -> str:
    """The ``jax.named_scope`` path of a device event's HLO instruction
    ('' where the trace has none)."""
    value = event.stats.get(SCOPE_STAT)
    return value if isinstance(value, str) else ""


def kernel_of(event: Event):
    """Function name of the Pallas kernel a device event runs, from its
    scope path, or ``None``."""
    m = _KERNEL.search(scope_of(event))
    return m.group(1) if m else None


def is_mosaic(event: Event) -> bool:
    return trace_reduce.MOSAIC_TARGET in event.name


@dataclasses.dataclass
class Slice:
    """The traced slice: its bounds, the program's host spans inside it,
    and the device's operations clipped to it with their self times."""
    lo: float
    hi: float
    spans: list          # Event: program spans that touch the slice
    ops: list            # Event: device operations clipped to the slice
    self_ns: list        # self time of each of ``ops``, same order
    modules: list        # Event: whole programs on the device, unclipped
    lead_ns: float       # the device line leads the host line by this much
    lead_max_ns: float   # ... and by no more than this (inf: not known)
    reported: bool = False   # its tables are on stdout already

    @classmethod
    def of(cls, planes: dict) -> "Slice":
        marks = [e for lines in planes.values() for events in lines.values()
                 for e in events if e.name == trace_reduce.SLICE_NAME]
        if len(marks) != 1:
            raise trace_reduce.TraceError(
                f"{len(marks)} host annotations named "
                f"{trace_reduce.SLICE_NAME!r} in the trace, not one")
        lo, hi = marks[0].start, marks[0].end
        host = [e for name, lines in planes.items()
                if not name.startswith("/device:")
                for events in lines.values() for e in events]
        spans = sorted((e for e in host if e.name.startswith(SPAN_PREFIXES)
                        and e.start < hi and e.end > lo),
                       key=lambda e: (e.start, -e.end))
        try:
            device = planes[trace_reduce.device_plane(planes)]
        except trace_reduce.TraceError:
            device = {}          # the CPU of the tier-1 tests
        ops = sorted(_clip(device.get(trace_reduce.OPS_LINE, []), lo, hi),
                     key=lambda e: (e.start, -e.end))
        self_ns = [0.0] * len(ops)
        for t, i in trace_reduce.self_times(
                (e.start, e.end, i) for i, e in enumerate(ops)):
            self_ns[i] = t
        modules = [m for m in device.get(MODULES_LINE, [])
                   if m.start < hi and m.end > lo]
        # the clock offset: a program cannot start on the device before the
        # host enqueued it, nor end after the host saw it complete
        by_run = {m.stats.get("run_id"): m for m in modules}
        lead, lead_max = 0.0, float("inf")
        for e in host:
            m = by_run.get(e.stats.get("run_id"))
            if m is None:
                continue
            if e.name == ENQUEUE:
                lead = max(lead, e.start - m.start)
            elif e.name == COMPLETE:
                lead_max = min(lead_max, e.start - m.end)
        return cls(lo, hi, spans, ops, self_ns, modules, lead, lead_max)

    # -- host -----------------------------------------------------------
    def whole(self, name: str) -> list:
        """The spans named ``name`` that lie wholly inside the slice."""
        return [e for e in self.spans if e.name == name
                and e.start >= self.lo and e.end <= self.hi]

    def median_ms(self, name: str):
        found = self.whole(name)
        return statistics.median(e.ns for e in found) / 1e6 if found else None

    def host_table(self) -> dict:
        """``{span name: count, median and total ms}`` of the spans wholly
        inside the slice."""
        table = {}
        for name in sorted({e.name for e in self.spans}):
            ns = [e.ns for e in self.whole(name)]
            if ns:
                table[name] = {"n": len(ns), "median_ms":
                               round(statistics.median(ns) / 1e6, 4),
                               "total_ms": round(sum(ns) / 1e6, 3)}
        return table

    def step_args(self) -> list:
        """The integer arguments of each ``serve/engine_step`` span wholly
        inside the slice that fed the device (an idle step has none)."""
        return [{k: int(v) for k, v in e.stats.items()
                 if isinstance(v, int)
                 or (isinstance(v, str) and v.lstrip("-").isdigit())}
                for e in self.whole(ENGINE_SPAN) if "fed_tokens" in e.stats]

    # -- device idle time, by what the host was doing --------------------
    def idle_ns(self):
        """Device idle time in the slice, split by the program span open on
        the host meanwhile: ``forward`` under ``serve/step``, ``engine_host``
        under any other ``serve/*`` span, ``caller`` under none. The gaps
        are those of ``trace_reduce`` (the slice less the union of the
        device's operations), so the three add up to its idle time; the
        host's spans are moved back by ``lead_ns`` onto the device's clock.
        ``None`` where the slice holds no such span or no device
        operation."""
        def on_device_clock(pred):
            return [(e.start - self.lead_ns, e.end - self.lead_ns)
                    for e in self.spans if pred(e.name)]

        steps = on_device_clock(lambda n: n == FORWARD_SPAN)
        engine = on_device_clock(lambda n: n.startswith("serve/"))
        if not steps or not self.ops:
            return None
        gaps = complement([(e.start, e.end) for e in self.ops],
                          self.lo, self.hi)
        total = sum(b - a for a, b in gaps)
        forward = overlap_ns(gaps, steps)
        under_engine = overlap_ns(gaps, engine)
        return {"forward": forward, "engine_host": under_engine - forward,
                "caller": total - under_engine, "total": total}

    def programs_inside_forward(self):
        """``(inside, of)``: how many of the programs wholly inside the
        slice start and end, on the host's clock (moved by ``lead_ns``),
        inside one ``serve/step`` span."""
        forward = [e for e in self.spans if e.name == FORWARD_SPAN]
        whole = [m for m in self.modules
                 if m.start >= self.lo and m.end <= self.hi]
        inside = sum(any(s.start <= m.start + self.lead_ns
                         and m.end + self.lead_ns <= s.end for s in forward)
                     for m in whole)
        return inside, len(whole)

    # -- device time, by the program's names -----------------------------
    def self_ns_where(self, pred) -> float:
        """Self time of the device operations ``pred`` holds for."""
        return sum(t for e, t in zip(self.ops, self.self_ns) if pred(e))

    def self_ns_by(self, key) -> dict:
        """``{key(event): self ns}``, longest first; a ``None`` key is
        left out."""
        out = {}
        for e, t in zip(self.ops, self.self_ns):
            k = key(e)
            if k is not None:
                out[k] = out.get(k, 0.0) + t
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


_LAYER_WORDS = ("embed", "layers", "attn", "kv_write", "mlp", "lm_head",
                "sample", "optimizer")


def layer_of(event: Event) -> str:
    """A short key for a device event's scope path: backward
    (``transpose(``), recompute (``rematted_computation``), the layer
    scopes and the kernel, as far as the path names them."""
    scope = scope_of(event)
    if not scope:
        return "(no scope)"
    parts = ["bwd"] if "transpose(" in scope else []
    if REMAT in scope:
        parts.append("remat")
    words = set(re.split(r"[/()\s]+", scope))
    parts += [w for w in _LAYER_WORDS if w in words]
    kernel = kernel_of(event)
    if kernel:
        parts.append(kernel)
    return "/".join(parts) or "(other)"


_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_TUPLE_OK = ("copy-start", "copy-done")


def result_shapes(instruction: str) -> list:
    """Dimensions of the result of a whole HLO instruction (an event's
    name): one tuple of ints, or for a ``copy-start``/``copy-done`` one per
    element of its result; ``[]`` for any other tuple."""
    _, _, rest = instruction.partition(" = ")
    op = trace_reduce._OPCODE.search(rest)
    if not op:
        return []
    result = rest[:op.start()].strip()
    if result.startswith("(") and op.group(1) not in _TUPLE_OK:
        return []
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in _SHAPE.findall(result)]


def is_pool_copy(event: Event, shapes) -> bool:
    """A non-Mosaic operation whose result has one of ``shapes``
    (``kernel_costs.pool_shapes``)."""
    return not is_mosaic(event) and any(
        s in shapes for s in result_shapes(event.name))


# -- what the readers in layers/ call ---------------------------------------

def _say(what: str, table) -> None:
    print(f"bench: {what}: {json.dumps(table)}", flush=True)


def report(sl: Slice, steps: int) -> None:
    """The slice's named tables on earlier lines of stdout, once a run:
    PERF.md section 5 is written from these."""
    if sl.reported:
        return
    sl.reported = True
    per = 1e6 * max(steps, 1)

    def ms_a_step(table):
        return {k: round(v / per, 3) for k, v in table.items()}

    _say("host_spans", sl.host_table())
    idle = sl.idle_ns()
    if idle:
        _say(f"idle_ms_per_step ({steps} steps)",
             {k: round(v / per, 4) for k, v in idle.items()})
    if sl.ops:
        _say(f"device_by_kernel (ms a step, {steps} steps)",
             ms_a_step(sl.self_ns_by(kernel_of)))
        _say(f"device_by_scope (ms a step, {steps} steps)",
             ms_a_step(sl.self_ns_by(layer_of)))
        _say("mosaic", {
            "custom_calls": sum(map(is_mosaic, sl.ops)),
            "self_ms": round(sl.self_ns_where(is_mosaic) / 1e6, 3),
            "of_which_under_no_pallas_scope_ms": round(sl.self_ns_where(
                lambda e: is_mosaic(e) and not kernel_of(e)) / 1e6, 3)})
    if sl.modules:
        clocks = {"device_leads_host_ms": round(sl.lead_ns / 1e6, 4),
                  "and_by_no_more_than_ms": None
                  if sl.lead_max_ns == float("inf")
                  else round(sl.lead_max_ns / 1e6, 4)}
        if idle:
            inside, of = sl.programs_inside_forward()
            clocks.update(programs_wholly_in_slice=of,
                          of_which_inside_a_serve_step_span=inside)
        _say("clocks", clocks)


def span_median_ms(run: dict, name: str):
    """Median length in ms of the spans ``name`` wholly inside the slice."""
    sl = traced(run)
    if sl is None:
        return None
    report(sl, run["counters"].get("trace_steps", 0))
    return sl.median_ms(name)


def idle_ms_per_step(run: dict, part: str):
    """``part`` of :meth:`Slice.idle_ns` over ``counters.trace_steps``, the
    count ``host_gap_ms_per_step`` divides by, in ms."""
    sl, steps = traced(run), run["counters"].get("trace_steps")
    if sl is None or not steps:
        return None
    report(sl, steps)
    idle = sl.idle_ns()
    return None if idle is None else idle[part] / steps / 1e6


def self_ms_per_step(run: dict, pred, steps_key: str):
    """Self time of the device operations ``pred`` holds for, over the
    count ``run["counters"][steps_key]``, in ms; ``None`` where there is no
    such operation."""
    sl, steps = traced(run), run["counters"].get(steps_key)
    if sl is None or not steps:
        return None
    report(sl, steps)
    ns = sl.self_ns_where(pred)
    return ns / steps / 1e6 if ns else None


def kernel_ms_per_step(run: dict, kernels, steps_key: str):
    """Self time under the ``pallas/<kernel>`` scopes of ``kernels``."""
    return self_ms_per_step(run, lambda e: kernel_of(e) in kernels,
                            steps_key)


def dump_stats(profile_dir: str, per_line: int = 12) -> str:
    """One event of each kind on every line of a trace, with its stats:
    what to read by hand before trusting :func:`scope_of`."""
    rows = []
    planes = read_planes(newest_trace(profile_dir, 0.0))
    for plane, lines in planes.items():
        rows.append(f"PLANE {plane!r}")
        for line, events in lines.items():
            rows.append(f"  LINE {line!r}: {len(events)} events")
            seen = {}
            for e in events:
                # an instruction's kind is its opcode (and target), any
                # other event's its name up to its arguments
                short = trace_reduce.short_name(e.name)
                kind = short.partition(" ")[2] if " = " in e.name \
                    else short.partition("(")[0]
                if kind not in seen and len(seen) < per_line:
                    seen[kind] = e
            for kind, e in seen.items():
                stats = {k: (v if not isinstance(v, str) else v[:400])
                         for k, v in e.stats.items()}
                rows.append(f"    {kind}: name={e.name[:160]!r} "
                            f"start={e.start:.0f} ns={e.ns:.0f} "
                            f"stats={json.dumps(stats, default=str)}")
    return "\n".join(rows)


if __name__ == "__main__":
    print(dump_stats(sys.argv[1]))
