"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
prints: device busy time, time per device operation, the longest idle gaps.

Written against a real trace of this repo's train step and engine steps on
a TPU v5 lite (PR 24), a cut of which is the fixture of this file's test:

* the device is the plane ``/device:TPU:0`` (the first TPU plane, by
  number). Its line ``XLA Ops`` holds one event per device operation; its
  lines ``XLA Modules`` and ``Steps`` hold whole programs and ``Async XLA
  Ops`` the copies in flight under the operations — all of those cover the
  same stretches again, so only ``XLA Ops`` is read, never a sum over lines;
* events of ``XLA Ops`` NEST: a ``while`` (the scan over layers) spans the
  operations of its body, so the line's durations add up to about twice the
  time it covers. Busy time is therefore the UNION of the events'
  intervals, clipped to the slice, and the time of one operation is its
  SELF time: its duration less that of the events nested directly in it;
* an event's name is the whole HLO instruction, kilobytes long. Its short
  name is the instruction's own name and opcode (``%fusion.271 fusion``);
  a Mosaic (Pallas) kernel is an event whose instruction is a custom call
  with the target ``tpu_custom_call`` — the kernel function's name is not in
  the trace, so kernels are told from other operations, not from each other;
* the slice is the span of the host annotation ``SLICE_NAME`` on the
  profiler's own clock, which the device plane shares (the first operation
  of the first real trace starts 1.1 ms after the annotation opens).

Nothing here defaults: no device plane, no operation line, no event in the
slice, or a busy time above the slice is an error that names what was
found.
"""
from __future__ import annotations

import glob
import os
import re
import sys

OPS_LINE = "XLA Ops"
SLICE_NAME = "benchmark_slice"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")


class TraceError(RuntimeError):
    """The trace does not hold what the reduction needs."""


def union_s(intervals) -> float:
    """Total length, in the intervals' unit, covered by at least one of
    ``intervals`` (``(start, end)`` pairs)."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def short_name(name: str) -> str:
    """``%fusion.271 fusion`` from a whole HLO instruction; a custom call
    also names its target."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:80]
    op = _OPCODE.search(rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join(filter(None, (head, op and op.group(1),
                                  target and target.group(1))))[:80]


def self_times(events) -> list:
    """``(self time, name)`` of each of ``events`` (``(start, end, name)``,
    properly nested or disjoint): its length less that of the events nested
    directly inside it."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [b - a for a, b, _ in events]
    stack = []                                  # indices of open events
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return [(t, e[2]) for t, e in zip(own, events)]


def device_plane(planes: dict) -> str:
    """Name of the first TPU plane of ``planes`` (``{name: lines}``)."""
    tpus = sorted((n for n in planes if n.startswith("/device:TPU:")),
                  key=lambda n: int(n.rsplit(":", 1)[1].split()[0]))
    if not tpus:
        raise TraceError(f"no TPU plane in the trace; its planes are "
                         f"{sorted(planes)}")
    return tpus[0]


def reduce_events(planes: dict, slice_ns=None) -> dict:
    """The reduction itself, on plain data: ``planes`` maps a plane's name
    to ``{line name: [(event name, start_ns, duration_ns), ...]}``.
    ``slice_ns`` is ``(start, end)`` of the slice; ``None`` finds the host
    annotation ``SLICE_NAME``."""
    if slice_ns is None:
        spans = [(s, s + d) for lines in planes.values()
                 for events in lines.values()
                 for name, s, d in events if name == SLICE_NAME]
        if len(spans) != 1:
            raise TraceError(f"{len(spans)} host annotations named "
                             f"{SLICE_NAME!r} in the trace, not one")
        slice_ns = spans[0]
    lo, hi = slice_ns
    plane = device_plane(planes)
    if OPS_LINE not in planes[plane]:
        raise TraceError(f"plane {plane} has no line {OPS_LINE!r}; its "
                         f"lines are {sorted(planes[plane])}")
    clipped = sorted((max(s, lo), min(s + d, hi), name)
                     for name, s, d in planes[plane][OPS_LINE]
                     if s < hi and s + d > lo and d > 0)
    if not clipped:
        raise TraceError(f"line {OPS_LINE!r} of plane {plane} has no event "
                         f"inside the slice {lo}..{hi} ns")
    busy_ns = union_s((a, b) for a, b, _ in clipped)
    window_ns = hi - lo
    if not 0 < busy_ns <= window_ns:
        raise TraceError(f"busy time {busy_ns} ns is not within the slice "
                         f"of {window_ns} ns")

    by_name, mosaic_ns = {}, 0.0
    for t, name in self_times(clipped):
        short = short_name(name)
        by_name[short] = by_name.get(short, 0.0) + t
        if MOSAIC_TARGET in name:
            mosaic_ns += t
    # idle gaps: between the running end of everything before and the next
    # start, named by the operations on either side (what the host did
    # meanwhile has no span on this clock yet) and added up by name
    gaps, end, last = {}, lo, "slice start"

    def gap(length, name):
        n, t = gaps.get(name, (0, 0.0))
        gaps[name] = (n + 1, t + length)

    for a, b, name in clipped:
        if a > end:
            gap(a - end, f"{last} -> {short_name(name)}")
        if b > end:
            end, last = b, short_name(name)
    if hi > end:
        gap(hi - end, f"{last} -> slice end")
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "plane": plane,
        "events": len(clipped),
        "mosaic_s": mosaic_ns / 1e9,
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[f"x{n} {name}", t / 1e9] for name, (n, t) in sorted(
            gaps.items(), key=lambda kv: -kv[1][1])],
    }


def read_planes(profile_dir: str) -> dict:
    """``{plane: {line: [(name, start_ns, duration_ns)]}}`` of the one
    ``*.xplane.pb`` under ``profile_dir``."""
    import jax
    found = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise TraceError(f"{len(found)} *.xplane.pb files under "
                         f"{profile_dir}, not one")
    data = jax.profiler.ProfileData.from_file(found[0])
    return {plane.name: {line.name: [(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
                         for line in plane.lines}
            for plane in data.planes}


def reduce_dir(profile_dir: str, host_window_s: float) -> dict:
    """Reduce the trace under ``profile_dir``. ``host_window_s`` is the
    slice's length on the host's clock: the annotation has to agree with
    it, or the two clocks are not the ones this file was written for."""
    out = reduce_events(read_planes(profile_dir))
    if abs(out["window_s"] - host_window_s) > 0.05 * host_window_s + 0.01:
        raise TraceError(f"the slice is {out['window_s']:.4f} s in the "
                         f"trace and {host_window_s:.4f} s on the host")
    return out


def dump(profile_dir: str, top: int = 20) -> str:
    """A trace's planes and lines with their event counts and extent, and
    each line's ``top`` longest event names: what to read by hand before
    trusting the reduction."""
    rows = []
    for plane, lines in read_planes(profile_dir).items():
        rows.append(f"PLANE {plane!r}: {len(lines)} lines")
        for line, events in lines.items():
            if not events:
                rows.append(f"  LINE {line!r}: no events")
                continue
            lo = min(s for _, s, _ in events)
            hi = max(s + d for _, s, d in events)
            total = sum(d for _, _, d in events)
            rows.append(f"  LINE {line!r}: {len(events)} events, "
                        f"{lo:.0f}..{hi:.0f} ns, sum of durations "
                        f"{total:.0f} ns, union "
                        f"{union_s((s, s + d) for _, s, d in events):.0f} ns")
            by_name = {}
            for name, _, d in events:
                n, t = by_name.get(name, (0, 0.0))
                by_name[name] = (n + 1, t + d)
            for name, (n, t) in sorted(by_name.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"      {t:14.0f} ns  x{n:<6d} {name}")
    return "\n".join(rows)


if __name__ == "__main__":
    print(dump(sys.argv[1]))
