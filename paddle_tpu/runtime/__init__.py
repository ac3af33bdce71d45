"""Runtime health layer: phase watchdogs, heartbeats, hang recovery.

Hang defenses as one shared subsystem: `watchdog` holds the phase-deadline machinery and
deadline executors, `health` the cross-rank heartbeat/beacon failure
detector that converts hangs into exit-101 elastic relaunches.
"""
from __future__ import annotations

from typing import List

from . import watchdog, health, rewind  # noqa: F401
from .watchdog import (PhaseTimeout, Watchdog, run_with_deadline,  # noqa: F401
                       incidents, last_incident,
                       record_incident, clear_incidents,
                       persist_incidents, incident_sidecar_path)
from .health import (CollectiveTimeout, HealthMonitor,  # noqa: F401
                     HeartbeatTracker, collective_beacon)
from .rewind import (RewindBudgetExceeded, RewindResult,  # noqa: F401
                     RewindGuard)

__all__ = ["watchdog", "health", "rewind", "PhaseTimeout", "Watchdog",
           "run_with_deadline", "incidents",
           "last_incident", "record_incident", "clear_incidents",
           "persist_incidents", "incident_sidecar_path",
           "CollectiveTimeout", "HealthMonitor", "HeartbeatTracker",
           "collective_beacon",
           "RewindBudgetExceeded", "RewindResult",
           "RewindGuard", "summary_lines"]


def summary_lines() -> List[str]:
    """The "Health" block of ``Profiler.summary_table()``: watchdog
    flag state, monitor state (when installed), and the tail of the
    incident buffer."""
    from ..core.flags import flag
    lines: List[str] = ["Health"]
    mon = health.get()
    if mon is None:
        state = "on" if flag("FLAGS_tpu_watchdog") else "off"
        lines.append(f"  monitor: not installed (FLAGS_tpu_watchdog "
                     f"{state})")
    else:
        lines.extend("  " + ln for ln in mon.summary_lines())
    recs = incidents()
    if not recs:
        lines.append("  incidents: none")
        return lines
    lines.append(f"  incidents: {len(recs)} (last {min(len(recs), 5)}):")
    for rec in recs[-5:]:
        extra = {k: v for k, v in rec.items()
                 if k not in ("kind", "time", "rank")}
        detail = ", ".join(f"{k}={v}" for k, v in sorted(extra.items()))
        lines.append(f"    {rec['kind']} (rank {rec['rank']}"
                     + (f": {detail}" if detail else "") + ")")
    return lines
