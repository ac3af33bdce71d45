"""Shared XLA_FLAGS composition for virtual-CPU-mesh entry points.

stdlib-only and importable BEFORE jax (XLA reads the env at backend
init). Single source for the collective-watchdog timeouts: the CPU
in-process collective rendezvous ABORTS the process ("Termination
timeout ... Expected N threads to join") when virtual-device threads
are slow to arrive — which on an oversubscribed CI host is load, not
deadlock.

XLA fatally aborts on *unknown* XLA_FLAGS; both timeout flags are known
to the installed jaxlib (0.9.0: a CPU backend starts and runs a
collective with them set), so they are always added.
"""
from __future__ import annotations

import os

_TIMEOUT_FLAGS = (
    " --xla_cpu_collective_call_warn_stuck_timeout_seconds=300"
    " --xla_cpu_collective_call_terminate_timeout_seconds=1200")


def ensure(device_count: int | None = None) -> None:
    """Idempotently add the watchdog timeouts (and optionally the
    virtual device count) to XLA_FLAGS. Call before importing jax."""
    flags = os.environ.get("XLA_FLAGS", "")
    if device_count and "host_platform_device_count" not in flags:
        flags += f" --xla_force_host_platform_device_count={device_count}"
    if "collective_call_terminate_timeout" not in flags:
        flags += _TIMEOUT_FLAGS
    os.environ["XLA_FLAGS"] = flags.strip()
