"""Global flag registry.

Reference analog: the gflags-backed exported-flag system
(paddle/phi/core/flags.cc, PADDLE_DEFINE_EXPORTED_*) surfaced to Python as
paddle.set_flags / paddle.get_flags. Flags here are plain Python values with
env-var (FLAGS_*) initialization, matching the reference's startup parsing
(paddle/fluid/platform/init.cc).
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}


def define_flag(name: str, default: Any, help_str: str = ""):
    """Register a flag. Env var of the same name overrides the default."""
    val = default
    env = os.environ.get(name)
    if env is not None:
        if isinstance(default, bool):
            val = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            val = int(env)
        elif isinstance(default, float):
            val = float(env)
        else:
            val = env
    _REGISTRY[name] = val
    return val


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if k not in _REGISTRY:
            raise KeyError(f"Unknown flag {k!r}; registered: {sorted(_REGISTRY)}")
        _REGISTRY[k] = v


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    return {k: _REGISTRY[k] for k in flags}


def flag(name: str):
    return _REGISTRY[name]


# Core flags (subset of the reference's 89 exported flags that are meaningful
# on the TPU stack; see paddle/phi/core/flags.cc).
define_flag("FLAGS_check_nan_inf", False, "Scan op outputs for NaN/Inf in eager mode.")
define_flag("FLAGS_benchmark", False, "Synchronize after each op (block_until_ready).")
define_flag("FLAGS_cudnn_deterministic", False, "Determinism knob (XLA is deterministic by default).")
define_flag("FLAGS_use_autotune", True, "Enable kernel autotuning where applicable.")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "Kept for API parity; XLA manages buffers.")
define_flag("FLAGS_allocator_strategy", "auto_growth", "Kept for API parity; PJRT allocates.")
define_flag("FLAGS_log_level", 0, "Framework verbose log level (VLOG analog).")
define_flag("FLAGS_tpu_metrics", False,
            "Enable the profiler.metrics registry (counters/gauges/"
            "histograms on optimizer, collectives, dataloader, predictor). "
            "Off: every recording call is a dict lookup + bool check.")
define_flag("FLAGS_tpu_metrics_port", 0,
            "Serve live observability over HTTP (profiler.exporter): "
            "/metrics (Prometheus text), /healthz, /slo, /incidents, "
            "/trace/tail. 0 disables (the check is one dict lookup); "
            "-1 binds an ephemeral port; >0 binds that port, falling "
            "back to an ephemeral one if it is taken.")
define_flag("FLAGS_tpu_check_nan_inf", False,
            "Framework-wide numerics watchdog: check_numerics sites and "
            "to_static output checks scan for NaN/Inf, with first-bad-op "
            "localization on failure (profiler.numerics). Off: every "
            "instrumented site is a dict lookup + bool check.")
define_flag("FLAGS_tpu_lint", False,
            "Run the static-analysis suite (paddle_tpu.analysis jaxpr "
            "checks) on every new to_static trace signature: host "
            "callbacks in loop bodies, f64 promotion, int32-overflow "
            "reductions, oversized baked constants, unusable donations, "
            "collective divergence. Findings land in the Profiler 'Lint' "
            "section and lint_findings_total metrics. Off: zero per-call "
            "overhead (the check sits inside the new-signature branch; "
            "its gate is one dict lookup + bool check).")
define_flag("FLAGS_tpu_watchdog", False,
            "Runtime health layer (paddle_tpu.runtime): phase watchdogs "
            "with faulthandler dumps on expiry, cross-rank heartbeat "
            "failure detection, and collective entry/exit beacons that "
            "convert a hung peer into an exit-101 elastic relaunch "
            "within the configured deadline. Off: every hook is a "
            "module-global None check.")
define_flag("FLAGS_tpu_watchdog_device_init", 240.0,
            "Deadline (s) for the device_init watchdog phase — the "
            "budget for claiming a backend before the attempt is "
            "declared hung. <=0 disables.")
define_flag("FLAGS_tpu_watchdog_compile", 600.0,
            "Deadline (s) for the compile watchdog phase (trace + XLA "
            "compile of one executable). <=0 disables.")
define_flag("FLAGS_tpu_watchdog_first_step", 300.0,
            "Deadline (s) for the first_step watchdog phase (first "
            "post-compile step, which still pays transfer/warmup "
            "costs). <=0 disables.")
define_flag("FLAGS_tpu_watchdog_collective", 120.0,
            "Deadline (s) a rank may spend inside one collective before "
            "the health monitor declares a CollectiveTimeout and "
            "converts it to an exit-101 relaunch. <=0 disables.")
define_flag("FLAGS_tpu_watchdog_ckpt_commit", 300.0,
            "Deadline (s) for the ckpt.commit watchdog phase (the "
            "atomic checkpoint rename + fsync protocol). <=0 disables.")
define_flag("FLAGS_tpu_watchdog_serve_step", 120.0,
            "Deadline (s) for one serving engine step (serve.step "
            "watchdog phase): schedule + compiled forward + commit. A "
            "step past the deadline is treated as a hung device call "
            "and converted into the engine's pool-rebuild replay "
            "recovery. <=0 disables.")
define_flag("FLAGS_tpu_trace", False,
            "Structured event/span tracing (profiler.trace flight "
            "recorder): ring-buffered request-lifecycle, train-step, "
            "pipeline-schedule, and collective events with rank-tagged "
            "JSONL sidecars for tools/trace_report.py. Off: every "
            "recording call is a dict lookup + bool check.")
define_flag("FLAGS_tpu_xmem", False,
            "Capture per-executable memory_analysis()/cost_analysis() "
            "(HBM peaks, temp bytes, flops) at every jit/Executor/"
            "Predictor compile. Implied by FLAGS_tpu_metrics. New "
            "signatures compile via the AOT path so capture never "
            "double-compiles.")
