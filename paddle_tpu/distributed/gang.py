"""Real multi-process gang runtime: Plan execution across process
boundaries.

Reference analog: the launch controller + fleet elastic manager pair —
a pod of gang-scheduled trainer processes where any worker death tears
the pod down and the manager relaunches it as a unit. PR 13's Plan
reproduced the schedule and overlap inside ONE process; this module
promotes it to an actual ``python -m paddle_tpu.distributed.launch``
pod: N worker processes rendezvous over the launcher's TCPStore,
bootstrap ``jax.distributed`` (gloo CPU collectives on the test
backend, ICI on real TPU slices), and each rank binds its
HealthMonitor / Watchdog / TraceRecorder to its real pid.

One rank's lifecycle::

    ctx = gang.init_gang()              # store + jax.distributed + mesh
                                        # + health monitor, all wired
    plan = Plan(...)                    # any Plan; world = all procs
    with ctx.running():                 # failure -> save -> exit 101
        plan.run_train_loop(cfg, batches, on_step=ctx.step_boundary,
                            ckpt_root=ctx.config.ckpt_root)
    ctx.shutdown(0)                     # sidecars + ordered teardown

Failure semantics (the headline): when a REAL peer dies or hangs
mid-collective, every surviving rank detects it within the heartbeat /
collective-beacon deadline (runtime/health.py, PR 7), writes a final
step-boundary checkpoint from the state snapshot ``step_boundary``
handed over, flushes its incident + trace sidecars, and exits 101 —
the cooperative relaunch code the elastic launcher honors without
burning restart budget. The relaunched generation restores through
``reshard.restore_resharded`` (possibly at a different world size) and
resumes the trajectory.

The flight recorder is the correctness oracle: each rank writes a
trace sidecar ending in the :data:`profiler.trace.TERMINAL_BARRIER`
barrier; ``tools/trace_report.py --gang`` merges the per-rank sidecars
and fails the run when any rank's recorded 1F1B schedule diverges from
the static ``overlap.schedule_events`` model, or any rank is missing
its sidecar / terminal barrier.

``python -m paddle_tpu.distributed.gang`` is the runnable preset: the
multichip llama config driven through ``Plan.run_train_loop``
under a real gang, printing one ``GANG_RESULT {json}`` line per rank.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import socket
import sys
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional

from ..profiler import trace as _trace
from ..runtime import health as _health
from ..runtime.watchdog import (Watchdog, incidents, persist_incidents,
                                record_incident)
from ..testing import chaos as _chaos

__all__ = ["GangConfig", "GangContext", "init_gang", "main"]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@dataclasses.dataclass
class GangConfig:
    """Tunables for one gang worker. ``from_env`` reads the
    ``PTQ_GANG_*`` overrides the launcher/test environment passes down
    (every knob also has a constructor default sized for real pods —
    tests shrink the deadlines to keep E2Es fast)."""

    heartbeat_interval: float = 1.0
    heartbeat_timeout: float = 5.0
    collective_deadline: Optional[float] = None  # None -> watchdog flag
    straggler_skew: int = 5
    rendezvous_timeout: float = 60.0
    coordinator_host: str = "127.0.0.1"
    trace_dir: Optional[str] = None
    ckpt_root: Optional[str] = None
    # chaos `kill` rules become os._exit (sudden real peer death) rather
    # than an in-process ReplicaKilled exception
    process_kill_mode: bool = True
    # also beat the fleet.elastic hb keys so a launcher started with
    # --heartbeat_timeout can declare the whole pod hung
    launcher_heartbeat: bool = True

    _ENV = {
        "PTQ_GANG_HEARTBEAT_INTERVAL": ("heartbeat_interval", float),
        "PTQ_GANG_HEARTBEAT_TIMEOUT": ("heartbeat_timeout", float),
        "PTQ_GANG_COLLECTIVE_DEADLINE": ("collective_deadline", float),
        "PTQ_GANG_STRAGGLER_SKEW": ("straggler_skew", int),
        "PTQ_GANG_RENDEZVOUS_TIMEOUT": ("rendezvous_timeout", float),
        "PTQ_GANG_COORD_HOST": ("coordinator_host", str),
        "PTQ_GANG_TRACE_DIR": ("trace_dir", str),
        "PTQ_GANG_CKPT_ROOT": ("ckpt_root", str),
    }

    @classmethod
    def from_env(cls, **overrides) -> "GangConfig":
        kw: Dict[str, Any] = {}
        for var, (field, cast) in cls._ENV.items():
            # one-shot bootstrap read, not a hot path
            raw = os.environ.get(var)  # tpu-lint: disable=flag-lookup-in-loop
            if raw:
                kw[field] = cast(raw)
        kw.update({k: v for k, v in overrides.items() if v is not None})
        return cls(**kw)


class GangContext:
    """One rank's handle on a live gang: the rendezvous store, the
    health monitor bound to this process, the final-save snapshot box,
    and the teardown protocol."""

    def __init__(self, config: GangConfig, store, rank: int,
                 world_size: int, restart: int, job_id: str,
                 owns_store: bool = False):
        self.config = config
        self.store = store
        self.rank = int(rank)
        self.world_size = int(world_size)
        self.restart = int(restart)
        self.job_id = job_id
        self.pid = os.getpid()
        self.monitor: Optional[_health.HealthMonitor] = None
        self.watchdog: Optional[Watchdog] = None
        self._owns_store = owns_store
        self._hb_stop = None
        self._final_box: Dict[str, Any] = {}
        self._finalized = False

    # -- training-loop integration ------------------------------------------

    def step_boundary(self, step: int, params=None, opt_state=None):
        """Per-step hook (``run_train_loop(on_step=...)`` shape): stamp
        the health step, hand the just-completed state to the
        final-save box, record the step barrier, and pass through the
        gang's per-step sync point.

        Ordering matters: the step stamp and the state snapshot land
        BEFORE the eager ``all_reduce`` below — that call is identity
        outside a trace but fires the health collective beacon and the
        ``collective.all_reduce`` chaos point, so a ``kill@``/``hang@``
        rule matching this step bites a rank whose snapshot already
        holds this step's state (survivors and self-detectors then
        final-save exactly the crash-step checkpoint)."""
        if self.monitor is not None:
            self.monitor.set_step(int(step))
        else:
            _health.set_step(int(step))
        if params is not None:
            self._final_box = {"step": int(step), "params": params,
                               "opt_state": opt_state}
        _trace.barrier(f"gang/step{step}")
        import numpy as np
        from ..core.tensor import to_tensor
        from .collective import all_reduce
        all_reduce(to_tensor(np.zeros((), np.float32)))

    def final_save(self):
        """Write the last step-boundary snapshot as a committed
        checkpoint. Runs on the MONITOR thread during failure
        conversion (the main thread may be hung inside a collective),
        so it only touches state handed over at step boundaries —
        already-computed arrays that fetch without any collective."""
        box = self._final_box
        root = self.config.ckpt_root
        if not box or not root:
            return
        if self.world_size > 1:
            # gang coordination: first claimant owns the step's save —
            # survivors all hold identical (replicated) state, so one
            # commit suffices and concurrent commits to one root would
            # race on the step's tmp dir. Store down -> save anyway:
            # worst case is a racy duplicate, never a lost checkpoint.
            try:
                claim = self.store.add(
                    f"gang/save/{self.restart}/{box['step']}", 1)
                if claim > 1:
                    return
            except Exception:  # tpu-lint: disable=except-pass
                pass
        import jax
        from .fault_tolerance import CheckpointManager
        from .reshard import host_full
        state = {
            "params": jax.tree_util.tree_map(host_full, box["params"]),
            "opt_state": jax.tree_util.tree_map(host_full,
                                                box["opt_state"]),
        }
        CheckpointManager(root, backend="pickle",
                          sync=True).save(box["step"], state)

    @contextmanager
    def running(self):
        """Scope the training loop: an exception escaping it (a gloo
        collective erroring out under a dead peer, a poisoned step)
        converts to the save-and-exit-101 path instead of an arbitrary
        crash code."""
        try:
            yield self
        except SystemExit:
            raise
        except BaseException as exc:  # noqa: B036 — must catch KeyboardInterrupt too
            self.abort(f"{type(exc).__name__}: {exc}")

    # -- failure conversion --------------------------------------------------

    def abort(self, reason: str):
        """Main-thread failure path: record, then route through the
        monitor's conversion (final save + gang fail flag + incident
        flush + exit 101). If another thread already converted, wait
        for its exit; a hard exit-101 backstop guarantees this call
        never returns."""
        record_incident("gang_abort", reason=str(reason)[-500:],
                        gang_rank=self.rank)
        m = self.monitor
        if m is not None:
            m._convert(f"rank {self.rank}: {reason}")
            # _convert returned -> a conversion is already in flight on
            # the monitor thread; give it time to save and exit us
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                time.sleep(0.1)
        try:
            self.final_save()
        except Exception as e:
            record_incident("final_save_failed", error=str(e)[-500:])
        try:
            persist_incidents()
        except OSError:
            pass
        os._exit(_health.RELAUNCH_EXIT_CODE)

    # -- teardown ------------------------------------------------------------

    def finalize(self, status: str = "ok"):
        """Flush this rank's flight-recorder sidecar (terminal barrier
        last) and stop the background threads. Idempotent; does not
        exit. The incident buffer is only persisted when non-empty so a
        clean relaunched generation never clobbers the previous
        generation's post-mortem files."""
        if self._finalized:
            return
        self._finalized = True
        if _trace.enabled():
            _trace.barrier(_trace.TERMINAL_BARRIER, status=status,
                           step=(self._final_box or {}).get("step"))
            if self.config.trace_dir:
                os.makedirs(self.config.trace_dir, exist_ok=True)
                _trace.write_sidecar(
                    _trace.sidecar_path(self.config.trace_dir, self.rank),
                    extra={"world_size": self.world_size,
                           "restart": self.restart, "status": status})
        if self._hb_stop is not None:
            self._hb_stop.set()
        if self.monitor is not None:
            self.monitor.stop()
            if _health.get() is self.monitor:
                _health.uninstall()
        if incidents():
            try:
                persist_incidents()
            except OSError:
                pass

    def shutdown(self, exit_code: int = 0):
        """Orderly gang teardown: finalize sidecars, align every rank
        on the exit barrier, then detach from the store and the jax
        coordination service (whose own shutdown barrier holds the
        coordinator open until every client disconnected)."""
        self.finalize(status="ok" if exit_code == 0
                      else f"exit{exit_code}")
        if self.world_size > 1:
            try:
                self.store.barrier(f"gang/done/{self.restart}",
                                   rank=self.rank,
                                   timeout=self.config.rendezvous_timeout)
            except Exception as e:  # peers died mid-exit: still leave
                sys.stderr.write(f"gang: exit barrier skipped: {e}\n")
        try:
            self.store.close()
        except Exception:  # tpu-lint: disable=except-pass
            pass
        from .parallel import shutdown as _dist_shutdown
        _dist_shutdown()


def _init_jax_distributed(store, rank: int, world: int, restart: int,
                          cfg: GangConfig):
    """Multi-client bootstrap: rank 0 publishes a coordinator address
    on the rendezvous store, every rank joins ``jax.distributed``. On
    the CPU test backend cross-process collectives need the gloo
    implementation — selected here iff the backend is not yet
    initialized (tier-1 in-process callers skip this whole path)."""
    import jax
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # backend already initialized or option not present
    except Exception:  # tpu-lint: disable=except-pass
        pass
    key = f"gang/coord/{restart}"
    if rank == 0:
        coord = f"{cfg.coordinator_host}:{_free_port()}"
        store.set(key, coord.encode())
    else:
        coord = store.wait(key, cfg.rendezvous_timeout).decode()
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=world, process_id=rank)
    except RuntimeError as e:
        if "already" not in str(e).lower():
            raise
    if jax.process_count() != world:
        raise RuntimeError(
            f"gang bootstrap mismatch: jax sees "
            f"{jax.process_count()} processes, launcher promised {world}")


def init_gang(config: Optional[GangConfig] = None) -> GangContext:
    """Bring this process up as one rank of a real gang.

    Reads the launcher env contract (PADDLE_TRAINER_ID /
    PADDLE_TRAINERS_NUM / PADDLE_MASTER / PADDLE_RESTART_COUNT), joins
    the rendezvous store, runs the named-rank boot barrier (a wedged
    peer is called out BY RANK in the TimeoutError), bootstraps
    ``jax.distributed`` + the global mesh, and starts the
    HealthMonitor bound to this real pid. Single-process (world 1, no
    PADDLE_MASTER) degrades to a self-owned store with the same API so
    unit tests and notebooks run the identical code path."""
    cfg = config if config is not None else GangConfig.from_env()
    env = os.environ
    rank = int(env.get("PADDLE_TRAINER_ID", "0"))
    world = int(env.get("PADDLE_TRAINERS_NUM", "1"))
    restart = int(env.get("PADDLE_RESTART_COUNT", "0"))
    job_id = env.get("PADDLE_JOB_ID", "gang")
    master = env.get("PADDLE_MASTER")

    if cfg.process_kill_mode:
        _chaos.set_kill_mode("process")

    from .store import TCPStore
    owns = False
    if master and world > 1:
        host, port = master.rsplit(":", 1)
        store = TCPStore(host, int(port), is_master=False,
                         world_size=world,
                         timeout=cfg.rendezvous_timeout)
    else:
        store = TCPStore("127.0.0.1", 0, is_master=True,
                         world_size=world,
                         timeout=cfg.rendezvous_timeout)
        owns = True

    wd = Watchdog(deadlines={"gang.rendezvous": cfg.rendezvous_timeout})
    with wd.phase("gang.rendezvous"):
        store.barrier(f"gang/boot/{restart}", rank=rank,
                      timeout=cfg.rendezvous_timeout)
        if world > 1:
            _init_jax_distributed(store, rank, world, restart, cfg)
    from . import parallel as _parallel
    from .mesh import init_mesh
    init_mesh()
    # later init_parallel_env() calls must no-op: the gang already owns
    # the jax.distributed bootstrap (re-initializing would fail)
    _parallel._INITIALIZED[0] = True

    ctx = GangContext(cfg, store, rank, world, restart, job_id,
                      owns_store=owns)
    ctx.watchdog = wd

    monitor = _health.HealthMonitor(
        store, rank, world, job_id=job_id, restart=restart,
        heartbeat_interval=cfg.heartbeat_interval,
        heartbeat_timeout=cfg.heartbeat_timeout,
        collective_deadline=cfg.collective_deadline,
        straggler_skew=cfg.straggler_skew)
    monitor.register_final_save(ctx.final_save)
    _health.install(monitor)
    monitor.start()
    ctx.monitor = monitor

    if cfg.launcher_heartbeat and master and world > 1:
        from .fleet.elastic import start_heartbeat
        ctx._hb_stop = start_heartbeat(cfg.heartbeat_interval,
                                       store=store)

    _trace.barrier(f"gang/boot{restart}", rank_pid=ctx.pid)
    return ctx


# ---------------------------------------------------------------------------
# runnable preset: the bench multichip llama config under a real gang
# ---------------------------------------------------------------------------

def _preset_result(ctx: GangContext, plan, history,
                   step_ms: float) -> Dict[str, Any]:
    from .overlap import schedule_events
    matches = None
    if _trace.enabled() and plan.pp > 1:
        recorded = _trace.pipeline_schedule_events(_trace.events())
        static = schedule_events(plan.pp,
                                 plan.n_microbatches or plan.pp,
                                 overlap=plan.overlap)
        matches = recorded == static
    return {
        "rank": ctx.rank, "pid": ctx.pid,
        "world_size": ctx.world_size, "restart": ctx.restart,
        "plan": plan.dims, "schedule": plan.schedule,
        "n_microbatches": plan.n_microbatches,
        "overlap": plan.overlap,
        "steps": len(history["losses"]),
        "losses": [float(x) for x in history["losses"]],
        "step_ms": round(step_ms, 2),
        "matches_static": matches,
    }


def main(argv=None) -> int:
    """``python -m paddle_tpu.distributed.gang``: run the multichip
    llama preset through ``Plan.run_train_loop`` under a real gang and
    print one ``GANG_RESULT {json}`` line (parsed by the gang E2E
    tests). The pipeline spans
    the processes: with N ranks of one device each, ``pp=N`` 1F1B p2p
    crosses real process boundaries."""
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.gang")
    p.add_argument("--steps", type=int, default=2)
    p.add_argument("--trace-out", default=None,
                   help="flight-recorder sidecar dir (enables tracing)")
    p.add_argument("--ckpt-root", default=None)
    p.add_argument("--n-micro", type=int, default=4)
    p.add_argument("--no-overlap", action="store_true")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=32)
    args = p.parse_args(argv)

    from ..core.flags import set_flags
    set_flags({"FLAGS_tpu_trace": args.trace_out is not None})

    cfg = GangConfig.from_env(trace_dir=args.trace_out,
                              ckpt_root=args.ckpt_root)
    ctx = init_gang(cfg)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..models.llama import LlamaConfig
    from .plan import Plan

    ndev = jax.device_count()
    model_cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=64,
        dtype=jnp.float32, use_remat=False)
    if ndev > 1:
        plan = Plan(pp=ndev, schedule="1f1b",
                    n_microbatches=args.n_micro,
                    overlap=not args.no_overlap)
    else:
        plan = Plan()

    rng = np.random.default_rng(0)
    B, S = args.batch, args.seq
    batches = [{
        "input_ids": rng.integers(0, model_cfg.vocab_size, (B, S),
                                  dtype=np.int32),
        "labels": rng.integers(0, model_cfg.vocab_size, (B, S),
                               dtype=np.int32),
    } for _ in range(args.steps)]

    t0 = time.perf_counter()
    with ctx.running():
        history = plan.run_train_loop(
            model_cfg, batches, on_step=ctx.step_boundary,
            ckpt_root=args.ckpt_root, verify=False)
    step_ms = (time.perf_counter() - t0) / max(1, args.steps) * 1e3

    result = _preset_result(ctx, plan, history, step_ms)
    print("GANG_RESULT " + json.dumps(result, sort_keys=True),
          flush=True)
    ctx.shutdown(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
