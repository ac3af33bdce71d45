"""First-class parallelism plans: one object from planner to compiled step.

A :class:`Plan` names everything needed to run a training step on a pod:
the mesh axis degrees (``dp/pp/sharding/sp/mp``), the pipeline schedule
and microbatch count, whether compute/communication overlap is enabled,
and (optionally) the per-parameter partition specs in the portable JSON
form ``reshard.spec_to_json`` emits.

Three ways in, one way out:

* ``Plan(dp=2, pp=2, schedule="1f1b", overlap=True)`` — by hand.
* ``Plan.from_report(report_or_path)`` — load the winning topology from a
  ``tools/pod_report.py`` report (or from the executable spec its
  ``--plan-out`` flag writes), so planner → compile → run is one path.
* ``Plan.load(path)`` / ``Plan.from_spec(dict)`` — round-trip the spec.

Out: ``plan.train_step(cfg)`` builds the llama training step for the
plan's topology, and the generic ``plan.compile(fn, ...)`` follows the
Titanax selection rule: explicit ``in_shardings`` **and**
``out_shardings`` → compiler-placed ``jax.jit`` (pjit); only one of them
→ error (half-specified placement silently degrades to GSPMD guessing);
``in_specs``/``out_specs`` → per-device ``shard_map`` for map-style
collectives; neither → plain ``jit``.

Every compiled plan can be gated through the SPMD collective-consistency
checker (``verify=True``, default follows ``FLAGS_tpu_lint``): the step
is traced to a jaxpr and the Level-3 rules (divergent collectives,
rank-dependent loops, axis misuse) must come back clean before the first
real execution.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from ..profiler import exporter as _exporter
from ..profiler import trace as _trace

__all__ = ["Plan", "PlanError", "PlanCompilationError",
           "PlanVerificationError", "SCHEDULES"]

SCHEDULES = ("none", "gpipe", "1f1b", "interleaved")

AXES = ("dp", "pp", "sharding", "sp", "mp")


class PlanError(Exception):
    """Base for plan construction/compilation/verification failures."""


class PlanCompilationError(PlanError):
    """The compile request is inconsistent (e.g. half-specified
    shardings, or both shardings and specs)."""


class PlanVerificationError(PlanError):
    """The SPMD checker found error-severity findings in the compiled
    step's jaxpr."""

    def __init__(self, findings):
        self.findings = list(findings)
        lines = "; ".join(f"{f.rule}: {f.message}" for f in self.findings)
        super().__init__(
            f"SPMD verification failed with {len(self.findings)} "
            f"error finding(s): {lines}")


def _as_sharding_tree(tree, mesh):
    """Bind a pytree of PartitionSpecs (or already-built Shardings) to
    ``mesh``. Leaves that are PartitionSpecs become NamedShardings; JSON
    spec lists are rebound with missing axes dropped (→ replicated)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def seq(x):
        return isinstance(x, (list, tuple)) and not isinstance(x, P)

    def json_spec(leaf):
        # reshard JSON form: per dim None or a list of axis names. Any
        # other list/tuple is a container of specs (one per argument)
        return seq(leaf) and all(
            d is None or (seq(d) and all(isinstance(a, str) for a in d))
            for d in leaf)

    def bind(leaf):
        if isinstance(leaf, P):
            return NamedSharding(mesh, leaf)
        if json_spec(leaf):
            from .reshard import _rebind_spec, spec_from_json
            return NamedSharding(
                mesh, spec_from_json(_rebind_spec(list(leaf), mesh)))
        return leaf   # None, or a Sharding built by the caller

    return jax.tree_util.tree_map(
        bind, tree,
        is_leaf=lambda l: l is None or isinstance(l, P) or json_spec(l))


def _error_findings(findings):
    return [f for f in findings if getattr(f, "severity", "") == "error"]


def _put_global(arr, sharding):
    """Place one host array under ``sharding`` — single- OR multi-process
    safe. ``jax.device_put`` can only target addressable devices; in a
    real gang every rank materializes the same deterministic global host
    array and contributes just its addressable shards via
    ``make_array_from_callback`` (the standard multi-controller feeding
    pattern)."""
    import jax
    import numpy as np
    arr = np.asarray(arr)
    # match device_put's dtype canonicalization (int64 -> int32 with x64
    # off); make_array_from_callback feeds raw host bytes to XLA, where
    # a non-canonical dtype corrupts the runtime instead of downcasting
    canon = jax.dtypes.canonicalize_dtype(arr.dtype)
    if arr.dtype != canon:
        arr = arr.astype(canon)
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx, _a=arr: _a[idx])


def _wrap_step_tracing(plan: "Plan", step_fn: Callable) -> Callable:
    """The trainer's ``train/step`` span, around the step's dispatch.

    Always a profiler annotation (``profiler.trace.span``). With
    ``FLAGS_tpu_trace`` on, each invocation also emits a shared-name
    barrier event (the anchor ``trace.merge_ranks`` aligns rank clocks
    on) and the span's ring event; the first traced step of a pipelined
    plan also records the static 1F1B schedule via
    ``trace.record_pipeline_schedule`` so ``tools/trace_report.py`` can
    compute measured overlap with the simulator's exact event schema.
    """
    counter = {"n": 0}
    # what the layers keep for the backward pass (llama.saved_residuals),
    # worked out once a batch shape; a pipelined step has no such rule
    residuals = getattr(step_fn, "residuals", None)
    kept = {"args": {}}

    def traced(params, opt_state, batch):
        n = counter["n"]
        counter["n"] += 1
        if _trace.enabled():
            if n == 0 and plan.pp > 1 and plan.schedule != "none":
                _trace.record_pipeline_schedule(
                    plan.pp, plan.n_microbatches or plan.pp,
                    overlap=plan.overlap, step=n)
            _trace.barrier(f"train/step{n}")
        if residuals is not None and \
                batch["input_ids"].shape != kept.get("shape"):
            names, nbytes, _ = residuals(batch["input_ids"].shape)
            kept.update(shape=batch["input_ids"].shape,
                        args={"saved": ",".join(names),
                              "saved_bytes": nbytes})
        with _trace.span("train/step", step=n, pp=plan.pp,
                         schedule=plan.schedule, **kept["args"]):
            return step_fn(params, opt_state, batch)

    _copy_step_attrs(step_fn, traced)
    return traced


def _copy_step_attrs(step_fn: Callable, wrapper: Callable) -> None:
    """What ``build_train_step`` and ``Plan.train_step`` hang on a step
    function, carried over to a wrapper of it."""
    for attr in ("jitted", "lower", "abstract_state", "batch_shardings",
                 "residuals", "plan", "plan_topology"):
        if hasattr(step_fn, attr):
            setattr(wrapper, attr, getattr(step_fn, attr))


@dataclasses.dataclass
class Plan:
    """Executable parallelism plan over the fleet's 5-axis hybrid mesh.

    ``param_specs``, when present, maps '/'-joined parameter paths to
    ``reshard.spec_to_json`` partition specs — the portable form that
    survives meshes with different axis sets (binding to a mesh that
    lacks an axis silently drops it, i.e. replicates that dimension).
    """

    dp: int = 1
    pp: int = 1
    sharding: int = 1
    sp: int = 1
    mp: int = 1
    schedule: str = "none"
    n_microbatches: Optional[int] = None
    overlap: bool = False
    param_specs: Optional[Dict[str, List[Optional[List[str]]]]] = None

    def __post_init__(self):
        if self.schedule not in SCHEDULES:
            raise PlanError(
                f"unknown schedule {self.schedule!r}; expected one of "
                f"{SCHEDULES}")
        for a in AXES:
            d = getattr(self, a)
            if not isinstance(d, int) or d < 1:
                raise PlanError(f"axis degree {a}={d!r} must be a "
                                "positive int")
        if self.schedule != "none" and self.pp == 1:
            raise PlanError(
                f"schedule={self.schedule!r} needs pp > 1 (got pp=1); "
                "use schedule='none' for non-pipelined plans")

    # -- topology -----------------------------------------------------------
    @property
    def world_size(self) -> int:
        return self.dp * self.pp * self.sharding * self.sp * self.mp

    @property
    def dims(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXES}

    def topology(self, devices=None):
        """HybridTopology (and its Mesh) for this plan's degrees."""
        import jax
        from .mesh import HybridTopology
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) < self.world_size:
            raise PlanError(
                f"plan needs {self.world_size} devices "
                f"({'x'.join(str(d) for d in self.dims.values())}), "
                f"only {len(devices)} available")
        return HybridTopology(dp=self.dp, pp=self.pp,
                              sharding=self.sharding, sp=self.sp,
                              mp=self.mp,
                              devices=devices[:self.world_size])

    # -- generic compile (Titanax selection rule) ---------------------------
    def compile(self, fn: Callable, *, devices=None, mesh=None,
                in_shardings=None, out_shardings=None,
                in_specs=None, out_specs=None, axis_names=None,
                verify: Optional[bool] = None, example_args=None,
                donate_argnums=(), **jit_kwargs):
        """Compile ``fn`` for this plan's mesh.

        Selection rule (SNIPPETS.md Titanax pattern):

        * ``in_shardings`` AND ``out_shardings`` → ``jax.jit`` with
          explicit placements (pjit path — GSPMD inserts collectives).
        * exactly one of them → :class:`PlanCompilationError`. A
          half-specified placement is the silent-degradation case: GSPMD
          would guess the other side and the plan would no longer mean
          what it says.
        * ``in_specs``/``out_specs`` → ``shard_map`` (manual map-style
          collectives: the fn body sees per-device shards and calls
          ``lax.psum``/``ppermute`` itself), wrapped in ``jit``.
        * neither → plain ``jit``.

        Sharding/spec leaves may be PartitionSpecs (bound to the plan
        mesh here) or prebuilt NamedShardings. ``verify`` gates the
        result through the SPMD checker (None → ``FLAGS_tpu_lint``):
        eagerly when ``example_args`` is given, else lazily on the
        first call. The returned callable carries ``.path`` ('pjit' |
        'shard_map' | 'jit'), ``.mesh`` and ``.jitted``.
        """
        import jax
        from ..core import compile_cache

        compile_cache.ensure()
        topo = None
        if mesh is None:
            topo = self.topology(devices)
            mesh = topo.mesh

        have_in_sh = in_shardings is not None
        have_out_sh = out_shardings is not None
        have_specs = (in_specs is not None) or (out_specs is not None)
        if have_in_sh != have_out_sh:
            missing = "out_shardings" if have_in_sh else "in_shardings"
            raise PlanCompilationError(
                "pjit compilation requires BOTH in_shardings and "
                f"out_shardings; {missing} is missing. Half-specified "
                "placements fall back to GSPMD inference and stop "
                "meaning what the plan says — pass both, or use "
                "in_specs/out_specs for the shard_map path")
        if have_in_sh and have_specs:
            raise PlanCompilationError(
                "pass either shardings (pjit path) or specs (shard_map "
                "path), not both")
        if have_specs and ((in_specs is None) != (out_specs is None)):
            raise PlanCompilationError(
                "shard_map compilation requires both in_specs and "
                "out_specs")

        if have_in_sh:
            path = "pjit"
            inner = jax.jit(
                fn,
                in_shardings=_as_sharding_tree(in_shardings, mesh),
                out_shardings=_as_sharding_tree(out_shardings, mesh),
                donate_argnums=donate_argnums, **jit_kwargs)
            traceable = fn
        elif have_specs:
            path = "shard_map"
            names = (set(axis_names) if axis_names is not None
                     else set(mesh.axis_names))
            traceable = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                      out_specs=out_specs,
                                      axis_names=names, check_vma=False)
            inner = jax.jit(traceable, donate_argnums=donate_argnums,
                            **jit_kwargs)
        else:
            path = "jit"
            inner = jax.jit(fn, donate_argnums=donate_argnums,
                            **jit_kwargs)
            traceable = fn

        from ..core.flags import flag
        do_verify = flag("FLAGS_tpu_lint") if verify is None else verify

        # jax.set_mesh, not the legacy ``with mesh``: a trace reads the
        # mesh from this context to place Pallas kernels
        # (pallas_ops.kernel_axes), here as in train_step
        def _lint(args, kwargs):
            with jax.set_mesh(mesh):
                self.verify_callable(
                    traceable, *args, mesh=mesh,
                    name=getattr(fn, "__name__", "plan_fn"), **kwargs)

        state = {"checked": not do_verify}
        if do_verify and example_args is not None:
            _lint(tuple(example_args), {})
            state["checked"] = True

        def compiled(*args, **kwargs):
            if not state["checked"]:
                _lint(args, kwargs)
                state["checked"] = True
            with jax.set_mesh(mesh):
                return inner(*args, **kwargs)

        compiled.path = path
        compiled.mesh = mesh
        compiled.topology = topo
        compiled.jitted = inner
        compiled.plan = self
        return compiled

    def verify_callable(self, fn, *args, mesh=None, name=None, **kwargs):
        """Trace ``fn(*args)`` and run the SPMD collective-consistency
        rules (PR-8 checker). Raises :class:`PlanVerificationError` on
        error-severity findings; warnings (e.g. donation-sharding) pass
        through. Returns the full finding list."""
        from ..analysis.jaxpr_checks import lint_callable
        axis_names = (set(mesh.axis_names) if mesh is not None
                      else set(self.dims))
        findings = lint_callable(fn, *args, name=name,
                                 axis_names=axis_names, **kwargs)
        errors = _error_findings(findings)
        if errors:
            raise PlanVerificationError(errors)
        return findings

    # -- the llama training step --------------------------------------------
    def train_step(self, cfg, devices=None, *, optimizer=None, zero=True,
                   verify: Optional[bool] = None):
        """(step_fn, init_fn) for this plan: ``models.llama
        .build_train_step`` on the plan's topology, with the plan's
        schedule/microbatching/overlap, optionally gated through the
        SPMD checker on first call (verify=None → ``FLAGS_tpu_lint``).
        """
        import jax
        from ..models.llama import build_train_step
        from ..core import compile_cache
        from ..core.flags import flag

        compile_cache.ensure()
        topo = self.topology(devices)
        use_pp = self.pp > 1 and self.schedule != "none"
        schedule = self.schedule if use_pp else "gpipe"
        n_micro = self.n_microbatches or (self.pp if use_pp else None)
        step_fn, init_fn = build_train_step(
            cfg, topo, optimizer=optimizer, use_pp=use_pp,
            n_microbatches=n_micro, zero=zero, schedule=schedule,
            overlap=self.overlap)

        step_fn.plan = self
        step_fn.plan_topology = topo
        do_verify = flag("FLAGS_tpu_lint") if verify is None else verify
        if not do_verify:
            return _wrap_step_tracing(self, step_fn), init_fn

        state = {"checked": False}
        inner = step_fn

        def verified_step(params, opt_state, batch):
            if not state["checked"]:
                with jax.set_mesh(topo.mesh):
                    self.verify_callable(inner.jitted, params, opt_state,
                                         batch, mesh=topo.mesh,
                                         name="train_step")
                state["checked"] = True
            return inner(params, opt_state, batch)

        _copy_step_attrs(inner, verified_step)
        return _wrap_step_tracing(self, verified_step), init_fn

    # -- spec round-trip ----------------------------------------------------
    def to_spec(self) -> Dict[str, Any]:
        spec = {"axes": self.dims, "schedule": self.schedule,
                "n_microbatches": self.n_microbatches,
                "overlap": self.overlap}
        if self.param_specs is not None:
            spec["param_specs"] = self.param_specs
        return spec

    @classmethod
    def from_spec(cls, spec: Dict[str, Any]) -> "Plan":
        axes = dict(spec.get("axes", {}))
        kw = {a: int(axes.get(a, 1)) for a in AXES}
        return cls(schedule=spec.get("schedule", "none"),
                   n_microbatches=spec.get("n_microbatches"),
                   overlap=bool(spec.get("overlap", False)),
                   param_specs=spec.get("param_specs"), **kw)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_spec(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "Plan":
        with open(path) as f:
            return cls.from_spec(json.load(f))

    @classmethod
    def from_report(cls, report) -> "Plan":
        """Build a Plan from a pod_report: accepts the report dict, a
        path to the report JSON, a ``--plan-out`` spec dict, or a path
        to one. The planner's winning ``(dp, pp, sharding, mp)`` becomes
        the plan axes; ``pp > 1`` selects the 1F1B schedule with the
        report's microbatch count."""
        if isinstance(report, (str, os.PathLike)):
            with open(report) as f:
                report = json.load(f)
        if "axes" in report:  # already an executable plan spec
            return cls.from_spec(report)
        topo = report.get("topology")
        if topo is None:
            raise PlanError("report has no 'topology' section (and is "
                            "not a plan spec)")
        kw = {a: int(topo.get(a, 1)) for a in AXES}
        pp = kw["pp"]
        return cls(schedule="1f1b" if pp > 1 else "none",
                   n_microbatches=int(topo.get("n_microbatches", pp))
                   if pp > 1 else None,
                   overlap=True, **kw)

    # -- elasticity ---------------------------------------------------------
    def for_world_size(self, n: int) -> "Plan":
        """Refit the plan to ``n`` devices: keep the model axes
        (pp/sharding/sp/mp) and refit dp when they divide ``n``; else
        collapse to pure data parallelism (the always-valid fallback —
        params replicated, no pipeline)."""
        model = self.pp * self.sharding * self.sp * self.mp
        if n >= model and n % model == 0:
            return dataclasses.replace(self, dp=n // model)
        return dataclasses.replace(
            self, dp=n, pp=1, sharding=1, sp=1, mp=1,
            schedule="none", n_microbatches=None)

    def run_train_loop(self, cfg, batches: Iterable[Dict[str, Any]], *,
                       devices=None, optimizer=None, rng=None,
                       job_id: str = "plan", scale_store=None,
                       ckpt_root: Optional[str] = None,
                       verify: Optional[bool] = None,
                       on_step: Optional[Callable] = None):
        """Plan-driven training loop with elastic resize.

        Before each step the loop polls ``scale_store`` for the
        ``fleet.elastic.request_scale`` key of ``job_id``; on a changed
        world size it checkpoints (params + opt state), refits the plan
        with :meth:`for_world_size`, recompiles the step on the new
        device set, and restores via ``reshard.restore_resharded`` onto
        the new mesh — the PR-9 machinery, driven by the Plan.

        Returns ``{"losses", "world_sizes", "resizes"}`` (one entry per
        step; ``resizes`` records ``(step_index, old_world, new_world)``
        tuples).

        ``on_step(step_count, params, opt_state)`` fires after every
        completed step with the 1-based step count and the live state —
        the gang runtime's step-boundary hook (health step stamp +
        final-save snapshot + beacon).
        """
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .fault_tolerance import CheckpointManager
        from .reshard import restore_resharded
        from .fleet.elastic import _scale_key

        devices = list(devices if devices is not None else jax.devices())
        plan = self
        topo = plan.topology(devices)
        step_fn, init_fn = plan.train_step(cfg, devices,
                                           optimizer=optimizer,
                                           verify=verify)
        params, opt_state = init_fn(
            rng if rng is not None else jax.random.PRNGKey(0))

        def _poll_scale():
            if scale_store is None:
                return None
            try:
                raw = scale_store.get(_scale_key(job_id))
            except KeyError:
                return None
            if raw is None:
                return None
            if isinstance(raw, bytes):
                raw = raw.decode()
            return int(raw)

        def _place_like(state, abstract):
            # the pickle restore wraps leaves in the eager Tensor facade
            # (a pytree NODE) — unwrap to host arrays before re-placing
            # per the new step's shardings
            from ..core.tensor import Tensor
            state = jax.tree_util.tree_map(
                lambda x: np.asarray(getattr(x, "_array", x)),
                state, is_leaf=lambda x: isinstance(x, Tensor))
            return jax.tree_util.tree_map(
                lambda x, a: _put_global(x, a.sharding), state, abstract)

        history = {"losses": [], "world_sizes": [], "resizes": []}
        step_idx = 0
        # live observability: /healthz reports train progress when
        # FLAGS_tpu_metrics_port is set (no-op otherwise)
        _train_status = {"job_id": job_id, "step": 0, "loss": None,
                         "world_size": plan.world_size, "done": False}
        _exporter.maybe_serve("train", lambda: dict(_train_status))
        for batch in batches:
            want = _poll_scale()
            if (want is not None and want != plan.world_size
                    and want <= len(devices)):
                if ckpt_root is None:
                    raise PlanError(
                        "resize requested but run_train_loop was given "
                        "no ckpt_root to reshard through")
                mgr = CheckpointManager(ckpt_root, backend="pickle",
                                        sync=True)
                mgr.save(step_idx,
                         {"params": jax.tree_util.tree_map(
                             np.asarray, params),
                          "opt_state": jax.tree_util.tree_map(
                              np.asarray, opt_state)})
                old_world = plan.world_size
                plan = plan.for_world_size(want)
                topo = plan.topology(devices)
                step_fn, init_fn = plan.train_step(
                    cfg, devices, optimizer=optimizer, verify=verify)
                state, _ = restore_resharded(ckpt_root, mesh=topo.mesh)
                p_abs, o_abs = step_fn.abstract_state()
                params = _place_like(state["params"], p_abs)
                opt_state = _place_like(state["opt_state"], o_abs)
                history["resizes"].append((step_idx, old_world, want))
            sh = NamedSharding(topo.mesh, P(topo.batch_axes, None))
            placed = {k: _put_global(v, sh) for k, v in batch.items()}
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 placed)
            history["losses"].append(float(metrics["loss"]))
            history["world_sizes"].append(plan.world_size)
            step_idx += 1
            if on_step is not None:
                on_step(step_idx, params, opt_state)
            _train_status.update(step=step_idx,
                                 loss=history["losses"][-1],
                                 world_size=plan.world_size)
        _train_status["done"] = True
        return history
