"""Kind ``train``: ``Plan().train_step(cfg)`` on one chip under a job file.

Job file keys: ``batch``, ``seq``, ``zipf_a`` (data), ``trace_steps`` (steps
under the profiler in a traced run), ``check`` (``ce0_abs_tol``: how far the
first step's cross entropy may lie from the float32 reference on the same
weights and batch; ``grad_dir_tol``: how far the direction of its gradient
may, see ``reference.direction_errors``; ``require_pallas_kernel``).

Set-up: weights and optimizer state from the seed on the device, the
reference's cross entropy and gradient on the first batch, the step lowered
once for its kernel names, then one real step (compile or cache load), whose
first Adam moment is the program's gradient. The window: a new host-made
batch ``device_put`` every step, each step fenced with
``block_until_ready``; the rate is all tokens over all the time.
"""
from __future__ import annotations

import json
import time

import numpy as np

from benchmark import harness, reference, traffic


def run(ctx: harness.Context) -> dict:
    import jax
    import optax
    from paddle_tpu.distributed.plan import Plan
    from paddle_tpu.profiler import compile_tracker

    job, check = ctx.traffic, ctx.traffic["check"]
    compile_tracker.install()
    cfg = harness.build_config(ctx.config)
    B, S = int(job["batch"]), int(job["seq"])

    step_fn, init_fn = Plan().train_step(cfg, jax.devices()[:1], verify=False)
    params, opt_state = init_fn(ctx.key())
    batches = traffic.train_batches(job, cfg.vocab_size, ctx.seed)

    def place(batch):
        return {k: jax.device_put(v, step_fn.batch_shardings[k])
                for k, v in batch.items()}

    first = next(batches)
    harness.log(f"set-up: weights and optimizer state at {ctx.since_start()}")
    ref_ce, ref_grads = reference.loss_and_grads(ctx.config, params, first)
    harness.log(f"set-up: reference loss and gradient at {ctx.since_start()}")
    placed = place(first)
    kernels = harness.pallas_kernels(
        step_fn.lower(params, opt_state, placed).as_text())
    harness.log(f"train step kernels: {json.dumps(kernels)}")

    parts = {}

    def fenced_step(placed):
        nonlocal params, opt_state
        t_call = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, placed)
        parts["dispatch"] = time.perf_counter() - t_call
        jax.block_until_ready((params, opt_state, metrics))
        return metrics["ce"]

    harness.log(f"set-up: step lowered for its kernels at {ctx.since_start()}")
    ces = [fenced_step(placed)]                      # step 0: the warm-up
    grad_err = reference.direction_errors(
        optax.tree_utils.tree_get(opt_state, "mu"), ref_grads)
    del ref_grads
    harness.log(f"step 0 ce {float(ces[0]):.6f}, float32 reference "
                f"{ref_ce:.6f} (tol {check['ce0_abs_tol']}); gradient "
                f"direction against the reference {json.dumps(grad_err)} "
                f"(tol {check['grad_dir_tol']})")

    tracer = harness.TraceSlice(ctx.profile_dir) if ctx.trace else None
    trace_steps = int(job.get("trace_steps", 3))
    traced_steps = 0
    compiles0 = compile_tracker.compile_count()
    turns = []
    harness.settle_collector()
    steal0 = harness.host_steal_s()
    with harness.Heartbeat() as heart:
        t0 = t_prev = time.perf_counter()
        setup_s = t0 - ctx.t_start
        while t_prev - t0 < ctx.seconds:
            if tracer and not tracer.started \
                    and t_prev - t0 >= ctx.seconds / 2:
                tracer.start()
                t_prev = time.perf_counter()       # starting is not a step
            elif tracer and tracer.active and traced_steps >= trace_steps:
                tracer.stop()
                t_prev = time.perf_counter()
            cpu0 = time.thread_time()
            placed = place(next(batches))
            t_placed = time.perf_counter()
            ces.append(fenced_step(placed))
            now = time.perf_counter()
            turns.append({"wall": now - t_prev,
                          "batch_and_put": t_placed - t_prev,
                          "dispatch": parts["dispatch"],
                          "wait_for_device": now - t_placed
                          - parts["dispatch"],
                          "main_thread_cpu": time.thread_time() - cpu0})
            t_prev = now
            if tracer and tracer.active:
                traced_steps += 1
        if tracer and tracer.active:
            tracer.stop()
        window_s = time.perf_counter() - t0
    beat = heart.report(t0)
    steal1 = harness.host_steal_s()
    compiles = compile_tracker.compile_count() - compiles0
    memory = harness.memory_peak_bytes()

    ces = [float(c) for c in ces]
    step_s = [t["wall"] for t in turns]
    finite = [bool(np.isfinite(c)) for c in ces]
    falling = float(np.mean(ces[-5:])) < ces[0]
    ce0_ok = abs(ces[0] - ref_ce) <= check["ce0_abs_tol"]
    grad_ok = max(grad_err.values()) <= check["grad_dir_tol"]
    kernel_ok = bool(kernels) or not check["require_pallas_kernel"]
    harness.log("losses " + json.dumps([round(c, 5) for c in ces]))
    harness.log(f"{len(step_s)} steps of {B}x{S} tokens in {window_s:.3f} s; "
                f"step median {np.median(step_s) * 1e3:.2f} ms, min "
                f"{min(step_s) * 1e3:.2f}, max {max(step_s) * 1e3:.2f}; "
                f"compilations inside the window: {compiles}")
    harness.log("stalls: " + json.dumps({
        "slowest_loop_turns_ms": harness.slowest(turns), "heartbeat": beat,
        "host_cpu_s_stolen": None if steal0 is None
        else round(steal1 - steal0, 2)}))
    harness.log(f"checks: finite {all(finite)}, falling {falling} (mean of "
                f"last five {np.mean(ces[-5:]):.4f} < {ces[0]:.4f}), "
                f"|ce0 - ref| {abs(ces[0] - ref_ce):.6f} ok {ce0_ok}, "
                f"gradient direction {max(grad_err.values()):.5f} ok "
                f"{grad_ok}, pallas kernel {kernel_ok}, no compile "
                f"{compiles == 0}")
    return {
        "correct": (all(finite) and falling and ce0_ok and grad_ok
                    and kernel_ok and compiles == 0),
        "attempted": len(step_s),
        "failed": sum(not f for f in finite[1:]),
        "end_to_end": {
            "setup_s": setup_s,
            "train_tokens_per_s": B * S * len(step_s) / window_s},
        "samples": {"step_s": step_s},
        "counters": {"tokens_per_step": B * S, "seq": S,
                     "compiles_in_window": compiles,
                     "trace_steps": traced_steps},
        "kernels": kernels,
        "trace": tracer.reduce() if tracer else None,
        "memory_peak_bytes": memory,
    }
