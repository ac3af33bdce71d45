"""Kind ``serve-closed-model``: ``kinds/serve-closed.py``'s closed loop for
whichever model the configuration names under ``model``: the program's
``paddle_tpu.models.<model>`` gives the config (``config_from_fields``), the
seeded weights (``init_params``) and, through ``cfg.serving``, the engine's
step; ``benchmark.reference_<model>`` gives the comparison that decides
``correct`` (``served_checks``).  ``harness.build_config`` makes a
``LlamaConfig`` and is not used.

The loop, the clocks, the window's accounting, the checks and the keys of
the returned dict are ``serve-closed``'s, line for line (the ``window:`` log
line also gives the tokens a second, which a cell may not report as a
metric, and the step time of each bucket apart), so that every
reader of its ``samples``, ``counters``, ``kernels`` and ``trace`` reads this
kind too; that file may not be edited by the PR that added this one (PR
27), and a ``benchmark`` PR should fold the two (PERF.md section 7).

Traffic file keys: as ``serve-closed``: ``clients``; ``engine``; ``prompt`` /
``output``, ``n_lengths``, ``round``; ``ramp_s``; ``trace_slice_s``; ``check``
(``sample``, ``sample_max_tokens``, ``logits_rel_tol``, ``token_gap_sigma_tol``,
``require_pallas_kernel``; ``state_rel_tol`` and ``state_slow_rel_tol`` where
the model's reference reports ``state_rel_err`` and ``state_slow_rel_err``,
the recurrent state after the replay).
"""
from __future__ import annotations

import functools
import importlib
import json
import time

import numpy as np

from benchmark import harness, stats, traffic

ENDED_BADLY = ("failed", "cancelled")


class Client:
    """One request in flight, timed on the benchmark's clock."""
    __slots__ = ("prompt", "n_out", "submitted", "token_t")

    def __init__(self, prompt, n_out, submitted):
        self.prompt, self.n_out, self.submitted = prompt, n_out, submitted
        self.token_t = []


def run(ctx: harness.Context) -> dict:
    import jax
    from paddle_tpu import serving
    from paddle_tpu.profiler import compile_tracker

    mix, check = ctx.traffic, ctx.traffic["check"]
    name = ctx.config["model"]
    model = importlib.import_module(f"paddle_tpu.models.{name}")
    reference = importlib.import_module(f"benchmark.reference_{name}")
    compile_tracker.install()
    cfg = model.config_from_fields(ctx.config)
    params = jax.jit(functools.partial(model.init_params, cfg))(ctx.key())
    stats0 = serving.serving_stats()
    harness.log(f"set-up: weights at {ctx.since_start()}")
    eng = serving.LLMEngine(cfg, params, **mix["engine"])
    stream = traffic.RequestStream(mix, cfg.vocab_size, ctx.seed)
    clock = time.perf_counter

    flying, ended = {}, []      # rid -> Client in flight; (rid, Client) done
    bad = []                    # when a request was refused or ended badly

    def on_token(rid, token, finished):
        flying[rid].token_t.append(clock())

    def submit():
        prompt, n_out = stream.next()
        try:
            rid = eng.add_request(prompt, n_out, on_token=on_token)
        except serving.AdmissionRejected:
            bad.append(clock())
            return
        flying[rid] = Client(prompt, n_out, clock())

    def step():
        """One engine step, then each client whose request ended submits
        its next one. Returns how long ``eng.step()`` took."""
        t_in = clock()
        finished = eng.step()
        t_out = clock()
        for rid in finished:
            ended.append((rid, flying.pop(rid)))
            submit()
        for rid in [r for r in flying
                    if eng.state_of(r).value in ENDED_BADLY]:
            bad.append(clock())
            flying.pop(rid)
            submit()
        return t_out - t_in

    def forward_counts():
        return {Tc: len(v) for Tc, v in eng._step_wall_s.items()}

    def forward_since(before):
        """The engine's own sample of the forward call and its sync in
        the turn that began with the counts ``before`` (0 if none ran)."""
        return sum(v[-1] for Tc, v in eng._step_wall_s.items()
                   if len(v) > before.get(Tc, 0))

    # warm-up: one short request alone runs both buckets (prefill chunks,
    # then one-token steps), compiling them or loading them from the cache
    warm = eng.add_request(list(range(1, eng.chunk + 5)), 4)
    while eng.has_work():
        eng.step()
    if eng.state_of(warm).value != "finished":
        raise RuntimeError(f"the warm-up request ended "
                           f"{eng.state_of(warm).value}")
    buckets = sorted(eng._step_fns)
    harness.log(f"set-up: both buckets warm at {ctx.since_start()}")
    harness.log(f"engine: {eng.max_running} slots, chunk {eng.chunk}, "
                f"{eng.num_pages} pages of {eng.page_size}, max_model_len "
                f"{eng.max_model_len}; buckets warmed: {buckets}")

    harness.settle_collector()
    for _ in range(int(mix["clients"])):
        submit()
    t_ramp = clock()
    while clock() - t_ramp < mix["ramp_s"]:
        step()

    tracer = harness.TraceSlice(ctx.profile_dir) if ctx.trace else None
    trace_steps = 0
    compiles0 = compile_tracker.compile_count()
    stats1 = serving.serving_stats()
    marks0 = {"queue": len(eng._queue_s), "step": forward_counts()}
    occupancy, turns = [], []
    steal0 = harness.host_steal_s()
    with harness.Heartbeat() as heart:
        t0 = now = clock()
        setup_s = t0 - ctx.t_start
        while now - t0 < ctx.seconds:
            if tracer and not tracer.started and now - t0 >= ctx.seconds / 2:
                tracer.start()
            elif tracer and tracer.active and \
                    tracer.elapsed() >= mix["trace_slice_s"]:
                tracer.stop()
            occupancy.append(len(flying))
            cpu0, before = time.thread_time(), forward_counts()
            in_engine = step()
            trace_steps += bool(tracer and tracer.active)
            then, now = now, clock()
            turns.append({"wall": now - then, "engine_step": in_engine,
                          "forward_and_sync": forward_since(before),
                          "main_thread_cpu": time.thread_time() - cpu0})
        if tracer and tracer.active:
            tracer.stop()
        t1 = clock()
    window_s = t1 - t0
    beat = heart.report(t0)
    steal1 = harness.host_steal_s()
    compiles = compile_tracker.compile_count() - compiles0
    memory = harness.memory_peak_bytes()
    stats2 = serving.serving_stats()
    queue_s = list(eng._queue_s[marks0["queue"]:])
    engine_step_s = {Tc: list(v[marks0["step"].get(Tc, 0):])
                     for Tc, v in eng._step_wall_s.items()}

    # -- the window's numbers, from the benchmark's own clock -------------
    def inside(t):
        return t0 < t <= t1

    everyone = [c for _, c in ended] + list(flying.values())
    ttft = [c.token_t[0] - c.submitted for c in everyone
            if c.token_t and inside(c.token_t[0])]
    gaps = [b - a for c in everyone
            for a, b in zip(c.token_t, c.token_t[1:])
            if inside(a) and inside(b)]
    tokens = sum(inside(t) for c in everyone for t in c.token_t)
    failed = sum(inside(t) for t in bad)
    attempted = sum(inside(c.submitted) for c in everyone) + failed
    finished_in = [(rid, c) for rid, c in ended if inside(c.token_t[-1])]
    half = t0 + window_s / 2
    harness.log("window: " + json.dumps({
        "window_s": window_s, "steps": len(occupancy),
        "output_tokens_by_half": [
            sum(t0 < t <= half for c in everyone for t in c.token_t),
            sum(half < t <= t1 for c in everyone for t in c.token_t)],
        "requests_submitted": attempted, "requests_finished":
        len(finished_in), "output_tokens": tokens,
        "output_tokens_per_s": tokens / window_s,
        "ttft_ms": stats.summary(ttft, 1e3),
        "gap_ms": stats.summary(gaps, 1e3),
        "mean_slots_busy_share": float(np.mean(occupancy)) / eng.max_running,
        "preemptions": stats2["requests_preempted"]
        - stats1["requests_preempted"],
        "engine_steps_by_bucket": {Tc: len(v)
                                   for Tc, v in engine_step_s.items()},
        "engine_step_ms_by_bucket": {Tc: stats.summary(v, 1e3)
                                     for Tc, v in engine_step_s.items() if v},
        "compilations": compiles,
        "slowest_loop_turns_ms": harness.slowest(turns),
        "heartbeat": beat,
        "host_cpu_s_stolen": None if steal0 is None
        else round(steal1 - steal0, 2)}))

    # -- correctness, outside the window ------------------------------------
    for rid in list(flying):
        eng.cancel(rid)
    flying.clear()
    pool = [(rid, c) for rid, c in finished_in
            if len(c.prompt) + c.n_out <= check["sample_max_tokens"]]
    rng = traffic.rng_for(ctx.seed, "serve-sample")
    picked = [pool[i] for i in sorted(rng.permutation(len(pool))
                                      [:check["sample"]])]
    served = [(c.prompt, eng.output_of(rid)) for rid, c in picked]
    full = all(len(out) == c.n_out for (_, out), (_, c) in zip(served, picked))
    verdict = reference.served_checks(ctx.config, eng, params, served) \
        if served else {}
    audit = eng.kv.audit()
    kernels = {Tc: harness.pallas_kernels(eng._lower(Tc).as_text())
               for Tc in buckets}
    stats3 = serving.serving_stats()
    eng.shutdown()
    checks = {
        "sampled": len(served) == check["sample"] and full,
        "tokens": verdict.get("token_gap_sigma", np.inf)
        <= check["token_gap_sigma_tol"],
        "logits": verdict.get("logits_rel_err", np.inf)
        <= check["logits_rel_tol"],
        # only the reference of a model with recurrent state reports them
        "state": all(verdict[k + "_err"] <= check[k + "_tol"]
                     for k in ("state_rel", "state_slow_rel")
                     if k + "_err" in verdict),
        "page_audit": bool(audit["ok"]),
        "no_recovery": all(stats3[k] == stats0[k]
                           for k in ("recoveries", "quarantined")),
        "both_buckets": buckets == sorted({1, eng.chunk}),
        "pallas_kernel": all(kernels.values())
        or not check["require_pallas_kernel"],
        "no_compile": compiles == 0,
    }
    harness.log(f"engine kernels by bucket: {json.dumps(kernels)}")
    harness.log(f"served against the float32 reference: "
                f"{json.dumps(verdict)} (tolerances: logits "
                f"{check['logits_rel_tol']}, token gap "
                f"{check['token_gap_sigma_tol']} sigma, state "
                f"{check.get('state_rel_tol')}, its slow tenth "
                f"{check.get('state_slow_rel_tol')})")
    harness.log(f"checks: {json.dumps(checks)}")
    return {
        "correct": all(checks.values()),
        "attempted": attempted,
        "failed": failed,
        "end_to_end": {
            "setup_s": setup_s,
            "serve_tokens_per_s": tokens / window_s,
            "serve_gap_p95_ms": stats.percentile(gaps, 95) * 1e3},
        "samples": {"ttft_s": ttft, "gap_s": gaps, "queue_s": queue_s,
                    "engine_step_s": engine_step_s},
        "counters": {"trace_steps": trace_steps,
                     "compiles_in_window": compiles,
                     "steps": len(occupancy)},
        "kernels": sorted({k for ks in kernels.values() for k in ks}),
        "trace": tracer.reduce() if tracer else None,
        "memory_peak_bytes": memory,
    }
