"""Kind ``serve-closed``: ``serving.LLMEngine`` under a closed loop, one
client per slot; a client submits its next request in the step after its
previous one finished.

Traffic file keys: ``clients``; ``engine`` (keyword arguments of
``LLMEngine`` that a deployment sizes from its traffic and memory, nothing
else); ``prompt`` / ``output`` (log-normal ``median``, ``sigma``, ``min``,
``max``), ``n_lengths``, ``round`` (see ``traffic.serve_lengths``: the same
lengths for every seed, ordered, paired and phased by ``--seed``);
``ramp_s`` (the loop runs this long before the window opens; it counts as
set-up); ``trace_slice_s``; ``check`` (``sample``, ``sample_max_tokens``,
``logits_rel_tol``, ``token_gap_sigma_tol``, ``require_pallas_kernel``).

All latencies are taken on this file's clock through ``on_token``; the
engine's own samples feed only per-layer metrics. A token, a first token or
a gap belongs to the window it falls in, whenever its request was
submitted: a request's time to first token is counted in exactly one
window, so long prompts submitted near the end are not censored out of the
tail. ``attempted`` is the requests submitted inside the window, and
``failed`` those refused at ``add_request`` or found failed or cancelled
inside it; what is still in flight when the window closes is cancelled by
the benchmark and counts as neither.

The time to first token is no end-to-end metric of this kind: with one
client per slot nothing queues, so it is a prompt's chunks times the step,
and its tail over the hundred or so first tokens of a window follows which
prompts the seed put there (see PERF.md). Its count, median and 95th
percentile go on the ``window:`` line and to the per-layer readers.
"""
from __future__ import annotations

import functools
import json
import time

import numpy as np

from benchmark import harness, reference, stats, traffic

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
    from paddle_tpu.models import llama
    from paddle_tpu.profiler import compile_tracker

    mix, check = ctx.traffic, ctx.traffic["check"]
    compile_tracker.install()
    cfg = harness.build_config(ctx.config)
    params = jax.jit(functools.partial(llama.init_params, cfg))(ctx.key())
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
        "ttft_ms": stats.summary(ttft, 1e3),
        "gap_ms": stats.summary(gaps, 1e3),
        "mean_slots_busy_share": float(np.mean(occupancy)) / eng.max_running,
        "preemptions": stats2["requests_preempted"]
        - stats1["requests_preempted"],
        "engine_steps_by_bucket": {Tc: len(v)
                                   for Tc, v in engine_step_s.items()},
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
                f"{check['token_gap_sigma_tol']} sigma)")
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
