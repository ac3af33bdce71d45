"""Serving benchmark: continuous-batching throughput on one chip.

Drives ``serving.LLMEngine`` with a staggered open-loop workload
(requests keep arriving while the batch is in flight, so continuous
admission and the mixed prefill+decode kernel path are both exercised)
and prints ONE line::

    BENCH_SERVE {"metric": "serve_tokens_per_sec_chip", ...}

with tokens/sec/chip, TTFT p50/p95 and request-latency p50/p95 — the
Gemma-on-Cloud-TPU serving comparison's headline numbers (PAPERS.md).
Percentiles come from the ``serve_*`` histograms in the metrics
registry (enabled for the run).  Every line names the platform, device
kind and device count it ran on; a failure prints an error line without
a value and exits 1 — no earlier result is carried forward.

Env knobs (all optional): PADDLE_TPU_BENCH_SERVE_PRESET (default
llama-debug), _REQUESTS, _PROMPT (max prompt len), _NEW (tokens per
request), _MAX_RUNNING, _CHUNK, _PAGE, _PAGES (pool pages — shrink to
force pool pressure), _MAX_QUEUE (admission bound — overload runs shed
past it), _TTFT_SLO_MS / _LAT_SLO_MS (SLO targets checked in the
resilience block), and PADDLE_TPU_BENCH_TIMEOUT for the watchdog
deadline shared with bench.py.

``--workload shared-prefix`` (or _WORKLOAD=shared-prefix) switches the
prompt mix to N requests over M shared system prompts (_SYS_PROMPTS,
default 2) and turns on the PR-12 reuse stack — prefix caching plus
self-draft speculative decoding (_SPEC_K, default 3; the draft IS the
target, so acceptance isolates the machinery from draft quality).  The
JSON line then carries a ``reuse`` block: prefix hit-rate, prefill
tokens saved, and the spec-decode acceptance rate.

The JSON line carries a ``resilience`` block (shed / recoveries /
quarantined / deadline-expired counts for the measured run, plus the
observed-vs-target SLO verdicts) so overload and chaos E2E runs are
assertable from the one-line contract.

``--workload diurnal|bursty|flash-crowd`` replays the matching seeded
arrival process from ``serving.workloads`` (the same streams
``tools/fleet_sim.py`` simulates), mapped onto engine steps so bursts
land as bursts.  Every run's JSON line carries a ``fleet`` block: the
per-replica service model calibrated from this run's measured step
wall-times (prefill-chunk / decode step costs, concurrency, predicted
capacity rps/chip, and the min-chips answer for the offered load) —
the live side of the fleet simulator's planning arithmetic.

``--kv-dtype int8`` (or _KV_DTYPE=int8) serves the same workload over
the quantized paged KV cache (int8 pages + per-page f32 scale pools;
parity-within-tolerance vs the bf16 pools, not bit-identical) and the
JSON line carries a ``kv`` block: page dtype, pool pages, scale-pool
bytes, and the pool's predicted max-concurrent capacity — the
measured side of the ``pod_report.py serving --kv-dtype`` prediction.

``--trace-out DIR`` (or _TRACE_OUT) turns on the flight recorder for
the measured run: every request's lifecycle events (queued -> admitted
-> prefill -> first token -> decode -> terminal) land in a rank-tagged
JSONL sidecar under DIR, the SLO block gains the TTFT breakdown
(queue/prefill/decode p95), and the sidecar path rides in the JSON
line — feed it to ``tools/trace_report.py`` for per-request timelines
whose breakdown sums exactly to the measured TTFT.

``--ledger-out [PATH]`` (or PADDLE_TPU_BENCH_LEDGER_OUT) appends the
normalized provenance-stamped row to the repo's own perf ledger (default
``runs/perf_ledger.jsonl``, never the driver's ``PERF_LEDGER.jsonl``;
gate it with ``tools/perf_ledger.py check``).
With ``FLAGS_tpu_metrics_port`` set the run is scrapeable live at
``/metrics`` and ``/slo`` (``paddle_tpu/profiler/exporter.py``) and the
JSON line carries the bound ``metrics_port``.
"""
from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
_T0 = time.monotonic()


def _ledger_out():
    """--ledger-out [PATH] / PADDLE_TPU_BENCH_LEDGER_OUT: perf ledger
    destination, or None when ledger emission is off."""
    path = os.environ.get("PADDLE_TPU_BENCH_LEDGER_OUT")
    if "--ledger-out" in sys.argv:
        i = sys.argv.index("--ledger-out")
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("--"):
            path = sys.argv[i + 1]
        else:
            path = os.path.join(_REPO, "runs", "perf_ledger.jsonl")
    return path


def _ledger_append(result):
    """Append the normalized row (success or error) to the perf ledger."""
    path = _ledger_out()
    if not path:
        return
    from paddle_tpu.profiler import ledger as _ledger
    cmd = "python " + " ".join(
        [os.path.basename(sys.argv[0] or "bench_serve.py")] + sys.argv[1:])
    row = _ledger.from_bench_serve_result(result, ts=time.time(), cmd=cmd)
    _ledger.append(path, row)
    _log(f"ledger row appended to {path}")


def _log(msg):
    sys.stderr.write(f"bench_serve[{time.monotonic() - _T0:6.1f}s]: "
                     f"{msg}\n")
    sys.stderr.flush()


def _env_int(name, default):
    return int(os.environ.get(f"PADDLE_TPU_BENCH_SERVE_{name}", default))


def _percentiles(hist_name, fallback):
    """p50/p95 (seconds) from a metrics-registry histogram, falling
    back to numpy over the raw per-request numbers."""
    import numpy as np

    from paddle_tpu.profiler import metrics
    v = metrics.snapshot().get(hist_name)
    if isinstance(v, dict) and v.get("count"):
        return float(v["p50"]), float(v["p95"])
    if not fallback:
        return 0.0, 0.0
    arr = np.asarray(fallback, dtype=float)
    return (float(np.percentile(arr, 50)), float(np.percentile(arr, 95)))


def main():
    import jax
    import numpy as np

    from paddle_tpu.core import flags as _flags
    from paddle_tpu.models import llama
    from paddle_tpu import serving
    from paddle_tpu.serving import workloads as _workloads

    _flags.set_flags({"FLAGS_tpu_metrics": True})

    preset = os.environ.get("PADDLE_TPU_BENCH_SERVE_PRESET",
                            "llama-debug")
    workload = os.environ.get("PADDLE_TPU_BENCH_SERVE_WORKLOAD",
                              "uniform")
    if "--workload" in sys.argv:
        workload = sys.argv[sys.argv.index("--workload") + 1]
    # one shared preset catalogue (serving/workloads.py): the error
    # enumerates every valid preset, and the shaped arrival processes
    # (diurnal/bursty/flash-crowd) are the exact streams fleet_sim
    # and pod_report plan against
    _workloads.validate(workload)
    kv_dtype = os.environ.get("PADDLE_TPU_BENCH_SERVE_KV_DTYPE", "bf16")
    if "--kv-dtype" in sys.argv:
        kv_dtype = sys.argv[sys.argv.index("--kv-dtype") + 1]
    if kv_dtype not in ("bf16", "int8"):
        raise ValueError(f"unknown --kv-dtype {kv_dtype!r} "
                         "(bf16 | int8)")
    trace_out = os.environ.get("PADDLE_TPU_BENCH_SERVE_TRACE_OUT")
    if "--trace-out" in sys.argv:
        trace_out = sys.argv[sys.argv.index("--trace-out") + 1]
    from paddle_tpu.profiler import trace as _trace
    from paddle_tpu.serving import autoscale as _autoscale
    if trace_out:
        _flags.set_flags({"FLAGS_tpu_trace": True})
    shared = workload == "shared-prefix"
    shaped = workload in ("diurnal", "bursty", "flash-crowd")
    n_req = _env_int("REQUESTS", 16)
    max_prompt = _env_int("PROMPT", 24)
    n_new = _env_int("NEW", 16)
    max_running = _env_int("MAX_RUNNING", 8)
    chunk = _env_int("CHUNK", 8)
    page = _env_int("PAGE", 128)
    n_sys = _env_int("SYS_PROMPTS", 2)
    spec_k = _env_int("SPEC_K", 3)
    max_queue = _env_int("MAX_QUEUE", 8 * max_running)
    pages_env = os.environ.get("PADDLE_TPU_BENCH_SERVE_PAGES")
    ttft_slo = os.environ.get("PADDLE_TPU_BENCH_SERVE_TTFT_SLO_MS")
    lat_slo = os.environ.get("PADDLE_TPU_BENCH_SERVE_LAT_SLO_MS")

    dev = jax.devices()[0]
    n_chips = jax.device_count()
    _log(f"backend={dev.platform} preset={preset} workload={workload} "
         f"requests={n_req} max_running={max_running} chunk={chunk} "
         f"page={page}")

    cfg = llama.preset(preset)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    max_model_len = min(cfg.max_position_embeddings,
                        max_prompt + n_new + chunk)
    slo = serving.SLOConfig(
        ttft_p95_s=float(ttft_slo) / 1e3 if ttft_slo else None,
        latency_p95_s=float(lat_slo) / 1e3 if lat_slo else None)
    reuse_kw = {}
    if shared:
        # self-draft: the draft model IS the target, so every proposal
        # verifies (acceptance rate ~1) — the bench isolates the spec
        # machinery's cost/benefit from draft-model quality
        reuse_kw = dict(prefix_cache=True,
                        spec=serving.SpecDecodeConfig(
                            cfg=cfg, params=params, k=spec_k))
    eng = serving.LLMEngine(cfg, params, max_running=max_running,
                            chunk=chunk, page_size=page,
                            num_pages=int(pages_env) if pages_env
                            else None,
                            max_model_len=max_model_len,
                            kv_dtype=(kv_dtype if kv_dtype != "bf16"
                                      else None),
                            max_queue=max_queue, slo=slo, **reuse_kw)

    rng = np.random.RandomState(0)
    arrivals = None
    if shaped:
        # the preset's seeded arrival process — the exact stream
        # tools/fleet_sim.py replays against the simulated fleet, so a
        # live bench and a sim run disagree only on time, never on
        # what arrived
        horizon_s = float(os.environ.get(
            "PADDLE_TPU_BENCH_SERVE_HORIZON_S", "60"))
        arrivals = _workloads.generate(
            workload, n_req, seed=_env_int("SEED", 0),
            horizon_s=horizon_s, prompt_len=max_prompt,
            max_new_tokens=n_new, vocab=cfg.vocab_size)
        prompts = [list(a.prompt) for a in arrivals]
    elif shared:
        # N requests over M distinct system prompts: the shared head is
        # most of the prompt (the few-shot/system-prompt shape), the
        # tail is per-request
        sys_len = max(max_prompt * 3 // 4, 2)
        sys_prompts = [list(rng.randint(0, cfg.vocab_size, sys_len))
                       for _ in range(n_sys)]
        prompts = [
            sys_prompts[i % n_sys]
            + list(rng.randint(0, cfg.vocab_size,
                               rng.randint(1, max(max_prompt - sys_len,
                                                  1) + 1)))
            for i in range(n_req)]
    else:
        prompts = [list(rng.randint(0, cfg.vocab_size,
                                    rng.randint(2, max_prompt + 1)))
                   for _ in range(n_req)]

    # warmup: compile both buckets before the clock starts.  In
    # shared-prefix mode warmup also runs one request per system
    # prompt, so the radix cache holds every shared head before the
    # measured run — the production shape, where system prompts are
    # warm long before the traffic being measured
    if shared:
        warm_ids = [eng.add_request(list(sp), 2) for sp in sys_prompts]
    else:
        warm_ids = [eng.add_request(prompts[0], 2)]
    while eng.has_work():
        eng.step()
    _log(f"warmup done ({len(eng._step_fns)} bucket(s) compiled), "
         f"warm tokens {eng.output_of(warm_ids[0])}")
    # drop the warmup's compile-inflated observations so the reported
    # percentiles describe steady-state serving only
    from paddle_tpu.profiler import metrics as _m
    _m.reset()
    eng._ttft_s.clear()
    eng._latency_s.clear()
    eng._queue_s.clear()
    eng._prefill_s.clear()
    eng._decode_s.clear()
    if trace_out:
        _trace.clear()  # measured-run lifecycle events only
    # the module stats dict is cumulative across the process — the
    # resilience block reports measured-run deltas from this snapshot
    base = serving.serving_stats()

    # measured run: half the requests up front, the rest arriving while
    # the batch is in flight — continuous admission, no drain between.
    # Overload runs (_MAX_QUEUE below the offered load) shed here with
    # the typed retriable AdmissionRejected — counted, never fatal.
    t_start = time.monotonic()
    rids = []
    shed_submits = 0

    def _submit(p):
        nonlocal shed_submits
        try:
            rids.append(eng.add_request(p, n_new))
        except serving.AdmissionRejected:
            shed_submits += 1

    steps = 0
    if shaped:
        # shaped presets arrive on the preset's own timeline, mapped
        # onto engine steps (workloads.step_schedule) — bursts land as
        # bursts instead of being smoothed into one-per-two-steps
        sched = _workloads.step_schedule(arrivals, max(2 * n_req, 1))
        last_step = max(sched) if sched else 0
        while eng.has_work() or steps <= last_step:
            for a in sched.get(steps, ()):
                _submit(list(a.prompt))
            eng.step()
            steps += 1
            if steps > 100000:
                raise RuntimeError("serve loop did not converge")
    else:
        for p in prompts[:n_req // 2]:
            _submit(p)
        pending = list(prompts[n_req // 2:])
        while eng.has_work() or pending:
            if pending and steps % 2 == 1:
                _submit(pending.pop(0))
            eng.step()
            steps += 1
            if steps > 100000:
                raise RuntimeError("serve loop did not converge")
    wall_s = time.monotonic() - t_start

    stats_now = serving.serving_stats()
    res = {k: int(stats_now[k] - base[k])
           for k in ("shed", "admission_waits", "recoveries",
                     "quarantined", "deadline_expired",
                     "callback_errors")}
    reqs = [eng._requests[r] for r in rids]
    done = [r for r in reqs if r.state.value == "finished"]
    assert all(len(r.output) == n_new for r in done), \
        "request finished short"
    if not (res["quarantined"] or res["deadline_expired"]):
        # without a terminal resilience event every admitted request
        # must complete — shedding only ever rejects at the front door
        assert len(done) == len(reqs), "admitted request lost"
    tokens = sum(len(r.output) for r in done)
    ttfts = [r.first_token_s - r.arrival_s for r in done
             if r.first_token_s is not None]
    lats = [r.finish_s - r.arrival_s for r in done
            if r.finish_s is not None]
    ttft_p50, ttft_p95 = _percentiles("serve_ttft_seconds", ttfts)
    lat_p50, lat_p95 = _percentiles("serve_request_latency_seconds",
                                    lats)
    tps_chip = tokens / wall_s / max(n_chips, 1)
    stats = stats_now

    def _ms(v):
        return None if v is None else round(v * 1e3, 2)

    # work-reuse report (measured-run deltas): prefix hit-rate over
    # the admitted prompt tokens — every hit token is a prefill token
    # the engine never fed — and the spec-decode acceptance rate
    hit = int(stats_now["prefix_hit_tokens"] - base["prefix_hit_tokens"])
    proposed = int(stats_now["spec_proposed"] - base["spec_proposed"])
    accepted = int(stats_now["spec_accepted"] - base["spec_accepted"])
    prompt_tokens = sum(len(r.prompt) for r in reqs)
    reuse = {
        "prefix_hit_tokens": hit,
        "prompt_tokens": prompt_tokens,
        "prefix_hit_rate": (round(hit / prompt_tokens, 4)
                            if prompt_tokens else 0.0),
        "prefill_tokens_saved": hit,
        "spec_proposed": proposed,
        "spec_accepted": accepted,
        "spec_acceptance_rate": (round(accepted / proposed, 4)
                                 if proposed else 0.0),
    }

    rep = eng.slo_report()
    res["slo"] = {
        "ttft_p95_ms": _ms(rep["ttft_p95_s"]),
        "ttft_slo_ms": _ms(rep["ttft_slo_s"]),
        "ttft_ok": rep["ttft_ok"],
        "latency_p95_ms": _ms(rep["latency_p95_s"]),
        "latency_slo_ms": _ms(rep["latency_slo_s"]),
        "latency_ok": rep["latency_ok"],
    }
    bd = rep.get("breakdown")
    if bd:
        res["slo"]["ttft_breakdown_ms"] = {
            "queue_p95": _ms(bd["queue_p95_s"]),
            "prefill_p95": _ms(bd["prefill_p95_s"]),
            "decode_p95": _ms(bd["decode_p95_s"]),
            "samples": bd["samples"],
        }

    # fleet block: the per-replica service model calibrated from this
    # run's measured step wall-times (by compiled bucket), plus the
    # capacity arithmetic fleet_sim and the autoscaler plan with —
    # predicted rps-per-chip next to the measured trajectory above
    sm = eng.service_model()
    mean_prompt = (prompt_tokens // len(reqs)) if reqs else max_prompt
    cap_rps = sm.capacity_rps(mean_prompt, n_new)
    offered_rps = (len(rids) + shed_submits) / wall_s if wall_s else 0.0
    fleet = {
        "calibrated": sm.calibrated,
        "prefill_chunk_ms": _ms(sm.prefill_chunk_s),
        "decode_step_ms": _ms(sm.decode_step_s),
        "concurrency": sm.concurrency,
        "capacity_rps_per_chip": round(cap_rps, 3),
        "offered_rps": round(offered_rps, 3),
        "min_chips_for_offered": _autoscale.replicas_for(
            sm, offered_rps, prompt_len=max(mean_prompt, 1),
            new_tokens=n_new),
    }

    trace_sidecar = None
    if trace_out:
        os.makedirs(trace_out, exist_ok=True)
        trace_sidecar = _trace.write_sidecar(
            _trace.sidecar_path(trace_out),
            extra={"bench": "serve", "workload": workload,
                   "requests": len(rids)})
        _log(f"trace sidecar: {trace_sidecar} (read with "
             "tools/trace_report.py)")

    result = {
        "metric": "serve_tokens_per_sec_chip",
        "value": round(tps_chip, 2),
        "unit": "tokens/s/chip",
        "ttft_p50_ms": round(ttft_p50 * 1e3, 2),
        "ttft_p95_ms": round(ttft_p95 * 1e3, 2),
        "latency_p50_ms": round(lat_p50 * 1e3, 2),
        "latency_p95_ms": round(lat_p95 * 1e3, 2),
        "requests": len(rids),
        "shed_submits": shed_submits,
        "max_queue": max_queue,
        "workload": workload,
        "reuse": reuse,
        "fleet": fleet,
        "resilience": res,
        "tokens": tokens,
        "steps": steps,
        "wall_seconds": round(wall_s, 3),
        "prefill_tokens": int(stats["prefill_tokens"]),
        "decode_tokens": int(stats["decode_tokens"]),
        "preemptions": int(stats["requests_preempted"]),
        "compiled_buckets": int(stats["compiled_buckets"]),
        "max_running": max_running,
        "chunk": chunk,
        "page_size": page,
        # predicted-vs-measured capacity: the pool's own arithmetic
        # (pages / blocks-per-request), pod_report serving's measured
        # counterpart for the BENCH_SERVE trajectory
        "kv": {
            "dtype": kv_dtype,
            "pages": int(eng.num_pages),
            "scale_pool_bytes": int(eng._scale_bytes),
            "max_concurrent_predicted":
                (eng.num_pages - 1) // eng.max_blocks,
        },
        "preset": preset,
        "platform": dev.platform,
        "device": dev.device_kind,
        "chips": n_chips,
    }
    if trace_sidecar is not None:
        result["trace_sidecar"] = trace_sidecar
    exp = _exporter_active()
    if exp is not None:
        result["metrics_port"] = exp.port
    return result


def _exporter_active():
    """The live exporter, if FLAGS_tpu_metrics_port started one when the
    engine was constructed."""
    from paddle_tpu.profiler import exporter
    return exporter.active()


def _error_result(msg):
    """An error line: the cause, the device and the runtime health
    layer's last incident — never a value."""
    import jax
    from paddle_tpu.runtime.watchdog import last_incident
    out = {"metric": "serve_tokens_per_sec_chip", "error": msg[-1500:]
           or "unknown"}
    try:
        dev = jax.devices()[0]
        out.update(platform=dev.platform, device=dev.device_kind,
                   chips=jax.device_count())
    except RuntimeError:      # no backend came up at all
        out.update(platform=None, device=None, chips=0)
    incident = last_incident()
    if incident is not None:
        out["incident"] = incident
    return out


def run():
    """Print the BENCH_SERVE line. A failure or a hang prints an error
    line (no value) with the runtime health layer's incident record
    attached, and the exit code is 1."""
    from paddle_tpu.runtime.watchdog import (PhaseTimeout,
                                             persist_incidents,
                                             run_with_deadline)

    def emit(result):
        print("BENCH_SERVE " + json.dumps(result))
        sys.stdout.flush()
        _ledger_append(result)

    timeout_s = float(os.environ.get("PADDLE_TPU_BENCH_TIMEOUT", "900"))
    try:
        result = run_with_deadline(main, timeout_s, phase="serve_measure")
    except PhaseTimeout:
        emit(_error_result(f"bench_serve timed out after {timeout_s:.0f}s "
                           "(compile or execute hang)"))
        persist_incidents()   # os._exit skips atexit
        os._exit(1)           # the hung measure thread would block exit
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        emit(_error_result(str(e) or repr(e)))
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(run())
