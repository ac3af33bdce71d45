"""Benchmark: Llama train-step MFU on one TPU chip — bf16 matmuls on the MXU,
Pallas attention/MLP kernels, remat, AdamW update inside one jit.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.
One named variant (remat "dots", batch 4, sequence 2048) or a failure: no
chip, an unknown ``device_kind``, a kernel that does not compile or a step
that does not fit all end the run with an error line and a non-zero exit
code. Nothing is retried on another path and no earlier number is carried
forward. (ROADMAP S1 replaces this script with the benchmark harness.)
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
_LEDGER_OUT = os.environ.get("PADDLE_TPU_BENCH_LEDGER_OUT")
_DEFAULT_LEDGER = os.path.join(_REPO, "runs", "perf_ledger.jsonl")
_T0 = time.monotonic()


def _log(msg):
    sys.stderr.write(f"bench[{time.monotonic() - _T0:6.1f}s]: {msg}\n")
    sys.stderr.flush()


def _ledger_append(result):
    """Append the normalized row to the repo's own perf ledger
    (--ledger-out), on success and on error: an error round is a row
    too."""
    if not _LEDGER_OUT:
        return
    from paddle_tpu.profiler import ledger as _ledger
    cmd = "python " + " ".join(
        [os.path.basename(sys.argv[0] or "bench.py")] + sys.argv[1:])
    row = _ledger.from_bench_result(result, ts=time.time(), cmd=cmd)
    _ledger.append(_LEDGER_OUT, row)
    _log(f"ledger row appended to {_LEDGER_OUT}")


# peak bf16 TFLOP/s by device generation (Google Cloud TPU documentation)
_PEAK_TFLOPS = {
    "v5 lite": 197.0, "v5litepod": 197.0, "v5e": 197.0,
    "v5p": 459.0, "v5": 459.0,
    "v4": 275.0, "v3": 123.0, "v2": 45.0,
    "v6 lite": 918.0, "v6e": 918.0,
}


def _peak_flops(dev) -> float:
    kind = dev.device_kind.lower()
    for key, tf in _PEAK_TFLOPS.items():
        if key in kind:
            return tf * 1e12
    raise RuntimeError(
        f"unknown device_kind {dev.device_kind!r}: no peak in "
        "_PEAK_TFLOPS, and a utilization needs one")


def _device_block():
    """Who measured: rides in every result line, error lines included."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def main():
    """Measure and return the result dict (raises on any failure; run()
    turns that into an error line and a non-zero exit code)."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from paddle_tpu.ops import autotune, pallas_ops
    import optax

    compile_cache.ensure()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures on a TPU; found platform {dev.platform!r} "
            f"({dev.device_kind}). A CPU run yields no time, rate or "
            "utilization.")
    peak = _peak_flops(dev)

    # ~0.95B params: fits one v5e chip (16G HBM) with Adam state
    policy, B, S, iters = "dots", 4, 2048, 10
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=2048,
        dtype=jnp.bfloat16, use_remat=True, remat_policy=policy)

    # block sizes: the committed cache (.flash_autotune.json, measured on
    # v5e) or the kernels' defaults — no sweep inside the measured command
    cache_file = os.path.join(_REPO, ".flash_autotune.json")
    if os.path.exists(cache_file):
        autotune.load(cache_file)

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    opt_state = opt.init(params)

    # donate params + opt_state: the update aliases into the same HBM
    # buffers instead of allocating a second copy of every tensor
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        (total, ce), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, ce

    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
    }

    _log(f"compiling remat={policy} B={B}")
    params, opt_state, ce = jax.block_until_ready(
        step(params, opt_state, batch))
    _log("compile + warmup done; measuring")
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, ce = step(params, opt_state, batch)
    jax.block_until_ready((params, opt_state, ce))
    dt = (time.perf_counter() - t0) / iters

    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    tokens = B * S
    # 6ND model FLOPs + attention 12*B*S^2*H*L (fwd+bwd, causal halves it)
    attn_flops = 6 * B * S * S * cfg.hidden_size * cfg.num_hidden_layers
    flops = 6.0 * n_params * tokens + attn_flops
    mfu = 100.0 * flops / dt / peak

    x_shape = (B, S, cfg.hidden_size)
    return {
        "metric": "llama_train_mfu_1chip",
        "value": round(mfu, 2),
        "unit": "percent_mfu",
        "vs_baseline": round(mfu / 40.0, 3),
        "device": _device_block(),
        "detail": {
            "tokens_per_sec_per_chip": round(tokens / dt, 1),
            "step_ms": round(dt * 1e3, 1),
            "n_params": n_params,
            "device": dev.device_kind,
            "batch": B, "seq": S,
            "fused_blocks": {
                "attention": pallas_ops.fused_attention_available(
                    x_shape, cfg.head_dim, cfg.dtype),
                "mlp": pallas_ops.fused_mlp_available(
                    x_shape, cfg.intermediate_size, cfg.dtype)},
            "remat_policy": policy,
            "autotune": {"stats": autotune.cache_stats(),
                         "configs": autotune.entries()},
        },
    }


def multichip_main(n_devices=8, trace_out=None):
    """--multichip preset: the Plan compile path on ``n_devices`` virtual
    host-platform devices (dp=2 x pp=2 x mp=2), 1F1B with double-buffered
    p2p (overlap=True) against the lockstep scan on the same config.

    Reports per-step wall time for both schedules, the PR-1 collective
    metrics (bytes/calls/latency from the instrumented collective API),
    modeled per-step collective traffic, and the static-schedule
    ``overlap_fraction`` (fraction of stage-boundary transfers with a
    full tick of slack to ride under compute — real async timing is not
    observable on the CPU backend, so the number comes from the shared
    schedule model in ``distributed.overlap``). With ``trace_out`` the
    flight recorder is enabled: train/step spans plus the recorded
    pipeline schedule land in a rank-tagged JSONL sidecar there, the
    measured overlap fraction (scored from the *recorded* schedule) is
    reported next to the static one, and the sidecar path rides in the
    JSON line for ``tools/trace_report.py``."""
    jax.config.update("jax_platforms", "cpu")
    import _xla_cpu_flags
    _xla_cpu_flags.ensure(device_count=n_devices)

    import optax
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed.overlap import (measured_overlap,
                                                overlap_fraction,
                                                schedule_events,
                                                transfer_stats)
    from paddle_tpu.distributed.plan import Plan
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.profiler import trace as _trace

    set_flags({"FLAGS_tpu_metrics": True,
               "FLAGS_tpu_trace": trace_out is not None})
    devices = jax.devices()
    _log(f"{len(devices)} virtual devices ready")

    dp, pp, mp = 2, 2, 2
    n_micro = 4
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=4, num_attention_heads=4,
                      num_key_value_heads=4, max_position_embeddings=64,
                      dtype=jnp.float32, use_remat=False)
    B, S = 8, 32
    rng = np.random.default_rng(0)
    batch_host = {
        "input_ids": rng.integers(0, cfg.vocab_size, (B, S)),
        "labels": rng.integers(0, cfg.vocab_size, (B, S)),
    }

    def measure(overlap):
        from jax.sharding import NamedSharding, PartitionSpec as P
        plan = Plan(dp=dp, pp=pp, mp=mp, schedule="1f1b",
                    n_microbatches=n_micro, overlap=overlap)
        step_fn, init_fn = plan.train_step(
            cfg, devices, optimizer=optax.sgd(1e-3), verify=False)
        params, opt_state = init_fn(jax.random.PRNGKey(0))
        topo = step_fn.plan_topology
        sh = NamedSharding(topo.mesh, P(topo.batch_axes, None))
        batch = {k: jax.device_put(jnp.asarray(v, jnp.int32), sh)
                 for k, v in batch_host.items()}
        params, opt_state, m = step_fn(params, opt_state, batch)  # compile
        jax.block_until_ready(m["loss"])
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, m = step_fn(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        return (time.perf_counter() - t0) / iters * 1e3, float(m["loss"])

    _log("measuring overlapped 1F1B Plan path")
    overlap_ms, loss_o = measure(True)
    evs_after_overlap = _trace.events() if trace_out else []
    _log("measuring lockstep 1F1B scan")
    lockstep_ms, loss_l = measure(False)

    # static schedule model: serialized transfer->compute ticks
    ev_o = schedule_events(pp, n_micro, overlap=True)
    ev_l = schedule_events(pp, n_micro, overlap=False)
    st_o, st_l = transfer_stats(ev_o), transfer_stats(ev_l)

    # measured schedule: scored from what the flight recorder saw the
    # executed plans emit — must match the static model bit-for-bit
    measured = None
    trace_sidecar = None
    if trace_out:
        all_evs = _trace.events()
        meas_o = _trace.pipeline_schedule_events(evs_after_overlap)
        meas_l = _trace.pipeline_schedule_events(
            all_evs[len(evs_after_overlap):])
        measured = {
            "overlap_fraction": round(
                measured_overlap(meas_o)["overlap_fraction"], 3),
            "overlap_fraction_lockstep": round(
                measured_overlap(meas_l)["overlap_fraction"], 3),
            "matches_static": meas_o == ev_o and meas_l == ev_l,
        }
        os.makedirs(trace_out, exist_ok=True)
        trace_sidecar = _trace.write_sidecar(
            _trace.sidecar_path(trace_out),
            extra={"bench": "multichip", "devices": len(devices)})
        _log(f"trace sidecar: {trace_sidecar}")

    # modeled per-step collective traffic on this plan
    itemsize = 4  # fp32
    edge_bytes = (B // dp // n_micro) * S * cfg.hidden_size * itemsize
    p2p_bytes = 2 * n_micro * (pp - 1) * edge_bytes  # fwd + bwd edges
    from paddle_tpu.models.llama import init_params
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
        jax.eval_shape(functools.partial(init_params, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))))
    grad_bytes = n_params * itemsize

    # exercise the instrumented collective API once at grad volume so
    # the PR-1 metric counters carry real measured entries for this run
    import paddle_tpu as paddle
    from paddle_tpu.profiler import metrics as _metrics
    paddle.distributed.all_reduce(
        paddle.to_tensor(np.zeros(n_params // 64, np.float32)))
    snap = _metrics.snapshot()
    coll = {k: v for k, v in snap.items() if k.startswith("collective_")}

    result = {
        "metric": "llama_train_multichip_step",
        "value": round(overlap_ms, 2),
        "unit": "ms_per_step",
        # baseline = the lockstep scan on the identical config
        "vs_baseline": round(lockstep_ms / overlap_ms, 3),
        "device": _device_block(),
        "detail": {
            "plan": {"dp": dp, "pp": pp, "mp": mp, "schedule": "1f1b",
                     "n_microbatches": n_micro, "overlap": True},
            "devices": len(devices),
            "device": getattr(devices[0], "device_kind", "cpu"),
            "batch": B, "seq": S,
            "step_ms_overlap": round(overlap_ms, 2),
            "step_ms_lockstep": round(lockstep_ms, 2),
            "loss": round(loss_o, 6),
            "loss_lockstep": round(loss_l, 6),
            "overlap": {
                "overlap_fraction": round(overlap_fraction(ev_o), 3),
                "overlap_fraction_lockstep":
                    round(overlap_fraction(ev_l), 3),
                "serialized_transfers": st_o["serialized_transfers"],
                "serialized_transfers_lockstep":
                    st_l["serialized_transfers"],
                "total_transfers": st_o["total_transfers"],
            },
            "collective_bytes_modeled": {
                "pipeline_p2p_per_step": p2p_bytes,
                "grad_allreduce_per_step": grad_bytes,
            },
            "collective_metrics": coll,
        },
    }
    if measured is not None:
        result["detail"]["overlap"]["measured"] = measured
        result["detail"]["trace_sidecar"] = trace_sidecar
    assert st_o["serialized_transfers"] < st_l["serialized_transfers"], \
        "overlap schedule must serialize strictly fewer transfers"
    return result


def multichip_gang_main(nproc, trace_out=None, steps=2):
    """--multichip --gang N: the same llama pipeline preset, but run as
    N REAL worker processes through ``python -m
    paddle_tpu.distributed.launch`` (pp spans process boundaries over
    the gloo CPU backend) instead of N virtual devices in one process.
    Parses the per-rank ``GANG_RESULT`` lines out of the workerlogs and
    folds them into one bench result whose ``detail.real_processes``
    records the actual process count — the ledger row for a gang run is
    distinguishable from a virtual-device run."""
    import re
    import subprocess
    import tempfile

    log_dir = tempfile.mkdtemp(prefix="bench_gang_")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc), "--max_restarts", "0",
           "--log_dir", log_dir,
           "--module", "paddle_tpu.distributed.gang",
           "--steps", str(steps)]
    if trace_out:
        cmd += ["--trace-out", trace_out]
    env = dict(os.environ)
    # the gang runtime is CPU-only by construction (gloo collectives,
    # distributed/gang.py): on a machine that sets JAX_PLATFORMS=tpu,
    # N workers must not all claim the one chip
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PTQ_CHAOS", None)  # never inherit chaos into a bench pod
    # each worker must see exactly ONE local device: a stray
    # host-platform-device-count flag would multiply the global device
    # count and break the pp=world_size plan
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+\s*",
                   " ", env.get("XLA_FLAGS", "")).strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    timeout_s = float(os.environ.get("PADDLE_TPU_BENCH_TIMEOUT", "1000"))
    _log(f"launching {nproc}-process gang pod (logs: {log_dir})")
    t0 = time.monotonic()
    proc = subprocess.run(cmd, env=env, cwd=_REPO, timeout=timeout_s,
                          capture_output=True, text=True)
    wall_s = time.monotonic() - t0

    results = {}
    for rank in range(nproc):
        path = os.path.join(log_dir, f"workerlog.{rank}")
        try:
            with open(path) as f:
                for ln in f:
                    if ln.startswith("GANG_RESULT "):
                        r = json.loads(ln[len("GANG_RESULT "):])
                        results[r["rank"]] = r
        except OSError:
            pass
    if proc.returncode != 0 or len(results) != nproc:
        tail = (proc.stderr or proc.stdout or "")[-800:]
        raise RuntimeError(
            f"gang pod failed: rc={proc.returncode}, "
            f"{len(results)}/{nproc} GANG_RESULT lines "
            f"(logs: {log_dir})\n{tail}")

    r0 = results[0]
    losses0 = r0["losses"]
    for rank, r in sorted(results.items()):
        if r["losses"] != losses0:
            raise RuntimeError(
                f"rank {rank} loss trajectory diverged from rank 0: "
                f"{r['losses']} != {losses0}")
    # None = tracing off for that rank; False = recorded schedule
    # diverged from the static model — a hard failure
    matches = [r["matches_static"] for _, r in sorted(results.items())]
    if any(m is False for m in matches):
        raise RuntimeError(
            f"recorded 1F1B schedule diverged from static model: "
            f"per-rank matches_static={matches}")
    step_ms = max(r["step_ms"] for r in results.values())
    return {
        "metric": "llama_train_multichip_step",
        "value": round(step_ms, 2),
        "unit": "ms_per_step",
        "vs_baseline": None,  # no lockstep twin run in gang mode
        "detail": {
            "real_processes": nproc,
            "plan": {"dims": r0["plan"], "schedule": r0["schedule"],
                     "n_microbatches": r0["n_microbatches"],
                     "overlap": r0["overlap"]},
            "world_size": r0["world_size"],
            "steps": r0["steps"],
            "loss": losses0[-1] if losses0 else None,
            "losses": losses0,
            "step_ms_per_rank": {str(rank): r["step_ms"]
                                 for rank, r in sorted(results.items())},
            "matches_static": matches,
            "pod_wall_s": round(wall_s, 2),
            "log_dir": log_dir,
        },
    }


def _error_result(metric, msg):
    """An error line: the metric's name, the cause, the device and the
    runtime health layer's last incident — never a value."""
    from paddle_tpu.runtime.watchdog import last_incident
    try:
        device = _device_block()
    except RuntimeError:      # no backend came up at all
        device = None
    out = {"metric": metric, "error": msg[-1500:] or "unknown",
           "device": device}
    incident = last_incident()
    if incident is not None:
        out["incident"] = incident
    return out


def run(measure, metric):
    """Run one measurement under PADDLE_TPU_BENCH_TIMEOUT and print its
    JSON line. A failure or a hang prints an error line instead and the
    exit code is 1."""
    from paddle_tpu.runtime.watchdog import (PhaseTimeout,
                                             persist_incidents,
                                             run_with_deadline)

    def emit(result):
        print(json.dumps(result))
        sys.stdout.flush()
        _ledger_append(result)

    timeout_s = float(os.environ.get("PADDLE_TPU_BENCH_TIMEOUT", "1000"))
    try:
        result = run_with_deadline(measure, timeout_s, phase="measure")
    except PhaseTimeout:
        emit(_error_result(metric, f"timed out after {timeout_s:.0f}s "
                                   "(compile or execute hang)"))
        persist_incidents()   # os._exit skips atexit
        os._exit(1)           # the hung measure thread would block exit
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        emit(_error_result(metric, str(e) or repr(e)))
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="bench the distributed Plan compile path "
                         "(1F1B + overlap) on virtual host devices "
                         "instead of the 1-chip MFU bench")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual device count for --multichip")
    ap.add_argument("--gang", type=int, default=None, metavar="N",
                    help="with --multichip: run the preset as N real "
                         "worker processes through the launcher "
                         "(pp crosses process boundaries) instead of "
                         "N virtual devices in one process")
    ap.add_argument("--gang-steps", type=int, default=2,
                    help="train steps for the --gang pod (default 2)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="enable the flight recorder and write the "
                         "rank-tagged trace sidecar into DIR "
                         "(--multichip only; read it with "
                         "tools/trace_report.py)")
    ap.add_argument("--ledger-out", nargs="?", metavar="PATH",
                    const=_DEFAULT_LEDGER, default=_LEDGER_OUT,
                    help="append the normalized run record (with "
                         "provenance) to the repo's own perf ledger at "
                         "PATH (default runs/perf_ledger.jsonl; never "
                         "the driver's PERF_LEDGER.jsonl)")
    cli = ap.parse_args()
    _LEDGER_OUT = cli.ledger_out
    if cli.multichip and cli.gang:
        sys.exit(run(lambda: multichip_gang_main(
            cli.gang, trace_out=cli.trace_out, steps=cli.gang_steps),
            "llama_train_multichip_step"))
    if cli.multichip:
        sys.exit(run(lambda: multichip_main(cli.devices,
                                            trace_out=cli.trace_out),
                     "llama_train_multichip_step"))
    sys.exit(run(main, "llama_train_mfu_1chip"))
