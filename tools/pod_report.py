#!/usr/bin/env python
"""Pod-fit reporter: will this model FIT on that pod, and how fast?

Compiles a named model preset's full training step on a *virtual* mesh
shaped like a real TPU pod (no hardware: JAX_PLATFORMS=cpu +
--xla_force_host_platform_device_count), lets the cost-model planner
choose the (dp, pp, sharding, mp) topology, and reads the answer
straight from XLA's compiled.memory_analysis() via profiler.xmem —
the same number the real pod would enforce. Parameters are never
materialized (jax.ShapeDtypeStruct throughout), so reporting on a 7B
model needs a laptop, not 64 chips.

    python tools/pod_report.py --preset llama7b --mesh v5p-64

emits a JSON report: per-device peak HBM, fits/doesn't-fit verdict
against the generation's HBM, the collective set XLA inserted, and the
cost-model-predicted step time / MFU / tokens-per-second.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

# TPU generation table: per-chip HBM, peak dense bf16 FLOP/s, and the
# per-core VMEM the Level-3 kernel verifier budgets Pallas blocks
# against (~16 MiB physical minus Mosaic spill/prologue headroom;
# double on v6e).
TPU_GENERATIONS = {
    "v4":  dict(hbm_gib=32.0,  peak_flops=275e12, ici_gbps=100.0,
                vmem_mib=12),
    "v5e": dict(hbm_gib=16.0,  peak_flops=197e12, ici_gbps=50.0,
                vmem_mib=12),
    "v5p": dict(hbm_gib=95.0,  peak_flops=459e12, ici_gbps=100.0,
                vmem_mib=12),
    "v6e": dict(hbm_gib=32.0,  peak_flops=918e12, ici_gbps=100.0,
                vmem_mib=24),
}

_MESH_RE = re.compile(r"^(?P<gen>[a-z0-9]+)-(?P<n>\d+)$")


def parse_mesh(spec: str):
    m = _MESH_RE.match(spec.strip().lower())
    if not m or m.group("gen") not in TPU_GENERATIONS:
        raise SystemExit(
            f"unrecognized --mesh {spec!r}; expected <gen>-<chips> with "
            f"gen in {sorted(TPU_GENERATIONS)} (e.g. v5p-64)")
    return m.group("gen"), int(m.group("n"))


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", nargs="?", default=None, choices=["serving"],
                    help="'serving' emits only the serving capacity "
                         "section (hardware-free arithmetic, no train-"
                         "step compile — seconds instead of minutes); "
                         "omit for the full pod-fit report")
    ap.add_argument("--preset", default="llama7b",
                    help="model preset from models.llama.PRESETS")
    ap.add_argument("--mesh", default="v5p-64",
                    help="pod shape <generation>-<chips>, e.g. v5p-64")
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=None,
                    help="sequence length (default: preset max positions)")
    ap.add_argument("--page-size", type=int, default=128,
                    help="paged-KV tokens per pool page for the "
                         "serving capacity section")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "fp16", "int8", "fp8"],
                    help="paged-KV page dtype for the serving capacity "
                         "section; sub-2-byte dtypes include the "
                         "quantized-KV per-page scale-pool overhead "
                         "and report the capacity ratio vs the bf16 "
                         "baseline")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind a serving.Router — "
                         "the serving section reports router-level "
                         "aggregate capacity (N x plan_capacity) "
                         "alongside the per-engine numbers")
    ap.add_argument("--fleet-workload", default="diurnal",
                    help="seeded arrival preset (serving.workloads) "
                         "the serving section's fleet block sizes "
                         "against; 'none' disables the block")
    ap.add_argument("--fleet-requests", type=int, default=200)
    ap.add_argument("--fleet-seed", type=int, default=0)
    ap.add_argument("--fleet-horizon-s", type=float, default=60.0)
    ap.add_argument("--fleet-prompt-len", type=int, default=12)
    ap.add_argument("--fleet-new-tokens", type=int, default=8)
    ap.add_argument("--max-running", type=int, default=8,
                    help="per-replica engine slots assumed by the "
                         "fleet block's service model")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk assumed by the fleet block's "
                         "service model")
    ap.add_argument("--prefix-hit-rate", type=float, default=None,
                    help="measured shared-prefix hit rate in [0, 1) "
                         "(e.g. LLMEngine.serving_stats()'s "
                         "prefix_hit_rate) — the serving "
                         "section then also reports effective "
                         "blocks-per-request and concurrency with "
                         "that fraction of each request's pages "
                         "shared from the radix cache")
    ap.add_argument("--topology", default=None,
                    help="override the planner: dp,pp,sharding,mp")
    ap.add_argument("--out", default="-",
                    help="output path for the JSON report (- = stdout)")
    ap.add_argument("--plan-out", default=None,
                    help="also write the winning topology as an "
                         "executable plan spec (distributed.plan.Plan "
                         "JSON: axes, schedule, microbatches, "
                         "per-param partition specs) — "
                         "Plan.from_report() / Plan.load() compile "
                         "exactly the config the planner scored")
    ap.add_argument("--list-presets", action="store_true")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# planner: enumerate (dp, pp, sharding, mp) factorizations, score with the
# alpha-beta cost model + an analytic memory estimate, pick the cheapest
# that fits. Only the winner is actually compiled.
# ---------------------------------------------------------------------------

def _candidate_topologies(cfg, n_dev, global_batch):
    L, H = cfg.num_hidden_layers, cfg.hidden_size
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = []
    for mp in range(1, n_dev + 1):
        if n_dev % mp or nh % mp or nkv % mp or H % mp:
            continue
        if cfg.intermediate_size % mp or cfg.vocab_size % mp:
            continue
        rest = n_dev // mp
        for pp in range(1, rest + 1):
            if rest % pp or L % pp:
                continue
            dpw = rest // pp          # data-parallel world = dp * sharding
            if global_batch % dpw:
                continue
            if pp > 1 and (global_batch // dpw) % pp:
                continue              # microbatch split (mb = pp)
            # sharding (ZeRO) axis: either fold the whole data world into
            # dp, or carve all of it out as a dedicated sharding axis
            for sharding in (1, dpw) if dpw > 1 else (1,):
                out.append(dict(dp=dpw // sharding, pp=pp,
                                sharding=sharding, mp=mp))
    return out


def _score_topology(cfg, topo, n_dev, global_batch, seq, n_params, gen,
                    model_flops):
    """(estimated per-device bytes, predicted step time in us, breakdown)."""
    from paddle_tpu.distributed.auto_parallel.cost_model import (
        CommContext, all_reduce_cost, p2p_cost)
    dp, pp, sharding, mp = (topo["dp"], topo["pp"], topo["sharding"],
                            topo["mp"])
    L, H, V = cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size
    I = cfg.intermediate_size
    ctx = CommContext(ici_bandwidth_gbps=gen["ici_gbps"])
    dpw = dp * sharding
    b_loc = global_batch // dpw
    mb = pp if pp > 1 else 1
    zero_deg = sharding if sharding > 1 else dp

    # -- memory (analytic, for ranking only; verdict comes from XLA) --
    param_dev = 2 * n_params / (pp * mp)          # bf16 weights
    grad_dev = param_dev
    opt_dev = 2 * param_dev / max(1, zero_deg)    # adamw mu+nu, ZeRO-1
    act_slab = b_loc * seq * H * 2                # one bf16 activation
    # remat 'dots' keeps matmul outputs: ~2H + 2I floats/layer/token
    act_dev = (L / pp) * (b_loc / mb) * seq * (2 * H + 2 * I) * 2 * \
        min(mb, pp)
    logits_dev = b_loc * seq * V * 4 / mp         # fp32 logits + lse
    mem_dev = param_dev + grad_dev + opt_dev + act_dev + logits_dev

    # -- time (alpha-beta) --
    eff = 0.55                                    # matmul fraction of peak
    compute_us = model_flops / n_dev / (gen["peak_flops"] * eff) * 1e6
    act_mb = act_slab / mb
    mp_comm_us = 0.0
    if mp > 1:
        # 2 all-reduces/layer forward (attention out + mlp out), 2 backward
        mp_comm_us = (L / pp) * mb * 4 * all_reduce_cost(act_mb, mp, ctx)
    bubble = (pp - 1) / (mb + pp - 1) if pp > 1 else 0.0
    pipe_us = (compute_us + mp_comm_us) / (1.0 - bubble)
    p2p_us = 2 * (pp - 1) * mb * p2p_cost(act_mb, ctx) if pp > 1 else 0.0
    sync_us = all_reduce_cost(grad_dev, dpw, ctx) if dpw > 1 else 0.0
    step_us = pipe_us + p2p_us + sync_us
    return mem_dev, step_us, dict(
        compute_us=compute_us, mp_comm_us=mp_comm_us, p2p_us=p2p_us,
        dp_sync_us=sync_us, pp_bubble_fraction=bubble,
        est_mem_bytes=mem_dev)


def plan_topology(cfg, n_dev, global_batch, seq, n_params, gen,
                  model_flops):
    cands = _candidate_topologies(cfg, n_dev, global_batch)
    if not cands:
        raise SystemExit(
            f"no valid (dp,pp,sharding,mp) factorization of {n_dev} "
            f"devices for this preset/batch — adjust --global-batch")
    hbm = gen["hbm_gib"] * 2**30
    scored = []
    for t in cands:
        mem, step_us, detail = _score_topology(
            cfg, t, n_dev, global_batch, seq, n_params, gen, model_flops)
        penalty = 1e12 if mem > hbm else 0.0
        scored.append((step_us + penalty, step_us, mem, t, detail))
    scored.sort(key=lambda s: s[0])
    return scored


# ---------------------------------------------------------------------------

def _collectives_of(compiled):
    """The set of collective ops XLA inserted, from the optimized HLO."""
    try:
        hlo = compiled.as_text()
    except Exception:
        return []
    names = re.findall(
        r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute|collective-broadcast)\b", hlo)
    return sorted(set(names))


def build_report(args):
    gen_name, n_dev = parse_mesh(args.mesh)
    gen = TPU_GENERATIONS[gen_name]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.distributed.mesh import HybridTopology
    from paddle_tpu.models import llama
    from paddle_tpu.profiler import xmem

    cfg = llama.preset(args.preset)
    seq = args.seq or cfg.max_position_embeddings
    B = args.global_batch

    # abstract parameter census (no materialization)
    p_shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    n_params = int(sum(np.prod(l.shape)
                       for l in jax.tree_util.tree_leaves(p_shapes)))
    tokens = B * seq
    # model FLOPs per step (fwd+bwd): 6N per token + attention term
    model_flops = 6.0 * n_params * tokens \
        + 12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq * tokens

    scored = plan_topology(cfg, n_dev, B, seq, n_params, gen, model_flops)
    if args.topology:
        dp, pp, sharding, mp = (int(x) for x in args.topology.split(","))
        choice = dict(dp=dp, pp=pp, sharding=sharding, mp=mp)
        mem, step_us, detail = _score_topology(
            cfg, choice, n_dev, B, seq, n_params, gen, model_flops)
        chosen = (step_us, step_us, mem, choice, detail)
    else:
        chosen = scored[0]
    _, pred_step_us, est_mem, topo_dims, detail = chosen

    topo = HybridTopology(**topo_dims)
    # use_pp=False: the layer stack is still sharded over the 'pp' mesh
    # axis (param_specs leads with P("pp", ...)), but stage scheduling is
    # left to GSPMD instead of the shard_map pipeline: for a fit verdict
    # the GSPMD lowering is the conservative one (same weights/optimizer
    # placement, activations not microbatched).
    step_fn, _init_fn = llama.build_train_step(cfg, topo, use_pp=False)
    p_abs, o_abs = step_fn.abstract_state()
    batch_abs = {
        k: jax.ShapeDtypeStruct((B, seq), jnp.int32, sharding=sh)
        for k, sh in step_fn.batch_shardings.items()}

    xmem.enable()
    # abstract compiles of 7B-scale steps take minutes; xmem.analyze
    # turns the persistent XLA cache on, so repeat reports start warm
    t0 = time.perf_counter()
    with jax.set_mesh(topo.mesh):
        profile, compiled = xmem.analyze(
            step_fn.jitted, p_abs, o_abs, batch_abs,
            source="pod_report", name=f"{args.preset}@{args.mesh}")
    compile_s = time.perf_counter() - t0
    if profile is None:
        raise SystemExit("backend returned no memory_analysis(); "
                         "cannot produce a pod-fit verdict")

    hbm_bytes = int(gen["hbm_gib"] * 2**30)
    peak = profile["peak_bytes"]
    pred_step_s = pred_step_us * 1e-6
    mfu = model_flops / (pred_step_s * n_dev * gen["peak_flops"])
    return {
        "preset": args.preset,
        "mesh": args.mesh,
        "generation": {"name": gen_name, "hbm_gib_per_chip": gen["hbm_gib"],
                       "peak_bf16_flops_per_chip": gen["peak_flops"]},
        "devices": n_dev,
        "model": {
            "n_params": n_params,
            "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_hidden_layers,
            "vocab_size": cfg.vocab_size,
            "seq_len": seq,
            "global_batch": B,
            "model_flops_per_step": model_flops,
        },
        "topology": dict(topo_dims,
                         n_microbatches=topo_dims["pp"]
                         if topo_dims["pp"] > 1 else 1,
                         zero_axis="sharding"
                         if topo_dims["sharding"] > 1 else "dp"),
        "planner": {
            "candidates_considered": len(scored),
            "top": [dict(rank=i + 1, **s[3],
                         predicted_step_ms=round(s[1] / 1e3, 3),
                         est_mem_gib=round(s[2] / 2**30, 2))
                    for i, s in enumerate(scored[:5])],
        },
        "memory": {
            "argument_bytes": profile["argument_bytes"],
            "output_bytes": profile["output_bytes"],
            "temp_bytes": profile["temp_bytes"],
            "alias_bytes": profile["alias_bytes"],
            "generated_code_bytes": profile["generated_code_bytes"],
            "per_device_peak_bytes": peak,
            "per_device_peak_gib": round(peak / 2**30, 3),
            "planner_estimate_gib": round(est_mem / 2**30, 3),
        },
        "fits": {
            "hbm_bytes_per_chip": hbm_bytes,
            "fits": peak <= hbm_bytes,
            "headroom_bytes": hbm_bytes - peak,
            "hbm_utilization": round(peak / hbm_bytes, 4),
        },
        "collectives": _collectives_of(compiled),
        "kernels": _kernel_section(gen),
        "serving": _serving_section(cfg, gen, args),
        "predicted": {
            "step_time_ms": round(pred_step_us / 1e3, 3),
            "mfu": round(mfu, 4),
            "tokens_per_second": round(tokens / pred_step_s, 1),
            "compute_ms": round(detail["compute_us"] / 1e3, 3),
            "mp_comm_ms": round(detail["mp_comm_us"] / 1e3, 3),
            "p2p_ms": round(detail["p2p_us"] / 1e3, 3),
            "dp_sync_ms": round(detail["dp_sync_us"] / 1e3, 3),
            "pp_bubble_fraction": round(detail["pp_bubble_fraction"], 4),
        },
        "xla": {
            "compile_seconds": round(compile_s, 2),
            "flops_reported": profile["flops"],
            "bytes_accessed": profile["bytes_accessed"],
        },
        "notes": _plan_notes(n_dev),
    }


def _kernel_section(gen):
    """Level-3 kernel verifier sweep for the report: trace the
    registered Pallas kernel library (CPU-only, nothing executes) with
    this generation's per-core VMEM budget and report per-kernel block
    footprints + verdicts. None when the analysis package is missing."""
    try:
        from paddle_tpu.analysis import kernel_checks
        from paddle_tpu.profiler import xmem
    except ImportError:
        return None
    budget = int(gen["vmem_mib"]) << 20
    try:
        findings = kernel_checks.verify_registered(
            config={"vmem_budget_bytes": budget})
        n_cases = len(kernel_checks.registered_cases())
    except Exception as e:  # a broken kernel library must not kill the fit report
        return {"error": f"{type(e).__name__}: {e}"}
    ests = xmem.kernel_estimates()
    return {
        "vmem_budget_mib": int(gen["vmem_mib"]),
        "cases_verified": n_cases,
        "estimates": [
            dict(kernel=e["kernel"],
                 vmem_bytes=e["vmem_bytes"],
                 vmem_mib=round(e["vmem_bytes"] / 2**20, 2),
                 within_budget=e["vmem_bytes"] <= budget)
            for e in ests[:16]],
        "findings": [f.to_dict() for f in findings],
        "ok": not any(f.severity == "error" for f in findings),
    }


def _serving_section(cfg, gen, args):
    """Paged-KV serving capacity on one chip of this generation —
    hardware-free arithmetic (serving.plan_capacity): how many pool
    pages fit beside the bf16 weights and how many concurrent
    max-length requests per chip that sustains.  The number an
    operator needs before sizing a serving fleet."""
    try:
        from paddle_tpu.serving import plan_capacity
    except ImportError:
        return None
    hbm = int(gen["hbm_gib"] * 2**30)
    seq = args.seq or cfg.max_position_embeddings
    kv_dtype = getattr(args, "kv_dtype", None) or "bf16"
    plan = plan_capacity(cfg, hbm_bytes=hbm,
                         page_size=int(args.page_size),
                         max_model_len=seq, kv_dtype=kv_dtype)
    plan["weights_gib"] = round(plan["weights_bytes"] / 2**30, 2)
    plan["usable_kv_gib"] = round(plan["usable_kv_bytes"] / 2**30, 2)
    plan["fits"] = plan["max_concurrent_requests"] > 0
    if kv_dtype != "bf16":
        # the --kv-dtype axis: same chip, same weights, only the page
        # format changes — the predicted capacity win of quantized KV
        base = plan_capacity(cfg, hbm_bytes=hbm,
                             page_size=int(args.page_size),
                             max_model_len=seq, kv_dtype="bf16")
        plan["baseline_bf16"] = {
            "num_pages": base["num_pages"],
            "page_bytes": base["page_bytes"],
            "max_concurrent_requests": base["max_concurrent_requests"],
        }
        if base["max_concurrent_requests"] > 0:
            plan["capacity_ratio_vs_bf16"] = round(
                plan["max_concurrent_requests"]
                / base["max_concurrent_requests"], 3)
    # measured prefix-hit-rate folds into capacity: a hit fraction h
    # means h of each request's pages come from the radix cache and
    # are shared, so only (1-h) of blocks_per_request are unique per
    # request.  Raw numbers stay in the report next to the effective
    # ones — the raw plan is the zero-reuse worst case
    hit = getattr(args, "prefix_hit_rate", None)
    if hit is not None:
        if not 0.0 <= hit < 1.0:
            raise SystemExit(
                f"--prefix-hit-rate {hit} out of range [0, 1)")
        raw_blocks = plan["blocks_per_request"]
        eff_blocks = max(int(math.ceil(raw_blocks * (1.0 - hit))), 1)
        n_pages = plan["num_pages"]
        eff_concurrent = (n_pages - 1) // eff_blocks if n_pages > 1 else 0
        plan["prefix_hit_rate"] = float(hit)
        plan["effective_blocks_per_request"] = eff_blocks
        plan["effective_max_concurrent_requests"] = int(eff_concurrent)
    # router-level view: N independent replicas behind serving.Router
    # multiply concurrency and pool pages linearly (each replica owns
    # its own chip and pool); per-request numbers are per-engine
    n = max(int(getattr(args, "replicas", 1) or 1), 1)
    plan["replicas"] = n
    plan["aggregate"] = {
        "max_concurrent_requests":
            n * plan["max_concurrent_requests"],
        "num_pages": n * plan["num_pages"],
        "usable_kv_bytes": n * plan["usable_kv_bytes"],
    }
    fleet = _fleet_block(plan, args)
    if fleet is not None:
        plan["fleet"] = fleet
    return plan


def _fleet_block(plan, args):
    """Analytic fleet sizing for this plan's page pool: the shared
    ``serving.autoscale.recommend_fleet`` arithmetic over the same
    seeded arrival stream ``tools/fleet_sim.py`` simulates — by
    construction the two tools return the same min-replica answer for
    the same knobs (the consistency test pins it)."""
    preset = getattr(args, "fleet_workload", None)
    if not preset or preset == "none":
        return None
    try:
        from paddle_tpu.serving import autoscale, workloads
    except ImportError:
        return None
    workloads.validate(preset)
    arrivals = workloads.generate(
        preset, int(args.fleet_requests), seed=int(args.fleet_seed),
        horizon_s=float(args.fleet_horizon_s),
        prompt_len=int(args.fleet_prompt_len),
        max_new_tokens=int(args.fleet_new_tokens))
    model = autoscale.ServiceModel(
        max_running=int(args.max_running), chunk=int(args.chunk),
        page_size=int(plan["page_size"]),
        num_pages=int(plan["num_pages"]),
        max_model_len=int(plan["max_model_len"]),
        max_queue=8 * int(args.max_running))
    rec = autoscale.recommend_fleet(model, arrivals)
    rec["workload"] = preset
    rec["seed"] = int(args.fleet_seed)
    rec["horizon_s"] = float(args.fleet_horizon_s)
    rec["service_model"] = model.to_dict()
    rec["note"] = ("uncalibrated step costs (shared defaults); feed a "
                   "measured trace through "
                   "tools/fleet_sim.py to validate under simulation")
    return rec


def build_serving_report(args):
    """The ``serving`` subcommand: just the capacity arithmetic —
    plan_capacity over the --kv-dtype axis, no train-step compile, so
    it answers "how many concurrent requests per chip" in seconds."""
    gen_name, n_dev = parse_mesh(args.mesh)
    gen = TPU_GENERATIONS[gen_name]
    from paddle_tpu.models import llama
    cfg = llama.preset(args.preset)
    return {
        "mode": "serving",
        "preset": args.preset,
        "mesh": args.mesh,
        "generation": {"name": gen_name,
                       "hbm_gib_per_chip": gen["hbm_gib"]},
        "serving": _serving_section(cfg, gen, args),
    }


def _plan_notes(n_dev):
    """Advisory lines attached to the report. A multi-host plan (more
    chips than one host carries — 8 on every supported generation)
    depends on DCN rendezvous and gang collectives, where a single hung
    rank stalls the whole job; flag it when the runtime health layer
    (FLAGS_tpu_watchdog) is off."""
    notes = []
    from paddle_tpu.core.flags import flag
    if n_dev > 8 and not flag("FLAGS_tpu_watchdog"):
        notes.append(
            f"multi-host plan ({n_dev} chips) with FLAGS_tpu_watchdog "
            "disabled: a hung rank in device init or a collective will "
            "stall the gang with no bounded-time recovery — set "
            "FLAGS_tpu_watchdog=1 (deadlines: FLAGS_tpu_watchdog_* ; "
            "see docs/robustness.md) to convert hangs into exit-101 "
            "elastic relaunches")
    return notes


def write_plan_spec(report, preset, path):
    """Serialize the report's winning topology as an executable
    ``distributed.plan.Plan`` spec: axes + schedule/microbatches from the
    report's ``topology`` section, plus the model's per-parameter
    partition specs in the portable ``reshard.spec_to_json`` form (keyed
    by '/'-joined parameter path). ``Plan.load(path)`` /
    ``Plan.from_report(path)`` then compile exactly the config the
    planner scored."""
    import dataclasses

    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed.plan import Plan
    from paddle_tpu.distributed.reshard import spec_to_json
    from paddle_tpu.models import llama

    cfg = llama.preset(preset)
    plan = Plan.from_report(report)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        llama.param_specs(cfg), is_leaf=lambda s: isinstance(s, P))

    def key(p):
        return "/".join(str(getattr(k, "key", k)) for k in p)

    plan = dataclasses.replace(
        plan, param_specs={key(p): spec_to_json(s) for p, s in flat})
    plan.save(path)
    print(f"wrote plan spec {path}", file=sys.stderr)


def main(argv=None):
    args = _parse_args(argv)
    _, n_dev = parse_mesh(args.mesh)

    # environment BEFORE jax import: hardware-free virtual pod
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo_root)
    os.environ["JAX_PLATFORMS"] = "cpu"   # never claims a chip
    import _xla_cpu_flags
    _xla_cpu_flags.ensure(device_count=n_dev)

    if args.list_presets:
        from paddle_tpu.models.llama import PRESETS
        print("\n".join(sorted(PRESETS)))
        return 0

    if args.mode == "serving":
        report = build_serving_report(args)
        payload = json.dumps(report, indent=2, sort_keys=False)
        if args.out == "-":
            print(payload)
        else:
            with open(args.out, "w") as f:
                f.write(payload + "\n")
            print(f"wrote {args.out}", file=sys.stderr)
        return 0

    report = build_report(args)
    if args.plan_out:
        write_plan_spec(report, args.preset, args.plan_out)
    payload = json.dumps(report, indent=2, sort_keys=False)
    if args.out == "-":
        print(payload)
    else:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
        print(f"wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
