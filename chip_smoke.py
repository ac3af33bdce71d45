"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two entry points a user calls, at the full width and
depth of the ``llama1b`` preset (hidden 2048, 16 heads x 128, intermediate
5504, 16 layers, vocab 32000, bf16), on seeded random weights:

  trainer   ``Plan().train_step(cfg)`` at B=4, S=2048: a few AdamW steps on one
            seeded batch; loss finite and falling; step time fenced with
            ``jax.block_until_ready``.
  server    ``serving.LLMEngine(cfg, params)`` at its defaults, requests of
            mixed prompt length (one longer than two pages) drained with
            ``eng.step()``; every request finishes with its full token
            count, the page audit holds, both buckets (Tc=chunk, Tc=1)
            compiled. What it served is checked while the engine is alive:
            every generated token against the float32 ``forward_pure`` on
            the same stream, and the longest request's logits, replayed on
            the engine's own weights, pools and page tables. Once dense
            (bf16 weights, bf16 pages), once int8 (``quantized="on"``,
            ``kv_dtype="int8"``).
  path      the Pallas kernels of the lowered train step and of both engine
            buckets are exactly the ones named below, none interpreted; no
            serve recovery, no quarantine.
  scan      ``pallas_ops.selective_scan`` (the Mamba layers' kernel, which no
            Llama engine runs) at the width and rows of both Mamba cells, E
            5120, N 16, R 128 and 48, both programs, on ragged chunks
            against its XLA body.
  latent    ``pallas_ops.latent_paged_attention`` and ``paged_latent_write``
            (multi-head latent attention on paged latent vectors, which no
            Llama engine runs) at the DeepSeek-V2-Lite cell's shapes: 32 rows,
            16 heads on one latent of 640 lanes of which 512 are the value,
            72 pages a row, both programs, against their XLA bodies.
  experts   ``pallas_ops.grouped_experts`` (the routed experts' grouped
            SwiGLU) at that cell's shapes: 64 experts of 2048 x 1408, the
            rows of 256 and of 32 tokens' six experts each, against three
            ``lax.ragged_dot``.
  4 chips   where the host has them: ``Plan(dp=2, mp=2)``, ``Plan(dp=4)`` and
            ``Plan(pp=2, mp=2)`` 1F1B, each against the one-device loss on the
            same weights and batch, with parameter shardings and per-device
            bytes.

Nothing here catches an error to carry on: any failed check or exception ends
the run with a non-zero exit code. Without a TPU it exits non-zero before any
phase. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu import serving  # noqa: E402
from paddle_tpu.core import compile_cache  # noqa: E402
from paddle_tpu.distributed.plan import Plan  # noqa: E402
from paddle_tpu.models import llama  # noqa: E402
from paddle_tpu.ops import pallas_ops  # noqa: E402
from paddle_tpu.profiler import compile_tracker  # noqa: E402

# The Pallas kernels of each program, exactly (the kernel functions of
# ops/pallas_ops.py, as Mosaic custom calls name them): one missing is a
# reference served quietly, one too many — an int8 kernel in the dense
# engine — is a different model.
# The train step runs the unfused decoder blocks on the flash kernels (per
# device wherever the mesh splits batch or heads); the forward kernel once
# a layer, as the layers keep its output for the backward pass: run_trainer
# checks in every plan's lowered text that none sits in the recomputation.
TRAIN_KERNELS = {"_flash_fwd_kernel_resident",
                 "_flash_bwd_dq_kernel_resident",
                 "_flash_bwd_dkv_kernel_resident"}
SERVE_KERNELS = {"_rpa_kernel", "_kv_write_kernel"}
SERVE_KERNELS_INT8 = {"_rpa_kernel_quant", "_int8_matmul_kernel"}
SCAN_KERNEL = "_ssm_scan_kernel"
LATENT_KERNELS = {"_rpa_kernel_latent", "_kv_write_kernel"}
EXPERTS_KERNEL = "_moe_experts_kernel"

# Served logits vs the float32 forward_pure on the same dense weights, as
# ||served - ref|| / ||ref|| over the longest request's logits (prompt and
# generated tokens, three pages), full depth.
# Dense: what differs is bf16 rounding of activations in the served path
# (eps 2^-8 per rounding, a handful of roundings in each of 16 layers):
# 0.018 measured on the v5e over 40 tokens (PR 21), 0.022 with the jnp
# bodies on CPU at this width, depth and length. The bound leaves that
# room and still refuses an engine that quantizes its weights unasked,
# which measures 0.112 the same way (CPU, jnp bodies).
DENSE_LOGITS_REL_TOL = 0.04
# int8 weights + int8 pages against the same dense reference: ~1% per
# matmul from per-channel weight and per-row activation absmax scales,
# seven matmuls in each of 16 layers, plus per-page kv scales: 0.120 with
# the jnp oracle (the same integer math as the kernels) on CPU at this
# width, depth and length; 0.099 on the v5e and on CPU over 40 tokens
# (PR 21). tests/test_quantized_path.py states 0.05 for its 2-layer model;
# the kernels themselves are held to their oracles by
# tools/kernel_verdicts.py.
INT8_LOGITS_REL_TOL = 0.18
# Served tokens: how far the reference logit of the token step() produced
# may trail the reference's best, in standard deviations of that logits
# row. served = ref + e picks a token at most 2 max|e| down, and the two
# candidates' errors are a few e_rms = a few (rel err x sigma) apart, so
# six times the logits tolerance (jnp bodies on CPU at this width: 0.04
# dense, 0.22 int8). A token from a wrongly fed page table or length is a
# random one: about 4 sigma down at vocab 32000.
DENSE_TOKEN_GAP = 0.24
INT8_TOKEN_GAP = 1.08
# The Mosaic selective scan against its XLA body on the same inputs, as max
# |difference| over max |reference| of the state and of y's live positions:
# float32 on both sides, the same equations in another order and with
# another exponential, over at most 16 positions.
SCAN_REL_TOL = 2e-5
# The latent walk against the gather-and-softmax body on the same bfloat16
# pages and queries, as ||kernel - body|| / ||body||: float32 accumulation
# on both sides; the kernel rounds its probabilities to the pages' dtype
# before the weighted sum (2^-9 a probability, averaged over hundreds of
# keys) and the output to bfloat16 (2^-9): 0.004 measured on the v5e.
LATENT_REL_TOL = 0.01
# The grouped SwiGLU against three ragged_dot on the same bfloat16 rows and
# weights: float32 accumulation and the same bfloat16 rounding of the
# activation on both sides, in another order of summation.
EXPERTS_REL_TOL = 0.01
# dp=2 x mp=2 cross entropy against the one-chip loss on the same weights
# and batch: the tolerance of __graft_entry__._run_variant.
MESH_CE_TOL = 2e-4


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def pallas_kernels(lowered_text: str) -> set:
    """Kernel names of the Mosaic custom calls in a lowered program."""
    return set(re.findall(r'kernel_name = "([^"]+)"', lowered_text))


def rematted_kernels(lowered_text: str) -> set:
    """Kernels that a lowered program (its text with debug_info) runs a
    second time, in the backward pass's recomputation of a layer: the
    ``pallas/<kernel>`` scopes under ``rematted_computation``."""
    return {loc.split("pallas/")[1].split("/")[0]
            for loc in re.findall(r'loc\("([^"]*pallas/[^"]*)"', lowered_text)
            if "rematted_computation" in loc}


def seeded_batch(cfg, batch: int, seq: int, seed: int = 0) -> dict:
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def run_trainer(cfg, *, batch: int, seq: int, steps: int, plan: Plan,
                expect_kernels: set) -> dict:
    """``plan.train_step(cfg)`` for ``steps`` steps on one seeded batch.
    On more than one device the first step's cross entropy is compared
    with the plain one-device loss on the same weights and batch, the
    parameter shardings with ``param_specs`` and the bytes in use on
    each device with an even share of the state."""
    devices = jax.devices()[:plan.world_size]
    step_fn, init_fn = plan.train_step(cfg, devices, verify=False)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    host = seeded_batch(cfg, batch, seq)
    placed = {k: jax.device_put(v, step_fn.batch_shardings[k])
              for k, v in host.items()}

    lowered = step_fn.lower(params, opt_state, placed).as_text(
        debug_info=True)
    found = pallas_kernels(lowered)
    log(f"train step kernels: {sorted(found)}")
    check(found == expect_kernels, f"train step runs Pallas kernels "
          f"{sorted(found)}, not {sorted(expect_kernels)}")
    again = rematted_kernels(lowered)
    check(not again, f"the backward pass runs {sorted(again)} a second "
          f"time: the layers do not keep the flash forward's output")

    plain_ce = None
    if len(devices) > 1:
        # host copy first: step_fn donates the placed weights
        dev0 = devices[0]
        _, plain_ce = jax.jit(functools.partial(llama.loss_fn, cfg))(
            jax.device_put(jax.tree_util.tree_map(np.asarray, params), dev0),
            jax.device_put(host, dev0))
        plain_ce = float(plain_ce)

    ces, times = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, placed)
        jax.block_until_ready((params, opt_state, metrics))
        fenced = time.perf_counter() - t0
        # a scalar read after a real fence has nothing left to wait for
        t1 = time.perf_counter()
        ce = float(metrics["ce"])
        readback = time.perf_counter() - t1
        ces.append(ce)
        times.append(fenced)
        log(f"train step {i}: ce {ce:.4f}  {fenced * 1e3:.1f} ms to "
            f"block_until_ready (+{readback * 1e3:.2f} ms scalar read)"
            + ("  [includes compile]" if i == 0 else ""))
    check(all(np.isfinite(ces)), f"non-finite loss {ces}")
    check(ces[-1] < ces[0], f"loss did not fall: {ces}")

    if plain_ce is not None:
        log(f"first-step ce {ces[0]:.6f} vs one-device {plain_ce:.6f}")
        np.testing.assert_allclose(ces[0], plain_ce, rtol=MESH_CE_TOL,
                                   atol=MESH_CE_TOL)
        is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)  # noqa: E731
        for leaf, spec in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(llama.param_specs(cfg),
                                          is_leaf=is_spec)):
            check(leaf.sharding.spec == spec,
                  f"parameter sharded {leaf.sharding.spec}, not {spec}")
        state = sum(x.nbytes for x in jax.tree_util.tree_leaves(
            (params, opt_state)))
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        log(f"bytes in use per device: "
            + ", ".join(f"{b / 2**30:.2f} GiB" for b in in_use)
            + f" (params + optimizer state {state / 2**30:.2f} GiB in all)")
        check(min(in_use) > 0 and max(in_use) < 0.6 * state,
              f"state is not spread over the devices: {in_use}")
    return {"ce": ces, "step_s": times}


def serve_prompts(cfg, chunk: int, page: int, n: int, seed: int = 1) -> list:
    """``n`` seeded prompts: even ones shorter than one prefill chunk, odd
    ones spanning several, the last one longer than two pages — so both
    engine buckets run and one block table walks three pages."""
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(2, chunk)) if i % 2 == 0
            else int(rng.integers(chunk + 1, 5 * chunk)) for i in range(n)]
    lens[-1] = 2 * page + chunk + 3
    return [rng.integers(0, cfg.vocab_size, ln).tolist() for ln in lens]


def run_server(cfg, params, *, kv_dtype, n_requests: int, n_new: int,
               expect_kernels: set):
    """Serve ``n_requests`` mixed-length requests through a default
    ``LLMEngine`` and drain it. Returns the live engine (the caller shuts
    it down) and each request's ``(prompt, generated tokens)``."""
    before = serving.serving_stats()
    eng = serving.LLMEngine(cfg, params, kv_dtype=kv_dtype)
    prompts = serve_prompts(cfg, eng.chunk, eng.page_size, n_requests)
    rids = [eng.add_request(p, n_new) for p in prompts]
    t0 = time.perf_counter()
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        check(steps < 100 * n_requests, "engine did not drain")
    wall = time.perf_counter() - t0

    for rid in rids:
        check(eng.state_of(rid).value == "finished",
              f"request {rid} ended {eng.state_of(rid).value}: "
              f"{eng.error_of(rid)}")
        check(len(eng.output_of(rid)) == n_new,
              f"request {rid} produced {len(eng.output_of(rid))}/{n_new}")
    check(eng.kv.audit()["ok"], f"page audit: {eng.kv.audit()}")
    check(sorted(eng._step_fns) == [1, eng.chunk],
          f"buckets compiled: {sorted(eng._step_fns)}")
    after = serving.serving_stats()
    for key in ("recoveries", "quarantined"):
        check(after[key] == before[key], f"serve {key} during the run")
    for Tc in (eng.chunk, 1):
        found = pallas_kernels(eng._lower(Tc).as_text())
        log(f"engine bucket Tc={Tc} kernels: {sorted(found)}")
        check(found == expect_kernels, f"engine bucket Tc={Tc} runs Pallas "
              f"kernels {sorted(found)}, not {sorted(expect_kernels)}")
    log(f"served {n_requests} requests x {n_new} tokens in {steps} steps, "
        f"{wall:.1f} s (compile included)")
    return eng, [(p, eng.output_of(rid)) for p, rid in zip(prompts, rids)]


def reference_logits(cfg, params, rows: list) -> list:
    """Logits of each token row from ``forward_pure`` in float32 (dense
    weights, unfused jnp composition, matmul precision "highest"). The
    rows are padded on the right to one length, which a causal model
    cannot see from the left."""
    ids = np.zeros((len(rows), max(map(len, rows))), np.int32)
    for i, row in enumerate(rows):
        ids[i, :len(row)] = row
    ref_cfg = dataclasses.replace(cfg, dtype=jnp.float32, fused_blocks="off",
                                  quantized="off")
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(functools.partial(llama.forward_pure, ref_cfg))(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params),
            jnp.asarray(ids))
    ref = np.asarray(ref)
    return [ref[i, :len(row)] for i, row in enumerate(rows)]


def replay_logits(eng, prompt: list, generated: list):
    """Logits of one served request, replayed on the engine's own state:
    ``eng.params`` (what it built, quantized or not), its page pools as
    serving left them and a block table from ``eng.kv`` — the prompt in
    ``eng.chunk`` pieces in slot 0 of the ``max_running``-wide batch, then
    ``generated`` one token at a time, the way ``step()`` fed them. The
    engine's executables return argmaxes only, so the logits come from
    the same ``forward_paged`` under a jit of this script's."""
    R, chunk = eng.max_running, eng.chunk
    ids = list(prompt) + list(generated)
    owner = "chip_smoke.replay"
    check(eng.kv.grow(owner, len(ids)), "no free pages for the replay")
    tbl = np.zeros((R, eng.max_blocks), np.int32)
    tbl[0] = eng.kv.block_row(owner)
    check(np.count_nonzero(tbl[0]) >= 3,
          f"the replayed request owns pages {tbl[0]}: fewer than three")

    @functools.partial(jax.jit,
                       donate_argnums=(2,) if eng._donate else ())
    def fwd(params, tokens, pools, tbl, lens, qlens):
        kp, vp, *scales = pools
        return eng._forward_paged(
            eng.cfg, params, tokens, kp, vp, tbl, lens, qlens,
            **dict(zip(("k_scales", "v_scales"), scales)))

    rows, pos = [], 0
    while pos < len(ids):
        q = min(chunk, len(prompt) - pos) if pos < len(prompt) else 1
        tokens = np.zeros((R, chunk if pos < len(prompt) else 1), np.int32)
        tokens[0, :q] = ids[pos:pos + q]
        lens = np.zeros((R,), np.int32)
        qlens = np.zeros((R,), np.int32)
        lens[0], qlens[0] = pos + q, q
        logits, eng._pools = fwd(
            eng.params, jnp.asarray(tokens), eng._pools, jnp.asarray(tbl),
            jnp.asarray(lens), jnp.asarray(qlens))
        rows.append(np.asarray(logits[0, :q]))
        pos += q
    eng.kv.release(owner)
    check(eng.kv.audit()["ok"], f"page audit after replay: {eng.kv.audit()}")
    return np.concatenate(rows)


def check_served(eng, params, served: list, *, logits_tol: float,
                 token_gap: float) -> float:
    """What the engine served against the float32 reference on the dense
    weights ``params`` it was built from: every token ``step()`` produced
    (teacher forced on the engine's own stream), and the logits of the
    longest request replayed on the engine's own state."""
    ref = reference_logits(eng.cfg, params,
                           [p + out[:-1] for p, out in served])
    worst, exact, total = 0.0, 0, 0
    for (prompt, out), logits in zip(served, ref):
        rows = logits[len(prompt) - 1:]               # one per token served
        gap = (rows.max(-1) - rows[np.arange(len(out)), out]) / rows.std(-1)
        worst = max(worst, float(gap.max()))
        exact += int((gap == 0).sum())
        total += len(out)
    log(f"served tokens vs forward_pure: {exact}/{total} are its argmax, "
        f"the worst trails it by {worst:.3f} sigma (tol {token_gap})")
    check(worst <= token_gap, f"a served token trails the reference's "
          f"choice by {worst:.3f} sigma > {token_gap}")

    i = max(range(len(served)), key=lambda j: len(served[j][0]))
    prompt, out = served[i]
    got = replay_logits(eng, prompt, out[:-1])
    check(got.shape == ref[i].shape and bool(np.all(np.isfinite(got))),
          f"replayed logits shape {got.shape} vs {ref[i].shape}, or "
          "non-finite")
    again = int((got[len(prompt) - 1:].argmax(-1) == out).sum())
    rel = float(np.linalg.norm(got - ref[i]) / np.linalg.norm(ref[i]))
    log(f"served logits vs forward_pure ({len(prompt)}+{len(out) - 1} "
        f"tokens on {-(-len(got) // eng.page_size)} pages): rel err "
        f"{rel:.4f} (tol {logits_tol}); the replay's argmax is the token "
        f"served at {again}/{len(out)} steps")
    check(rel <= logits_tol, f"served logits off by {rel:.4f} > {logits_tol}")
    return rel


def run_serving_passes(cfg, params, *, n_requests: int, n_new: int,
                       dense_kernels: set, int8_kernels: set) -> None:
    """The dense pass (bf16 weights, bf16 pages), then the int8 pass
    (``quantized="on"``, ``kv_dtype="int8"``) on a third of the requests,
    each checked against the reference while its engine is alive."""
    for pass_cfg, kv_dtype, n, kernels, tol, gap in (
            (cfg, None, n_requests, dense_kernels,
             DENSE_LOGITS_REL_TOL, DENSE_TOKEN_GAP),
            (dataclasses.replace(cfg, quantized="on"), "int8",
             max(2, n_requests // 3), int8_kernels,
             INT8_LOGITS_REL_TOL, INT8_TOKEN_GAP)):
        log(f"serving pass: quantized={pass_cfg.quantized} "
            f"kv_dtype={kv_dtype or 'model dtype'}")
        eng, served = run_server(pass_cfg, params, kv_dtype=kv_dtype,
                                 n_requests=n, n_new=n_new,
                                 expect_kernels=kernels)
        check_served(eng, params, served, logits_tol=tol, token_gap=gap)
        eng.shutdown()


def run_scan_parity(*, rows: tuple, inner: int, state: int, chunk: int,
                    expect_kernel: bool) -> None:
    """``selective_scan`` on layer 1 of a stack of three, ``R`` rows of
    ragged chunks (idle rows, decode rows, a few positions, whole chunks,
    fresh rows) on the compact flat batch of their tokens, NaN at every flat
    position that holds none, both programs, against ``_ssm_scan_jnp``; the
    other layers and the idle rows bit for bit."""
    layer, M = 1, 3
    for R in rows:
        for Tc in (chunk, 1):
            rng = np.random.default_rng(R * 100 + Tc)
            q = rng.choice([0, 1, 1, 1, 1, 1, min(3, Tc), Tc], R).astype(
                np.int32)
            start = np.cumsum(q) - q
            T = max(8, -(-int(q.sum()) // 8) * 8) + 8
            fresh = jnp.asarray((rng.random(R) < 0.25) & (q > 0))
            fed = np.zeros(T, bool)
            for r in range(R):
                fed[start[r]:start[r] + q[r]] = True
            dead = jnp.asarray(~fed)[:, None]

            def normal(*shape):
                return jnp.asarray(rng.standard_normal(shape), jnp.float32)

            ssm = normal(M, state, R, inner)
            dt = jax.nn.softplus(normal(T, inner) - 2.0)
            args = (dt, normal(T, inner), normal(T, state),
                    normal(T, state), -jnp.exp(0.3 * normal(state, inner)))
            tail = (jnp.asarray(q), jnp.asarray(start, jnp.int32), fresh)
            want_y, want_s = jax.jit(
                pallas_ops._ssm_scan_jnp, static_argnums=10)(
                    ssm, *args, *tail, layer, Tc)
            scan = jax.jit(functools.partial(pallas_ops.selective_scan,
                                             Tc=Tc, layer=layer))
            poisoned = tuple(jnp.where(dead, jnp.nan, a)
                             for a in args[:4]) + args[4:]
            names = pallas_kernels(scan.lower(ssm, *poisoned, *tail).as_text())
            check(names == ({SCAN_KERNEL} if expect_kernel else set()),
                  f"selective_scan [{R}, {Tc}] runs Pallas kernels {names}")
            got_y, got_s = (np.asarray(a) for a in scan(ssm, *poisoned, *tail))
            err_s = float(np.abs(got_s - want_s).max() / np.abs(want_s).max())
            err_y = float(np.abs(got_y - want_y)[fed].max()
                          / np.abs(np.asarray(want_y)[fed]).max())
            log(f"selective_scan [{R}, {Tc}]: state rel err {err_s:.2e}, "
                f"y rel err {err_y:.2e}")
            check(max(err_s, err_y) <= SCAN_REL_TOL,
                  f"selective_scan [{R}, {Tc}] off its XLA body")
            before = np.asarray(ssm)
            check(all(np.array_equal(got_s[m], before[m])
                      for m in range(M) if m != layer)
                  and np.array_equal(got_s[layer][:, q == 0],
                                     before[layer][:, q == 0]),
                  f"selective_scan [{R}, {Tc}] touched state it does not own")


def _rel(got, want) -> float:
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def run_latent_parity(*, rows: int, heads: int, lanes: int, v_lanes: int,
                      blocks: int, chunk: int, page: int = 128,
                      dtype=jnp.bfloat16, expect_kernel: bool) -> None:
    """``paged_latent_write`` then ``latent_paged_attention`` on layer 1 of a
    stack of three, ``rows`` requests of ragged lengths up to ``blocks``
    pages (prefill chunks, decode rows, idle rows), both programs, against
    their XLA bodies; what the write does not own bit for bit."""
    layer, M = 1, 3
    P = 1 + rows * blocks
    tbl = jnp.asarray(1 + np.arange(rows * blocks, dtype=np.int32).reshape(
        rows, blocks))
    for Tc in (chunk, 1):
        rng = np.random.default_rng(rows * 100 + Tc)
        q = rng.choice([0, 1, 1, min(3, Tc), Tc, Tc], rows).astype(np.int32)
        lens = np.where(q > 0, rng.integers(Tc, blocks * page, rows), 0) \
            .astype(np.int32)

        def normal(*shape):
            return jnp.asarray(rng.standard_normal(shape), jnp.float32
                               ).astype(dtype)

        pages, new = normal(M, 1, P, page, lanes), normal(rows, Tc, 1, lanes)
        queries = normal(rows, 1, Tc * heads, lanes)
        tail = (tbl, jnp.asarray(lens), jnp.asarray(q))
        write = jax.jit(functools.partial(pallas_ops.paged_latent_write,
                                          layer=layer))
        attend = jax.jit(functools.partial(
            pallas_ops.latent_paged_attention, rep=heads, v_lanes=v_lanes,
            scale=lanes ** -0.5, layer=layer))
        names = pallas_kernels(write.lower(pages, new, *tail).as_text()) \
            | pallas_kernels(attend.lower(queries, pages, *tail).as_text())
        check(names == (LATENT_KERNELS if expect_kernel else set()),
              f"latent pages [{rows}, {Tc}] run Pallas kernels {names}")
        want_p = pallas_ops._pools_write_jnp((pages,), (new,), *tail,
                                             layer)[0]
        got_p = write(pages, new, *tail)
        check(bool(jnp.array_equal(got_p, want_p)),
              f"paged_latent_write [{rows}, {Tc}] off the XLA row scatter")
        want = jax.jit(functools.partial(
            pallas_ops._ragged_attention_jnp, rep=heads, layer=layer,
            scale=lanes ** -0.5))(queries, want_p, want_p[..., :v_lanes],
                                  *tail)
        err = _rel(attend(queries, got_p, *tail), want)
        log(f"latent_paged_attention [{rows}, {Tc}]: rel err {err:.2e} over "
            f"{int(lens.sum())} cached tokens")
        check(err <= LATENT_REL_TOL,
              f"latent_paged_attention [{rows}, {Tc}] off its XLA body")


def run_experts_parity(*, experts: int, hidden: int, width: int, per_token:
                       int, tokens: tuple, dtype=jnp.bfloat16,
                       expect_kernel: bool) -> None:
    """``grouped_experts`` on layer 1 of a stack of two: the rows of each of
    ``tokens`` tokens' ``per_token`` distinct experts, sorted by expert and
    padded to whole tiles, against ``_moe_experts_jnp``."""
    rng = np.random.default_rng(experts)
    keys = iter(jax.random.split(jax.random.PRNGKey(experts), 8))

    def normal(*shape, std=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    stacks = (normal(2, experts, hidden, width, std=0.02),
              normal(2, experts, hidden, width, std=0.02),
              normal(2, experts, width, hidden, std=0.02))
    for T in tokens:
        chosen = np.stack([rng.permutation(experts)[:per_token]
                           for _ in range(T)])
        sizes = jnp.asarray(np.bincount(chosen.reshape(-1),
                                        minlength=experts).astype(np.int32))
        n = T * per_token
        xs = normal(n, hidden)
        fn = jax.jit(functools.partial(pallas_ops.grouped_experts, layer=1))
        names = pallas_kernels(fn.lower(xs, sizes, *stacks).as_text())
        check(names == ({EXPERTS_KERNEL} if expect_kernel else set()),
              f"grouped_experts [{n} rows] runs Pallas kernels {names}")
        want = jax.jit(functools.partial(pallas_ops._moe_experts_jnp,
                                         layer=1))(xs, sizes, *stacks)
        err = _rel(fn(xs, sizes, *stacks)[:n], want[:n])
        log(f"grouped_experts [{n} rows over {int((sizes > 0).sum())} of "
            f"{experts} experts, at most {int(sizes.max())}]: rel err "
            f"{err:.2e}")
        check(err <= EXPERTS_REL_TOL,
              f"grouped_experts [{n} rows] off three ragged_dot")


def run_four_chip(cfg, *, batch: int, seq: int, steps: int) -> None:
    """Hybrid-parallel steps on four chips in this one process. What the
    compiled step does with each kernel is a rule of ``pallas_ops.kernel_axes``:
    ``Plan(dp=2, mp=2)`` — GSPMD step, flash attention per device (batch on
    dp, heads on mp); ``Plan(dp=4)`` — flash attention per device over the
    batch; ``Plan(pp=2, mp=2)`` 1F1B — no Pallas kernel inside the
    pipeline's partially manual region."""
    for plan, kernels in (
            (Plan(dp=2, mp=2), TRAIN_KERNELS),
            (Plan(dp=4), TRAIN_KERNELS),
            (Plan(pp=2, mp=2, schedule="1f1b", n_microbatches=4), set())):
        log(f"plan {plan.to_spec()['axes']} schedule {plan.schedule}")
        run_trainer(cfg, batch=batch, seq=seq, steps=steps, plan=plan,
                    expect_kernels=kernels)


def cache_counts() -> tuple:
    """(hits, entries written) of the persistent compile cache so far:
    jax counts a miss when it writes an entry, so a warm run of a phase
    shows hits and writes nothing."""
    st = compile_tracker.stats()
    return st["persistent_cache_hits"], st["persistent_cache_misses"]


def main() -> int:
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {json.dumps(device)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this script measures nothing "
              "without one", file=sys.stderr)
        return 1
    check(pallas_ops._INTERPRET is False, "Pallas kernels are interpreted")
    compile_tracker.install()
    cfg = llama.preset("llama1b")

    def done(phase: str, t0: float, c0: tuple) -> None:
        hits, written = cache_counts()
        log(f"{phase} ok: phase {time.perf_counter() - t0:.1f} s, compile "
            f"cache +{hits - c0[0]} hits, +{written - c0[1]} entries written")

    t0, c0 = time.perf_counter(), cache_counts()
    out = run_trainer(cfg, batch=4, seq=2048, steps=4, plan=Plan(),
                      expect_kernels=TRAIN_KERNELS)
    log(f"trainer: ce {out['ce'][0]:.4f} -> {out['ce'][-1]:.4f}, median "
        f"warm step {np.median(out['step_s'][1:]) * 1e3:.1f} ms")
    done("trainer", t0, c0)

    t0, c0 = time.perf_counter(), cache_counts()
    params = jax.jit(functools.partial(llama.init_params, cfg))(
        jax.random.PRNGKey(1))
    run_serving_passes(cfg, params, n_requests=8, n_new=8,
                       dense_kernels=SERVE_KERNELS,
                       int8_kernels=SERVE_KERNELS_INT8)
    del params
    done("server", t0, c0)

    t0, c0 = time.perf_counter(), cache_counts()
    run_scan_parity(rows=(128, 48), inner=5120, state=16, chunk=16,
                    expect_kernel=True)
    done("scan", t0, c0)

    t0, c0 = time.perf_counter(), cache_counts()
    run_latent_parity(rows=32, heads=16, lanes=640, v_lanes=512, blocks=72,
                      chunk=16, expect_kernel=True)
    done("latent", t0, c0)

    t0, c0 = time.perf_counter(), cache_counts()
    run_experts_parity(experts=64, hidden=2048, width=1408, per_token=6,
                       tokens=(256, 32), expect_kernel=True)
    done("experts", t0, c0)

    if device["count"] >= 4:
        t0, c0 = time.perf_counter(), cache_counts()
        run_four_chip(cfg, batch=4, seq=2048, steps=3)
        done("four chips", t0, c0)
    else:
        log(f"{device['count']} chip(s): the four-chip part is skipped, "
            "nothing else")

    hits, written = cache_counts()
    st = compile_tracker.stats()
    log(f"compile cache {compile_cache.cache_dir()}: {hits} hits, {written} "
        f"entries written; {st['compile_count']} backend compiles, "
        f"{st['compile_seconds']:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
