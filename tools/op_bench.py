"""Op microbenchmark harness with a regression gate.

Reference analog: paddle/fluid/operators/benchmark/op_tester.cc (per-op
latency measurement from config) + tools/ci_op_benchmark.sh (the CI
gate that fails a PR when an op's time regresses against the recorded
baseline).

Usage:
  python tools/op_bench.py                 # measure, print table
  python tools/op_bench.py --record        # measure + write baseline
  python tools/op_bench.py --check         # measure + fail on >25% regr.
  python tools/op_bench.py --ops matmul,flash_attention

Baselines are stored per device kind (a CPU number never gates a TPU
run) in tools/op_bench_baseline.json. Timing uses the autotune module's
chained-execution timer so the measurement is device compute, not
host-transfer overhead.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "op_bench_baseline.json")
# fail --check when slower than baseline * this (overridable for noisy
# hosts / CI tiers)
THRESHOLD = float(os.environ.get("PTQ_OP_BENCH_THRESHOLD", "1.25"))


def _cases(quick=False):
    """name -> (build() -> (fn, args)); shapes sized for one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    S = 512 if quick else 2048
    B = 1 if quick else 4
    H = 1024 if quick else 4096

    def matmul():
        k = jax.random.PRNGKey(0)
        a = jax.random.normal(k, (H, H), jnp.bfloat16)
        b = jax.random.normal(k, (H, H), jnp.bfloat16)
        return jax.jit(lambda x, y: x @ y), (a, b)

    def flash_attention():
        from paddle_tpu.ops import pallas_ops
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        d, heads = 128, 8
        q, kk, v = (jax.random.normal(x, (B, S, heads, d), jnp.bfloat16)
                    for x in k)  # [B, S, H, D] — causal_attention layout

        def attn(q, k, v):
            return pallas_ops.causal_attention(q, k, v)
        return jax.jit(attn), (q, kk, v)

    def layernorm_residual():
        k = jax.random.PRNGKey(1)
        x = jax.random.normal(k, (B * S, H), jnp.bfloat16)
        g = jnp.ones((H,), jnp.float32)

        def f(x, g):
            m = jnp.mean(x.astype(jnp.float32), -1, keepdims=True)
            v = jnp.var(x.astype(jnp.float32), -1, keepdims=True)
            return ((x - m) * jax.lax.rsqrt(v + 1e-6) * g).astype(x.dtype) + x
        return jax.jit(f), (x, g)

    def embedding_gather():
        k = jax.random.PRNGKey(2)
        table = jax.random.normal(k, (32000, H), jnp.bfloat16)
        ids = jax.random.randint(k, (B * S,), 0, 32000)
        return jax.jit(lambda t, i: t[i]), (table, ids)

    def fused_adamw_update():
        import optax
        k = jax.random.PRNGKey(3)
        p = {"w": jax.random.normal(k, (H, H), jnp.float32)}
        opt = optax.adamw(1e-3)
        st = opt.init(p)
        g = {"w": jax.random.normal(k, (H, H), jnp.float32)}

        @jax.jit
        def upd(p, st, g):
            u, st = opt.update(g, st, p)
            return optax.apply_updates(p, u), st
        return upd, (p, st, g)

    def softmax_ce():
        k = jax.random.PRNGKey(4)
        logits = jax.random.normal(k, (B * S, 32000), jnp.float32)
        labels = jax.random.randint(k, (B * S,), 0, 32000)

        def f(lg, lb):
            ls = jax.nn.log_softmax(lg)
            return -jnp.mean(jnp.take_along_axis(ls, lb[:, None], 1))
        return jax.jit(f), (logits, labels)

    def llama_train_step():
        # End-to-end rung: a small train step (vocab 1024 / hidden 256 /
        # 4 layers / S 256 / B 2). Gating this one case catches gross
        # train-step regressions on the machine the baseline was
        # recorded on.
        import functools

        import optax

        from paddle_tpu.models.llama import LlamaConfig, init_params, loss_fn

        cfg = LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=512,
            dtype=jnp.float32, use_remat=False)
        Bs, Ss = 2, 256
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, batch):
            (_, ce), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, ce

        rng = np.random.default_rng(0)
        batch = {
            "input_ids": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (Bs, Ss)), jnp.int32),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (Bs, Ss)), jnp.int32),
        }
        return functools.partial(step, params, opt_state), (batch,)

    def llama_decode():
        # Generation rung: prefill + 16 greedy decode steps as the one
        # compiled scan models/decoding.py serves — gates KV-cache
        # decode throughput the way llama_train_step gates training.
        import functools

        from paddle_tpu.models.llama import (LlamaConfig, generate,
                                             init_params)

        cfg = LlamaConfig(
            vocab_size=1024, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=512,
            dtype=jnp.float32, use_remat=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                             jnp.int32)

        def decode(params, prompt):
            return generate(cfg, params, prompt, max_new_tokens=16)
        return functools.partial(decode, params), (prompt,)

    return {
        "matmul_bf16": matmul,
        "flash_attention": flash_attention,
        "layernorm_residual": layernorm_residual,
        "embedding_gather": embedding_gather,
        "fused_adamw_update": fused_adamw_update,
        "softmax_ce": softmax_ce,
        "llama_train_step": llama_train_step,
        "llama_decode": llama_decode,
    }


def measure(names=None, quick=False, iters=None):
    import jax

    from paddle_tpu.ops.autotune import time_callable
    from paddle_tpu.profiler import compile_tracker

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "cpu")
    cases = _cases(quick=quick)
    names = names or list(cases)
    n_iter = iters or (2 if quick else 5)
    out = {}
    compile_info = {}
    for name in names:
        if name not in cases:
            raise SystemExit(f"unknown op case {name!r}; "
                             f"have {sorted(cases)}")
        # per-op compile attribution: a timing regression caused by a
        # recompile (vs a genuinely slower kernel) shows up as a compile
        # delta during the measured window
        pre = compile_tracker.stats()
        fn, args = cases[name]()
        t = time_callable(fn, args, warmup=1, iters=n_iter)
        post = compile_tracker.stats()
        out[name] = round(t * 1e3, 4)  # ms
        compile_info[name] = {
            "compiles": post["compile_count"] - pre["compile_count"],
            "compile_s": round(
                post["compile_seconds"] - pre["compile_seconds"], 4),
        }
        print(f"{name:24s} {out[name]:10.3f} ms   "
              f"[{compile_info[name]['compiles']} compiles, "
              f"{compile_info[name]['compile_s']:.2f} s]", flush=True)
    return kind, out, compile_info


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true",
                    help="write measurements as the new baseline")
    ap.add_argument("--check", action="store_true",
                    help="fail (rc=1) on regression vs baseline")
    ap.add_argument("--ops", default=None,
                    help="comma-separated case subset")
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / fewer iters (harness smoke)")
    ap.add_argument("--strict", action="store_true",
                    help="with --check: a measured op with no recorded "
                         "baseline FAILS instead of being skipped, so new "
                         "ops cannot slip past the gate un-recorded")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a JSON telemetry sidecar (per-op compile "
                         "count/seconds + wall ms) so a BENCH_*.json "
                         "regression can be attributed to recompiles")
    args = ap.parse_args(argv)

    names = args.ops.split(",") if args.ops else None

    book = {}
    if os.path.exists(BASELINE):
        with open(BASELINE) as f:
            book = json.load(f)

    import platform
    host = platform.node()

    if args.record:
        # refuse BEFORE the (potentially minutes-long) measurement:
        # every input to the check is already known
        import jax
        kind0 = getattr(jax.devices()[0], "device_kind", "cpu")
        key0 = f"{kind0}{'|quick' if args.quick else ''}"
        prev = book.get(key0, {})
        prev_host = prev.get("__host__")
        will_record = set(names or _cases(quick=args.quick))
        survivors = set(prev) - will_record - {"__host__"}
        if prev_host is not None and prev_host != host and survivors:
            # merging would relabel host-A wall-clocks as host-B's and
            # gate them at the strict same-host threshold
            raise SystemExit(
                f"refusing partial --record: {key0!r} was recorded on "
                f"{prev_host!r} and ops {sorted(survivors)} would keep "
                f"its numbers under this host's ({host!r}) label. "
                "Re-record ALL ops (drop --ops) or delete the key from "
                f"{BASELINE} first.")

    kind, results, compile_info = measure(names, quick=args.quick)
    key = f"{kind}{'|quick' if args.quick else ''}"

    if args.metrics_out:
        sidecar = {
            "device_kind": kind,
            "host": host,
            "ops": {n: {"ms": results[n], **compile_info[n]}
                    for n in results},
        }
        with open(args.metrics_out, "w") as f:
            json.dump(sidecar, f, indent=1, sort_keys=True)
        print(f"telemetry sidecar -> {args.metrics_out}")

    if args.record:
        book.setdefault(key, {}).update(results)
        book[key]["__host__"] = host
        with open(BASELINE, "w") as f:
            json.dump(book, f, indent=1, sort_keys=True)
        print(f"baseline recorded for {key!r} -> {BASELINE}")
        return 0

    if args.check:
        base = book.get(key, {})
        threshold = THRESHOLD
        rec_host = base.get("__host__")
        if rec_host is not None and rec_host != host:
            # a committed baseline from another machine still catches
            # GROSS regressions, but absolute wall-clock does not port
            # across hosts at the same-host threshold
            xf = float(os.environ.get("PTQ_OP_BENCH_XHOST_FACTOR", "3"))
            threshold *= xf
            print(f"baseline recorded on {rec_host!r}, running on "
                  f"{host!r}: threshold relaxed to {threshold:.2f}x")
        bad = []
        missing = []
        for name, ms in results.items():
            ref = base.get(name)
            if ref is None:
                missing.append(name)
                print(f"{name}: no baseline for {key!r} "
                      f"({'FAIL (--strict)' if args.strict else 'skipped'})")
                continue
            ratio = ms / ref
            status = "OK" if ratio <= threshold else "REGRESSION"
            print(f"{name:24s} {ms:10.3f} ms vs {ref:10.3f} ms "
                  f"({ratio:5.2f}x) {status}")
            if ratio > threshold:
                bad.append((name, ratio))
        if bad:
            print(f"FAILED: {len(bad)} op(s) regressed >"
                  f"{(threshold - 1) * 100:.0f}%: {bad}")
            return 1
        if args.strict and missing:
            print(f"FAILED (--strict): {len(missing)} op(s) have no "
                  f"baseline for {key!r}: {missing}; run --record first")
            return 1
        print("all ops within threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
