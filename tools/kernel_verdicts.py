"""Compile and check every Pallas kernel family at llama1b widths.

One verdict per family the two entry points (``Plan.train_step``,
``serving.LLMEngine``) can select at hidden 2048 / 16 heads x 128 /
intermediate 5504: the kernel is compiled by Mosaic, uninterpreted, run,
and compared with its jnp body on seeded inputs.

  python tools/kernel_verdicts.py        # on the chip, one process

A family that fails is reported and the sweep goes on, so one call yields
every verdict; the exit code is non-zero if any family failed, and 2
without a TPU. Verdicts with their relative errors go to stdout and, as
JSON, to ``chiprun_out/kernel_verdicts.json``.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# llama1b widths (models/llama.py PRESETS)
H, NH, D, I, V = 2048, 16, 128, 5504, 32000
# engine defaults (serving/engine.py): batch width, prefill chunk, page
R, CHUNK, PAGE = 8, 16, 128
NUM_PAGES, BMAX = 257, 32      # max_model_len 4096 / page 128, +1 null page
LAYERS, LAYER = 2, 1           # the pools are stacked; the kernels index one


def _cases():
    """[(name, kernel_fn, reference_fn, make_args(key), rel_tol)]. Each fn
    maps the args to a pytree of arrays; kernel and reference are
    compared leaf by leaf on max |diff| / max |ref|."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_ops as po

    bf16 = jnp.bfloat16

    def grads_of(fn, n):
        def run(*args):
            def scalar(*diff):
                out = fn(*diff, *args[n:])
                return jnp.sum(out.astype(jnp.float32)
                               * jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                                         ).reshape(out.shape))
            out = fn(*args)
            return out, jax.grad(scalar, argnums=tuple(range(n)))(*args[:n])
        return run

    def qkv_args(B, S, nh):
        def make(key):
            ks = jax.random.split(key, 3)
            return tuple(jax.random.normal(k, (B, S, nh, D), bf16) * 0.5
                         for k in ks)
        return make

    cases = [
        ("flash_resident_fwd_bwd[S=2048]",
         grads_of(po.causal_attention, 3), grads_of(po._attention_jnp, 3),
         qkv_args(4, 2048, NH), 3e-2),
        # streamed takes over past _use_resident (S ~ 4900 at D=128);
        # 4 heads keep the [B,H,S,S] f32 reference inside HBM
        ("flash_streamed_fwd_bwd[S=8192]",
         grads_of(po.causal_attention, 3), grads_of(po._attention_jnp, 3),
         qkv_args(1, 8192, 4), 3e-2),
    ]

    def rope_tables(S):
        half = D // 2
        inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
        emb = jnp.concatenate([ang, ang], axis=-1)
        return jnp.sin(emb), jnp.cos(emb)

    def attn_block_args(key, B=2, S=2048):
        ks = jax.random.split(key, 6)
        x = jax.random.normal(ks[0], (B, S, H), bf16) * 0.5
        ln = (1.0 + 0.1 * jax.random.normal(ks[1], (H,))).astype(bf16)
        ws = tuple((jax.random.normal(k, (H, H)) * 0.02).astype(bf16)
                   for k in ks[2:6])
        return (x, ln) + ws + rope_tables(S)

    cases.append((
        "fused_attention_block_fwd_bwd",
        grads_of(functools.partial(po.fused_attention_block, head_dim=D), 6),
        grads_of(functools.partial(po._attention_block_jnp, head_dim=D,
                                   eps=1e-6), 6),
        attn_block_args, 3e-2))

    def mlp_block_args(key, B=2, S=2048):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, S, H), bf16) * 0.5
        ln = (1.0 + 0.1 * jax.random.normal(ks[1], (H,))).astype(bf16)
        wg = (jax.random.normal(ks[2], (H, I)) * 0.02).astype(bf16)
        wu = (jax.random.normal(ks[3], (H, I)) * 0.02).astype(bf16)
        wd = (jax.random.normal(ks[4], (I, H)) * 0.02).astype(bf16)
        return (x, ln, wg, wu, wd)

    cases.append((
        "fused_mlp_block_fwd_bwd",
        grads_of(po.fused_mlp_block, 5),
        grads_of(functools.partial(po._mlp_block_jnp, eps=1e-6), 5),
        mlp_block_args, 3e-2))

    # ragged paged attention at the engine's two buckets, in the form the
    # engine runs: the stacked pools of LAYERS layers and a layer other
    # than 0, against the jnp body on that layer's own 4-D pool; pages
    # shuffled as an allocator leaves them, ragged kv lengths.  MHA
    # (rep=1, the widths above) dense and int8; grouped heads as the
    # benchmark's models have them: rep 2 on 8 kv heads, rep 20 on one
    def rpa_args(Tc, quant, nkv=NH, rep=1):
        def make(key):
            ks = jax.random.split(key, 3)
            rng = np.random.RandomState(0)
            q = jax.random.normal(ks[0], (R, nkv, Tc * rep, D), bf16) * 0.5
            stack = (LAYERS, nkv, NUM_PAGES, PAGE, D)
            kp = jax.random.normal(ks[1], stack) * 0.5
            vp = jax.random.normal(ks[2], stack) * 0.5
            tbl = (1 + rng.permutation(NUM_PAGES - 1)[:R * BMAX]).reshape(
                R, BMAX).astype(np.int32)
            lens = rng.randint(Tc, BMAX * PAGE, size=(R,)).astype(np.int32)
            lens[0] = Tc                      # a request on its first chunk
            qlens = np.full((R,), Tc, np.int32)
            qlens[-1] = 0                     # an inactive slot
            if Tc > 1:
                qlens[1] = 1                  # a decode row in the mixed bucket
            out = (q,)
            if quant:
                amax = jnp.max(jnp.abs(kp), axis=(3, 4))
                ksc = jnp.maximum(amax, 1e-8) / 127.0
                vsc = jnp.maximum(jnp.max(jnp.abs(vp), axis=(3, 4)),
                                  1e-8) / 127.0
                kq = jnp.round(kp / ksc[..., None, None]).astype(jnp.int8)
                vq = jnp.round(vp / vsc[..., None, None]).astype(jnp.int8)
                out += (kq, vq, jnp.asarray(tbl), jnp.asarray(lens),
                        jnp.asarray(qlens), ksc, vsc)
            else:
                out += (kp.astype(bf16), vp.astype(bf16), jnp.asarray(tbl),
                        jnp.asarray(lens), jnp.asarray(qlens))
            return out
        return make

    def rpa(q, kp, vp, tbl, lens, qlens, ksc=None, vsc=None, rep=1):
        return po.ragged_paged_attention(q, kp, vp, tbl, lens, qlens,
                                         rep=rep, k_scales=ksc, v_scales=vsc,
                                         layer=LAYER)

    def rpa_ref(q, kp, vp, tbl, lens, qlens, ksc=None, vsc=None, rep=1):
        one = [None if a is None else a[LAYER] for a in (kp, vp, ksc, vsc)]
        return po._ragged_attention_jnp(q, one[0], one[1], tbl, lens, qlens,
                                        rep, one[2], one[3])

    cases += [
        (f"rpa_mixed[Tc={CHUNK}]", rpa, rpa_ref, rpa_args(CHUNK, False), 3e-2),
        ("rpa_decode[Tc=1]", rpa, rpa_ref, rpa_args(1, False), 3e-2),
        (f"rpa_quant_mixed[Tc={CHUNK}]", rpa, rpa_ref,
         rpa_args(CHUNK, True), 3e-2),
        ("rpa_quant_decode[Tc=1]", rpa, rpa_ref, rpa_args(1, True), 3e-2),
    ]
    for nkv, rep in ((8, 2), (1, 20)):
        fns = [functools.partial(f, rep=rep) for f in (rpa, rpa_ref)]
        cases += [
            (f"rpa_mixed[Tc={CHUNK},nkv={nkv},rep={rep}]", *fns,
             rpa_args(CHUNK, False, nkv, rep), 3e-2),
            (f"rpa_decode[Tc=1,nkv={nkv},rep={rep}]", *fns,
             rpa_args(1, False, nkv, rep), 3e-2),
        ]

    # the write of a step's new tokens into the same stacked pools, in
    # place, against the XLA row scatter: exact, whole pools compared
    def kv_write_args(Tc):
        def make(key):
            q, kp, vp, tbl, lens, qlens = rpa_args(Tc, False)(key)
            ks = jax.random.split(jax.random.fold_in(key, 1), 2)
            new = tuple(jax.random.normal(k, (R, Tc, NH, D), bf16)
                        for k in ks)
            return (kp, vp) + new + (tbl, lens, qlens)
        return make

    def kv_write(*a):
        return po.paged_kv_write(*a, layer=LAYER)

    def kv_write_ref(*a):
        return po._pools_write_jnp(a[:2], a[2:4], *a[4:], LAYER)

    cases += [
        (f"paged_kv_write[Tc={CHUNK}]", kv_write, kv_write_ref,
         kv_write_args(CHUNK), 0.0),
        ("paged_kv_write[Tc=1]", kv_write, kv_write_ref,
         kv_write_args(1), 0.0),
    ]

    # int8 weight matmul at every (M, K, N) forward_paged issues:
    # M = R*Tc rows of the two buckets; N over attn / mlp / lm_head
    def int8_args(M, K, N):
        def make(key):
            k1, k2 = jax.random.split(key)
            x = jax.random.normal(k1, (M, K), bf16)
            wq, ws = po.quantize_int8(jax.random.normal(k2, (K, N)) * 0.02)
            return (x, wq, ws)
        return make

    for M in (R * CHUNK, R):
        for K, N in ((H, H), (H, I), (I, H), (H, V)):
            # the jnp oracle is the same integer math: near-exact
            cases.append((f"int8_matmul[M={M},K={K},N={N}]", po.int8_matmul,
                          lambda x, wq, ws: po._int8_matmul_jnp(
                              x, wq, ws.reshape(1, -1)),
                          int8_args(M, K, N), 1e-2))
    return cases


def _max_rel_err(got, ref):
    import jax
    import numpy as np
    worst = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if g.shape != r.shape or not np.all(np.isfinite(g)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(g - r))
                                 / max(float(np.max(np.abs(r))), 1e-6)))
    return worst


def _pallas_calls(text: str) -> int:
    return text.count("tpu_custom_call")


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_verdicts: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    kind = dev.device_kind
    print(f"kernel_verdicts: {kind}, compile + run + compare")

    verdicts = []
    for name, fn, ref, make_args, tol in _cases():
        t0 = time.perf_counter()
        v = {"kernel": name}
        try:  # noqa: a refused family is the verdict, the sweep goes on
            a = make_args(jax.random.PRNGKey(0))
            compiled = jax.jit(fn).lower(*a).compile()
            n = _pallas_calls(compiled.as_text())
            got = jax.block_until_ready(compiled(*a))
            want = jax.block_until_ready(jax.jit(ref)(*a))
            err = _max_rel_err(got, want)
            v.update(verdict="compiles, matches reference"
                     if n > 0 and err <= tol else "FAILED: off its reference"
                     if n > 0 else "FAILED: no Pallas call in the executable",
                     pallas_calls=n, rel_err=round(err, 6), tol=tol)
        except Exception as e:  # noqa: BLE001
            v.update(verdict=f"FAILED: {type(e).__name__}",
                     error=str(e)[-3000:])
            traceback.print_exc(limit=3, file=sys.stderr)
        v["seconds"] = round(time.perf_counter() - t0, 2)
        verdicts.append(v)
        print(f"  {name}: {v['verdict']}, rel err {v.get('rel_err')} "
              f"(tol {tol}), {v['seconds']} s", flush=True)

    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_verdicts.json"), "w") as f:
        json.dump({"device_kind": kind, "verdicts": verdicts}, f, indent=1)
    failed = [v for v in verdicts if v["verdict"].startswith("FAILED")]
    print(f"kernel_verdicts: {len(verdicts) - len(failed)}/{len(verdicts)} ok")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
