"""Quantized end-to-end path (ISSUE 15): int8 weight matmuls, the
quantized paged-KV cache, and their serving integration.

Coverage per the issue's test satellite:

  * int8 matmul forward parity vs the jnp oracle (interpret-mode Pallas
    at lane-aligned shapes, jnp fallback elsewhere) and the dead-channel
    scale guard — including the ``_absmax_scale`` fp16-underflow
    regression in inference/convert.py;
  * dense-bf16 vs quantized-KV parity within tolerance through
    ``LLMEngine`` streams, including the prefix-cache hit, preemption
    replay, and spec-decode verify paths;
  * a ``plan_capacity`` unit asserting >= 1.9x max-concurrent capacity
    at int8 page dtype;
  * registry/numerics plumbing: the new kernel cases are registered
    with the Level-3 verifier and ``quant_err_*`` gauges land in the
    Numerics summary's Quantization block.

Tolerance contract (docs/serving.md): quantized-KV streams are parity
WITHIN TOLERANCE against dense bf16/f32 — NOT bit-identical, and exempt
from the PR 11/12 bit-exact stream guarantees.  What IS pinned exactly:
quantized writes are a pure function of the request's own tokens (stale
bytes on recycled pages are masked out of the page absmax), so replay
after preemption reproduces the unpreempted quantized streams and
every configuration is deterministic.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.inference.convert import _absmax_scale
from paddle_tpu.models import llama
from paddle_tpu.models.decoding import init_kv_cache
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import numerics


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_executables():
    # The engine tests below compile dozens of distinct step functions.
    # Left resident in the XLA CPU client they push the suite's total
    # loaded-executable count high enough to trip a flaky segfault in a
    # *later* module's backend_compile; drop them once this module is done.
    yield
    jax.clear_caches()


def _tiny_cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, dtype=jnp.float32, use_remat=False)
    base.update(kw)
    return llama.LlamaConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _dense_greedy(cfg, params, prompt, n):
    cache = init_kv_cache(cfg.num_hidden_layers, 1, len(prompt) + n,
                          cfg.num_key_value_heads, cfg.head_dim,
                          dtype=jnp.float32)
    ids = jnp.asarray([prompt], jnp.int32)
    logits, cache = llama.forward_with_cache(cfg, params, ids, cache, 0)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n - 1):
        logits, cache = llama.forward_with_cache(
            cfg, params, jnp.asarray([[out[-1]]], jnp.int32), cache, pos)
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


@pytest.fixture(scope="module")
def shared_workload(model):
    """8 requests over 2 system prompts: shared head, divergent tail."""
    cfg, params = model
    rng = np.random.RandomState(5)
    sys_a = [int(t) for t in rng.randint(1, 127, 13)]
    sys_b = [int(t) for t in rng.randint(1, 127, 9)]
    prompts = []
    for i in range(8):
        tail = [int(t) for t in rng.randint(1, 127, 3 + i % 3)]
        prompts.append((sys_a if i % 2 == 0 else sys_b) + tail)
    n_new = 8
    expect = [_dense_greedy(cfg, params, p, n_new) for p in prompts]
    return prompts, n_new, expect


def _agreement(got, expect):
    """Fraction of positions where the streams agree (and same length)."""
    assert len(got) == len(expect)
    if not expect:
        return 1.0
    return sum(g == e for g, e in zip(got, expect)) / len(expect)


def _run_engine(cfg, params, prompts, n_new, **kw):
    kw.setdefault("max_running", 4)
    kw.setdefault("chunk", 8)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 200)
    kw.setdefault("donate_pools", False)
    eng = serving.LLMEngine(cfg, params, **kw)
    rids = [eng.add_request(list(p), n_new) for p in prompts]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 2000, "engine did not converge"
    return eng, [eng.output_of(r) for r in rids]


# ---------------------------------------------------------------------------
# int8 weight quantization: scale rule + dead-channel guards
# ---------------------------------------------------------------------------


def test_quantize_int8_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    q, scale = pallas_ops.quantize_int8(w)
    assert q.dtype == jnp.int8 and scale.shape == (1, 96)
    # symmetric absmax round-trip: error <= scale/2 per element
    err = jnp.abs(q.astype(jnp.float32) * scale - w)
    assert bool(jnp.all(err <= scale * 0.5 + 1e-7))


def test_quantize_int8_dead_channel_guard():
    """All-zero / non-finite output channels take the benign 1/127
    scale: q == 0, dequant == exact 0, and the scale SURVIVES a cast
    to float16 (an epsilon-derived scale like 1e-8/127 underflows the
    fp16 subnormal floor and turns dequant into inf/NaN downstream)."""
    w = np.ones((32, 8), np.float32)
    w[:, 2] = 0.0            # dead channel
    w[:, 5] = np.nan         # poisoned channel
    q, scale = pallas_ops.quantize_int8(jnp.asarray(w))
    scale = np.asarray(scale)[0]
    assert scale[2] == pytest.approx(1.0 / 127.0)
    assert scale[5] == pytest.approx(1.0 / 127.0)
    assert float(np.asarray(scale, np.float16)[2]) > 0.0
    deq = np.asarray(q, np.float32) * scale
    assert np.all(deq[:, 2] == 0.0)
    assert np.all(np.isfinite(deq[:, 2] / scale[2]))


def test_absmax_scale_dead_channel_fp16_regression():
    """inference/convert.py edition of the same guard: a dead channel's
    scale must not underflow to 0.0 when stored in float16."""
    w = np.random.RandomState(1).standard_normal((64, 16)) \
        .astype(np.float32)
    w[:, 3] = 0.0
    scale = _absmax_scale(w, axis=1)
    assert scale.dtype == np.float32
    assert float(scale.reshape(-1)[3]) == pytest.approx(1.0 / 127.0)
    # the regression: fp16-stored scale stays nonzero and finite dequant
    s16 = scale.astype(np.float16)
    assert float(s16.reshape(-1)[3]) > 0.0
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    deq = q.astype(np.float32) * s16.astype(np.float32)
    assert np.all(np.isfinite(deq))
    # scalar (per-tensor) rule shares the guard
    assert float(_absmax_scale(np.zeros((4, 4), np.float32))) \
        == pytest.approx(1.0 / 127.0)


# ---------------------------------------------------------------------------
# int8 matmul kernel parity vs the jnp oracle
# ---------------------------------------------------------------------------


def test_int8_matmul_pallas_matches_jnp_oracle():
    """Interpret-mode Pallas kernel vs the jnp oracle at a lane-aligned
    shape: same math (per-row activation quant, int32 accumulate, f32
    dequant epilogue), so parity is tight."""
    rng = np.random.RandomState(2)
    M, K, N = 16, 128, 256
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    wq, ws = pallas_ops.quantize_int8(w)
    assert pallas_ops.int8_matmul_available((M, K), (K, N))
    out = pallas_ops._int8_matmul_call(x, wq, ws, bm=8, bn=128)
    ref = pallas_ops._int8_matmul_jnp(x, wq, ws)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    # quantized matmul approximates the float matmul within int8 budget
    exact = x @ w
    rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    assert rel < 0.05


def test_int8_matmul_public_entry_leading_dims_and_fallback():
    rng = np.random.RandomState(3)
    w = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
    wq, ws = pallas_ops.quantize_int8(w)
    # lane-unaligned (K=96, N=80): public entry must take the jnp
    # fallback and still match the oracle, preserving leading dims
    assert not pallas_ops.int8_matmul_available((8, 96), (96, 80))
    x = jnp.asarray(rng.standard_normal((2, 5, 96)), jnp.float32)
    out = pallas_ops.int8_matmul(x, wq, ws)
    ref = pallas_ops._int8_matmul_jnp(x.reshape(-1, 96), wq,
                                      ws.reshape(1, -1)).reshape(2, 5, 80)
    assert out.shape == (2, 5, 80)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-6


def test_int8_matmul_candidates_are_legal():
    M, K, N = 256, 128, 512
    cands = pallas_ops.int8_matmul_candidates(M, K, N)
    assert cands, "no legal (bm, bn) candidates at a TPU-legal shape"
    for bm, bn in cands:
        assert M % bm == 0 and N % bn == 0
        specs = pallas_ops.int8_matmul_block_specs(M, K, N, bm, bn)
        for blk, arr in specs["in"] + specs["out"]:
            assert pallas_ops.mosaic_block_legal(blk, arr, dtype_bits=8)


# ---------------------------------------------------------------------------
# quantized-KV ragged paged attention parity
# ---------------------------------------------------------------------------


def test_rpa_quantized_pools_match_jnp_reference():
    rng = np.random.RandomState(4)
    R, nkv, rep, Tc, d, P, page, Bmax = 4, 2, 2, 8, 32, 32, 16, 4
    Tr = Tc * rep
    q = jnp.asarray(rng.standard_normal((R, nkv, Tr, d)), jnp.float32)
    kp = jnp.asarray(rng.randint(-127, 128, (nkv, P, page, d)), jnp.int8)
    vp = jnp.asarray(rng.randint(-127, 128, (nkv, P, page, d)), jnp.int8)
    ksc = jnp.asarray(rng.uniform(0.005, 0.02, (nkv, P)), jnp.float32)
    vsc = jnp.asarray(rng.uniform(0.005, 0.02, (nkv, P)), jnp.float32)
    tbl = jnp.asarray((1 + rng.permutation(P - 1)[:R * Bmax])
                      .reshape(R, Bmax), jnp.int32)
    lens = jnp.asarray([40, 17, 64, 0], jnp.int32)
    qlens = jnp.asarray([8, 1, 3, 0], jnp.int32)
    out = pallas_ops._rpa_call(q, kp, vp, tbl, lens, qlens, rep=rep, k_scales=ksc, v_scales=vsc)
    ref = pallas_ops._ragged_attention_jnp(q, kp, vp, tbl, lens, qlens,
                                           rep, ksc, vsc)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_quantized_kernel_cases_registered():
    names = [c[0] for c in pallas_ops.kernel_verify_cases()]
    assert "int8_matmul" in names
    assert "ragged_paged_attention_quant_kv" in names
    from paddle_tpu.analysis import kernel_checks
    findings = kernel_checks.verify_registered(
        names=["int8_matmul", "ragged_paged_attention_quant_kv"])
    assert [f for f in findings if f.severity == "error"] == []


# ---------------------------------------------------------------------------
# quantized weight path through the model
# ---------------------------------------------------------------------------


def test_quantize_params_forward_parity(model):
    cfg, params = model
    qp = llama.quantize_params(cfg, params)
    assert isinstance(qp["layers"]["wq"], dict)
    assert qp["layers"]["wq"]["q"].dtype == jnp.int8
    assert isinstance(qp["lm_head"], dict)
    # embeddings / norms stay float
    assert not isinstance(qp["embed"], dict)
    # idempotent: already-quantized leaves pass through
    qp2 = llama.quantize_params(cfg, qp)
    assert qp2["layers"]["wq"]["q"] is qp["layers"]["wq"]["q"]

    ids = jnp.asarray([[3, 17, 99, 4, 42, 7, 8, 1]], jnp.int32)
    ref, _ = llama.forward_pure(cfg, params, ids)
    out, _ = llama.forward_pure(cfg, qp, ids)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel < 0.05
    # greedy next-token choice survives weight quantization here
    assert int(jnp.argmax(out[0, -1])) == int(jnp.argmax(ref[0, -1]))


def test_quantized_is_asked_for_never_inferred(model):
    """int8 weights are a different model: the engine serves them only
    when the config says "on" — never because of the platform (there is
    no "auto"), so CPU tests and chip serving share their numerics."""
    cfg, params = model
    assert cfg.quantized == "off"
    eng = serving.LLMEngine(cfg, params, max_running=2)
    assert not isinstance(eng.params["layers"]["wq"], dict)
    for bad in ("auto", "sometimes", None):
        with pytest.raises(AssertionError):
            _tiny_cfg(quantized=bad)


def test_engine_quantized_weights_streams(model):
    """cfg.quantized='on': the engine PTQs its weights at build and the
    streams stay parity-within-tolerance against dense greedy."""
    cfg, params = model
    qcfg = _tiny_cfg(quantized="on")
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8], [1, 1, 2, 3, 5]]
    expect = [_dense_greedy(cfg, params, p, 6) for p in prompts]
    eng, outs = _run_engine(qcfg, params, prompts, 6)
    assert isinstance(eng.params["layers"]["wq"], dict)
    for got, exp in zip(outs, expect):
        assert _agreement(got, exp) >= 0.5
    assert eng.kv.allocator.num_allocated == 0


# ---------------------------------------------------------------------------
# engine parity: dense bf16 pools vs quantized int8 pools
# ---------------------------------------------------------------------------


def test_engine_int8_kv_streams_parity_and_prefix_hit(model,
                                                      shared_workload):
    """Quantized-KV streams track dense greedy within tolerance, with
    the prefix cache actually hitting (reuse semantics preserved across
    the scale pools)."""
    cfg, params = model
    prompts, n_new, expect = shared_workload
    eng, outs = _run_engine(cfg, params, prompts, n_new,
                            kv_dtype="int8", prefix_cache=True)
    assert eng._quant_kv and eng._scale_bytes > 0
    agree = [_agreement(got, exp) for got, exp in zip(outs, expect)]
    # tolerance contract: most streams exactly match dense greedy; a
    # minority may cascade after one quantization-induced argmax flip
    assert sum(a == 1.0 for a in agree) >= len(agree) // 2, agree
    assert sum(agree) / len(agree) >= 0.6, agree
    st = eng.kv.prefix.stats
    assert st.hit_tokens > 0 and st.inserted_pages > 0
    assert eng.kv.audit()["ok"]


def test_engine_int8_kv_preemption_replay_matches_unpreempted(model):
    """Quantized writes are a pure function of the request's own tokens
    (stale bytes on recycled pages are zero-masked out of the page
    absmax — the regression this test pins), so a preempted-and-
    replayed quantized engine reproduces the unpreempted quantized
    streams, deterministically."""
    cfg, params = model
    rng = np.random.RandomState(3)
    prompts = [list(map(int, rng.randint(0, 128, 6))) for _ in range(5)]
    n_new = 20
    serving.reset_stats()
    _, base = _run_engine(cfg, params, prompts, n_new, kv_dtype="int8",
                          max_running=4, chunk=4, page_size=8,
                          num_pages=200, max_model_len=32)
    _, tight = _run_engine(cfg, params, prompts, n_new, kv_dtype="int8",
                           max_running=4, chunk=4, page_size=8,
                           num_pages=10, max_model_len=32)
    _, tight2 = _run_engine(cfg, params, prompts, n_new, kv_dtype="int8",
                            max_running=4, chunk=4, page_size=8,
                            num_pages=10, max_model_len=32)
    assert serving.serving_stats()["requests_preempted"] > 0
    assert tight == tight2, "quantized replay is nondeterministic"
    assert tight == base, "preemption replay diverged from unpreempted"
    # and the quantized streams track dense greedy within tolerance
    agree = [_agreement(got, _dense_greedy(cfg, params, p, n_new))
             for p, got in zip(prompts, base)]
    assert sum(agree) / len(agree) >= 0.6, agree


def test_engine_int8_kv_spec_decode_verify_path(model, shared_workload):
    """Spec decode over quantized pools: verify chunks write through the
    quantize-on-write path and acceptance still drives the stream to
    parity-within-tolerance with dense greedy."""
    cfg, params = model
    prompts, n_new, expect = shared_workload
    serving.reset_stats()
    spec = serving.SpecDecodeConfig(cfg=cfg, params=params, k=3)
    _, outs = _run_engine(cfg, params, prompts, n_new,
                          kv_dtype="int8", spec=spec)
    stats = serving.serving_stats()
    assert stats["spec_proposed"] > 0
    assert 0 < stats["spec_accepted"] <= stats["spec_proposed"]
    agree = [_agreement(got, exp) for got, exp in zip(outs, expect)]
    assert sum(a == 1.0 for a in agree) >= len(agree) // 2, agree
    assert sum(agree) / len(agree) >= 0.6, agree


# ---------------------------------------------------------------------------
# capacity planning: int8 pages must buy >= 1.9x concurrency
# ---------------------------------------------------------------------------


def test_plan_capacity_int8_ratio():
    cfg = llama.preset("llama7b")
    kw = dict(hbm_bytes=96 << 30, page_size=128, max_model_len=2048)
    base = serving.plan_capacity(cfg, **kw)
    quant = serving.plan_capacity(cfg, kv_dtype="int8", **kw)
    assert quant["kv_dtype"] == "int8"
    assert quant["scale_bytes_per_page"] > 0
    assert base.get("scale_bytes_per_page", 0) == 0
    ratio = quant["max_concurrent_requests"] / base["max_concurrent_requests"]
    assert ratio >= 1.9, f"int8 capacity ratio {ratio:.3f} < 1.9"
    # scale overhead is bounded: int8 never reaches the naive 2.0x but
    # must stay close (page_bytes ratio, independent of request rounding)
    assert quant["page_bytes"] * 1.9 <= base["page_bytes"] * 2.0
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        serving.plan_capacity(cfg, kv_dtype="int4", **kw)
    assert serving.KV_DTYPE_BYTES["int8"] == 1


# ---------------------------------------------------------------------------
# numerics: quant_err_* gauges under the Quantization block
# ---------------------------------------------------------------------------


def test_quant_err_gauges_in_numerics_summary(model):
    cfg, params = model
    numerics.reset()
    paddle.set_flags({"FLAGS_tpu_check_nan_inf": True})
    try:
        llama.quantize_params(cfg, params)
        stats = numerics.last_stats()
        assert any(k.startswith("quant_err_rms_") for k in stats)
        assert any(k.startswith("quant_err_absmax_") for k in stats)
        assert all(np.isfinite(v) for k, v in stats.items()
                   if k.startswith("quant_err_"))
        lines = numerics.summary_lines()
        assert any(ln.strip() == "Quantization" for ln in lines)
        assert any("quant_err_" in ln for ln in lines)
    finally:
        paddle.set_flags({"FLAGS_tpu_check_nan_inf": False})
        numerics.reset()
