"""Serving engine: paged KV cache, continuous batching, and the
ragged-paged-attention kernel.

The acceptance bar (ISSUE 10): allocator invariants hold under
alloc/free/eviction; the ragged kernel matches the jnp reference for
prefill, mixed prefill+decode and GQA; the kernel lowers for TPU
hardware-free via ``jax.export``; the scheduler admits/completes in
order; and ``LLMEngine`` streams are token-identical to per-request
``forward_with_cache`` greedy decoding — including under forced
preemption.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import llama
from paddle_tpu.models.decoding import init_kv_cache
from paddle_tpu.ops import pallas_ops
from paddle_tpu.serving.kv_cache import BlockAllocator, PagedKVCache
from paddle_tpu.serving.scheduler import Request, Scheduler


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


# ---------------------------------------------------------------------------
# Paged KV cache: allocator invariants
# ---------------------------------------------------------------------------


def test_allocator_reserves_null_page_and_round_trips():
    a = BlockAllocator(num_pages=8, page_size=16)
    assert a.capacity == 7  # page 0 is the reserved null page
    got = a.alloc(3, owner="r1")
    assert got is not None and 0 not in got
    assert a.num_allocated == 3 and a.num_free == 4
    a.free(got)
    assert a.num_allocated == 0 and a.num_free == 7


def test_allocator_refuses_overcommit_and_double_free():
    a = BlockAllocator(num_pages=4, page_size=16)
    assert a.alloc(5, owner="big") is None  # all-or-nothing
    assert a.num_allocated == 0
    pages = a.alloc(3, owner="r")
    with pytest.raises(ValueError):
        a.free([0])  # the null page is never allocatable
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)  # double free


def test_paged_cache_grow_commit_release():
    kv = PagedKVCache(num_pages=9, page_size=4, max_blocks=4)
    assert kv.grow("a", 6)  # two pages
    kv.commit("a", 6)
    assert kv.num_tokens("a") == 6
    assert kv.pages_needed("a", 7) == 0  # page 2 has room for token 7
    assert kv.pages_needed("a", 9) == 1
    row = kv.block_row("a")
    assert len(row) == 4 and row[2:] == [0, 0]  # null-padded
    # growth beyond max_blocks is refused without partial allocation
    free_before = kv.allocator.num_free
    assert not kv.grow("a", 4 * 4 + 1)
    assert kv.allocator.num_free == free_before
    freed = kv.release("a")
    assert len(freed) == 2 and kv.allocator.num_allocated == 0


def test_plan_capacity_shape():
    cfg = llama.preset("llama7b")
    plan = serving.plan_capacity(cfg, hbm_bytes=96 << 30, page_size=128,
                                 max_model_len=2048)
    assert plan["num_pages"] > 0
    assert plan["max_concurrent_requests"] >= 1
    assert plan["weights_bytes"] > 10 << 30  # ~13.5 GiB bf16
    assert plan["usable_kv_bytes"] < 96 << 30


# ---------------------------------------------------------------------------
# Ragged-paged-attention kernel parity vs the jnp reference
# ---------------------------------------------------------------------------


def _rpa_case(R, nkv, rep, Tc, d, P, page, Bmax, seq_lens, q_lens,
              dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    Tr = Tc * rep
    q = jnp.asarray(rng.standard_normal((R, nkv, Tr, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((nkv, P, page, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((nkv, P, page, d)), dtype)
    pages = 1 + rng.permutation(P - 1)[:R * Bmax]  # distinct, page 0 free
    tbl = jnp.asarray(pages.reshape(R, Bmax), jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    qlens = jnp.asarray(q_lens, jnp.int32)
    ref = pallas_ops._ragged_attention_jnp(q, kp, vp, tbl, lens, qlens, rep)
    out = pallas_ops._rpa_call(q, kp, vp, tbl, lens, qlens, rep=rep)
    return q, out, ref, qlens


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def test_rpa_mixed_prefill_decode_matches_reference():
    # slot 0 full prefill, slot 1 decode, slot 2 chunked tail, slot 3 idle
    _, out, ref, qlens = _rpa_case(
        R=4, nkv=2, rep=2, Tc=8, d=32, P=32, page=16, Bmax=4,
        seq_lens=[40, 17, 64, 0], q_lens=[8, 1, 3, 0])
    assert _maxerr(out, ref) < 2e-5
    # rows past q_len are exactly zero (the engine never reads them,
    # but garbage there would leak through a debugging sum)
    tok = np.arange(out.shape[2]) // 2
    pad = jnp.asarray(tok[None, :] >= np.asarray(qlens)[:, None])
    assert float(jnp.max(jnp.abs(
        jnp.where(pad[:, None, :, None], out, 0.0)))) == 0.0


def test_rpa_decode_specialization_matches_reference():
    _, out, ref, _ = _rpa_case(
        R=8, nkv=2, rep=2, Tc=1, d=32, P=64, page=16, Bmax=4,
        seq_lens=[1, 17, 33, 64, 5, 9, 0, 50],
        q_lens=[1, 1, 1, 1, 1, 1, 0, 1])
    assert _maxerr(out, ref) < 2e-5


def test_rpa_gqa_bf16_lane_aligned_page():
    # the TPU-legal geometry: page == 128 lanes, GQA rep=4, bf16
    _, out, ref, _ = _rpa_case(
        R=4, nkv=2, rep=4, Tc=4, d=128, P=16, page=128, Bmax=2,
        seq_lens=[256, 100, 129, 1], q_lens=[4, 2, 4, 1],
        dtype=jnp.bfloat16)
    assert _maxerr(out, ref) < 2e-2  # bf16 has ~8 mantissa bits


def _forced_group(monkeypatch, G, nkv, Tr, d, page, itemsize, Bmax):
    """Shrink the kernel's VMEM budget until the shapes give groups of G
    pages: the group size has no argument, it follows from what fits."""
    monkeypatch.setattr(pallas_ops, "_VMEM_BUDGET", 0)   # restored after
    for kib in range(16, 64 << 10, 16):
        pallas_ops._VMEM_BUDGET = kib << 10
        if pallas_ops._rpa_group_pages(nkv, Tr, d, page, itemsize,
                                       Bmax) == G:
            return
    raise AssertionError(f"no budget gives groups of {G} pages")


# the rows of one batch, by what the page walk has to get right; G is the
# group size the case forces, page 128, Bmax 4
_WALK_ROWS = (
    # name,                         seq_len,            q_len
    ("idle: no live page",          lambda G, Tc: 300,  lambda Tc: 0),
    ("exactly one page",            lambda G, Tc: 128,  lambda Tc: Tc),
    ("a whole number of groups",    lambda G, Tc: min(2 * G, 4) * 128,
                                                        lambda Tc: 1),
    ("one token past a group",      lambda G, Tc: min(G * 128 + 1, 512),
                                                        lambda Tc: 1),
    ("the whole table",             lambda G, Tc: 512,  lambda Tc: Tc),
    ("chunk across a page edge",    lambda G, Tc: 256 + Tc // 2,
                                                        lambda Tc: Tc),
    ("shares pages with the whole", lambda G, Tc: 200,
                                    lambda Tc: max(Tc - 1, 1)),
    ("first token",                 lambda G, Tc: 1,    lambda Tc: 1),
)


def _walk_case(rep, nkv, Tc, kind, G, layer=1, L=3, d=128, page=128,
               Bmax=4, seed=0):
    rng = np.random.RandomState(seed)
    R, P = len(_WALK_ROWS), 40
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    q = jnp.asarray(rng.standard_normal((R, nkv, Tc * rep, d)), dtype)
    kp = rng.standard_normal((L, nkv, P, page, d)).astype(np.float32)
    vp = rng.standard_normal((L, nkv, P, page, d)).astype(np.float32)
    scales = {}
    if kind == "int8":
        def quantize(p):
            sc = np.maximum(np.abs(p).max(axis=(3, 4)), 1e-8) / 127.0
            return (np.round(p / sc[..., None, None]).astype(np.int8),
                    sc.astype(np.float32))
        (kp, ksc), (vp, vsc) = quantize(kp), quantize(vp)
        scales = dict(k_scales=jnp.asarray(ksc), v_scales=jnp.asarray(vsc))
        kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    else:
        kp, vp = jnp.asarray(kp, dtype), jnp.asarray(vp, dtype)
    lens = np.array([f(G, Tc) for _, f, _ in _WALK_ROWS], np.int32)
    qlens = np.array([f(Tc) for _, _, f in _WALK_ROWS], np.int32)
    # a shuffled table; slots past a row's length hold the null page, as
    # the engine's do; row 6 names row 4's first two pages (a shared prefix)
    tbl = (1 + rng.permutation(P - 1)[:R * Bmax]).reshape(R, Bmax)
    tbl[np.arange(Bmax)[None, :] * page >= lens[:, None]] = 0
    tbl[6, :2] = tbl[4, :2]
    return (q, kp, vp, jnp.asarray(tbl, jnp.int32), jnp.asarray(lens),
            jnp.asarray(qlens), scales)


@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("Tc", [1, 16])
@pytest.mark.parametrize("heads", [(1, 2), (2, 2), (20, 1)],
                         ids=["mha", "gqa2", "mqa20"])
def test_rpa_walks_a_rows_live_pages_in_groups(heads, Tc, kind, G,
                                               monkeypatch):
    """The kernel against the reference on one batch that holds every
    kind of row the walk meets, on layer 1 of a stack of 3, with the
    pages fetched 1, 2 and 4 (the whole table) at a time."""
    rep, nkv = heads
    q, kp, vp, tbl, lens, qlens, scales = _walk_case(rep, nkv, Tc, kind, G)
    _forced_group(monkeypatch, G, nkv, Tc * rep, 128, 128,
                  kp.dtype.itemsize, tbl.shape[1])
    out = pallas_ops._rpa_call(q, kp, vp, tbl, lens, qlens, rep=rep,
                               layer=1, **scales)
    ref = pallas_ops._ragged_attention_jnp(
        q, kp, vp, tbl, lens, qlens, rep, *scales.values(), layer=1)
    assert not bool(jnp.any(jnp.isnan(out.astype(jnp.float32))))
    assert _maxerr(out, ref) < (2e-2 if kind == "bf16" else 2e-5)
    # padding rows and the idle request are exact zeros
    pad = (np.arange(Tc * rep) // rep)[None, :] >= np.asarray(qlens)[:, None]
    assert float(jnp.max(jnp.abs(jnp.where(
        jnp.asarray(pad)[:, None, :, None], out, 0)))) == 0.0
    assert float(jnp.max(jnp.abs(out[0].astype(jnp.float32)))) == 0.0


def test_rpa_group_size_follows_the_shapes_and_the_budget():
    group = pallas_ops._rpa_group_pages
    # the two cells' buckets: 8 kv heads of bf16 pages, Tr 32 and 2,
    # Bmax 20; one kv head, Tr 320 and 20, Bmax 24
    assert group(8, 32, 128, 128, 2, 20) == group(8, 2, 128, 128, 2, 20) == 8
    assert group(1, 320, 128, 128, 2, 24) == 8
    assert group(1, 20, 128, 128, 2, 24) == 16
    for nkv, Tr, itemsize, Bmax in ((8, 32, 2, 20), (16, 16, 2, 8),
                                    (1, 320, 2, 24), (2, 8, 4, 4),
                                    (32, 512, 4, 64), (8, 32, 1, 3)):
        G = group(nkv, Tr, 128, 128, itemsize, Bmax)
        assert 1 <= G <= Bmax and G & (G - 1) == 0
        # both slots of K and V for every head, and a head's scores
        used = 4 * nkv * G * 128 * 128 * itemsize + 8 * Tr * G * 128 * 4
        assert used <= pallas_ops._VMEM_BUDGET or G == 1
        # wider pages, more heads or a longer chunk never widen the group
        assert group(2 * nkv, Tr, 128, 128, itemsize, Bmax) <= G
        assert group(nkv, 2 * Tr, 128, 128, itemsize, Bmax) <= G
        assert group(nkv, Tr, 128, 256, itemsize, Bmax) <= G


def test_rpa_public_entry_falls_back_off_tpu():
    # without interpret mode on CPU the public wrapper must take the
    # jnp reference path and still produce the right answer
    pallas_ops._INTERPRET = False
    assert not pallas_ops.ragged_attention_available(
        (2, 2, 4, 16), (2, 8, 4, 16))
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.standard_normal((2, 2, 4, 16)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([8, 5], jnp.int32)
    qlens = jnp.asarray([4, 2], jnp.int32)
    out = pallas_ops.ragged_paged_attention(q, kp, vp, tbl, lens, qlens,
                                            rep=1)
    ref = pallas_ops._ragged_attention_jnp(q, kp, vp, tbl, lens, qlens, 1)
    assert _maxerr(out, ref) < 1e-5


def test_rpa_tpu_lowering_hardware_free():
    """jax.export compiles the real Mosaic kernel for TPU with no TPU
    attached — the ISSUE acceptance's lowering check."""
    import jax.export
    Rr, nkv, rep, page, P, Bmax, D = 4, 2, 2, 128, 16, 4, 128
    Tr = 8 * rep
    tbl = jnp.asarray((1 + np.arange(Rr * Bmax) % (P - 1))
                      .reshape(Rr, Bmax), jnp.int32)
    lens = jnp.full((Rr,), Bmax * page, jnp.int32)
    SDS = jax.ShapeDtypeStruct
    kv_aval = SDS((nkv, P, page, D), jnp.float32)
    pallas_ops._INTERPRET = False

    def mixed(q, kp, vp):
        return pallas_ops._rpa_call(
            q, kp, vp, tbl, lens, jnp.full((Rr,), 8, jnp.int32),
            rep=rep)

    def decode(q, kp, vp):
        return pallas_ops._rpa_call(
            q, kp, vp, tbl, lens, jnp.ones((Rr,), jnp.int32),
            rep=rep)

    for fn, rows in ((mixed, Tr), (decode, rep)):
        text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            SDS((Rr, nkv, rows, D), jnp.float32), kv_aval,
            kv_aval).mlir_module()
        assert "tpu_custom_call" in text and "_rpa_kernel" in text
        # the pool is the kernel's operand whole, as the stack of one layer
        assert f"tensor<1x{nkv}x{P}x{page}x{D}xf32>" in text


# ---------------------------------------------------------------------------
# Scheduler: admission / completion ordering, chunked prefill, preemption
# ---------------------------------------------------------------------------


def _sched(num_pages=64, page=4, max_blocks=16, **kw):
    kv = PagedKVCache(num_pages=num_pages, page_size=page,
                      max_blocks=max_blocks)
    return Scheduler(kv, **kw)


def test_scheduler_admits_fifo_and_chunks_prefill():
    s = _sched(max_running=2, chunk=4)
    reqs = [Request(prompt=[1] * 10, max_new_tokens=2) for _ in range(3)]
    for r in reqs:
        s.add(r)
    plan = s.schedule()
    # only two slots: requests 0 and 1 admitted, in arrival order
    assert [q.request for q in plan.seqs] == reqs[:2]
    assert all(q.q_len == 4 for q in plan.seqs)  # chunked prefill
    assert plan.bucket == s.chunk
    assert not any(q.produces for q in plan.seqs)  # prompt not consumed yet


def test_scheduler_completion_frees_slot_for_waiting_request():
    s = _sched(max_running=1, chunk=16)
    r1 = Request(prompt=[1, 2, 3], max_new_tokens=1)
    r2 = Request(prompt=[4, 5], max_new_tokens=1)
    s.add(r1)
    s.add(r2)
    plan = s.schedule()
    assert [q.request for q in plan.seqs] == [r1]
    assert plan.seqs[0].produces  # whole prompt fits in one chunk
    s.dispatch(plan)
    s.complete(plan, {plan.seqs[0].slot: 7}, now_s=1.0)
    assert r1.done and r1.output == [7] and r1.finish_s == 1.0
    plan2 = s.schedule()  # the freed slot goes to the waiting request
    assert [q.request for q in plan2.seqs] == [r2]
    assert s.kv.allocator.num_allocated > 0
    s.dispatch(plan2)
    s.complete(plan2, {plan2.seqs[0].slot: 9}, now_s=2.0)
    assert s.kv.allocator.num_allocated == 0  # everything released


def test_scheduler_eos_finishes_early():
    s = _sched(max_running=1, chunk=16)
    req = Request(prompt=[1, 2], max_new_tokens=5, eos_token_id=3)
    s.add(req)
    plan = s.schedule()
    s.dispatch(plan)
    s.complete(plan, {plan.seqs[0].slot: 3}, now_s=0.0)
    assert req.done and req.output == [3]


def test_scheduler_decode_bucket_is_one():
    s = _sched(max_running=2, chunk=8)
    s.add(Request(prompt=[1, 2], max_new_tokens=4))
    plan = s.schedule()
    s.dispatch(plan)
    s.complete(plan, {plan.seqs[0].slot: 5}, now_s=0.0)
    plan2 = s.schedule()
    assert plan2.bucket == 1 and plan2.seqs[0].q_len == 1
    assert plan2.seqs[0].produces


def test_scheduler_watermark_defers_admission():
    # pool: 5 usable pages of 4 tokens; each request needs 2 pages for
    # its 8-token prompt — the third must wait for a completion
    s = _sched(num_pages=6, page=4, max_blocks=4, max_running=4, chunk=8)
    reqs = [Request(prompt=[1] * 8, max_new_tokens=2) for _ in range(3)]
    for r in reqs:
        s.add(r)
    plan = s.schedule()
    admitted = [q.request for q in plan.seqs]
    assert reqs[2] not in admitted and admitted == reqs[:2]


def test_scheduler_preemption_requeues_and_replays():
    # one request's growth can evict the youngest running request; the
    # victim re-enters at the queue front with its KV refed from scratch
    s = _sched(num_pages=5, page=4, max_blocks=4, max_running=2, chunk=8)
    r1 = Request(prompt=[1] * 8, max_new_tokens=8)
    s.add(r1)
    plan = s.schedule()
    assert [q.request for q in plan.seqs] == [r1]
    s.dispatch(plan)
    s.complete(plan, {plan.seqs[0].slot: 2}, now_s=0.0)
    r2 = Request(prompt=[2] * 4, max_new_tokens=8)
    s.add(r2)
    preempted_total = 0
    for step in range(200):
        if not s.has_work():
            break
        plan = s.schedule()
        preempted_total += len(plan.preempted)
        assert plan.seqs, "live requests but an empty step plan"
        s.dispatch(plan)
        s.complete(plan, {q.slot: 3 for q in plan.seqs}, now_s=float(step))
    assert r1.done and r2.done
    assert preempted_total > 0  # the tiny pool forced at least one
    assert len(r1.output) == 8 and len(r2.output) == 8
    assert s.kv.allocator.num_allocated == 0


def test_scheduler_rejects_oversized_request():
    s = _sched(max_running=1, chunk=8, max_model_len=16)
    with pytest.raises(ValueError):
        s.add(Request(prompt=[1] * 12, max_new_tokens=8))
    with pytest.raises(ValueError):
        s.add(Request(prompt=[], max_new_tokens=4))


# ---------------------------------------------------------------------------
# Engine: end-to-end greedy parity with forward_with_cache
# ---------------------------------------------------------------------------


def _tiny_cfg():
    return llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, dtype=jnp.float32, use_remat=False)


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


def test_engine_streams_match_dense_greedy():
    """≥8 concurrent requests with continuous admission produce streams
    identical to per-request forward_with_cache greedy (ISSUE
    acceptance)."""
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, 128, rng.randint(3, 14)))
               for _ in range(10)]
    new_toks = [int(rng.randint(3, 9)) for _ in range(10)]
    expect = [_dense_greedy(cfg, params, p, n)
              for p, n in zip(prompts, new_toks)]

    eng = serving.LLMEngine(cfg, params, max_running=8, chunk=4,
                            page_size=8, max_model_len=32)
    streams = {}

    def on_tok(rid, tok, fin):
        streams.setdefault(rid, []).append(tok)

    rids = [eng.add_request(prompts[i], new_toks[i], on_token=on_tok)
            for i in range(4)]
    eng.step()
    eng.step()
    # the rest arrive mid-flight: continuous admission, no drain
    rids += [eng.add_request(prompts[i], new_toks[i], on_token=on_tok)
             for i in range(4, 10)]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 500, "engine did not converge"
    for i, rid in enumerate(rids):
        assert eng.output_of(rid) == expect[i], f"request {i} diverged"
        assert streams[rid] == expect[i], f"stream {i} diverged"
    assert eng.kv.allocator.num_allocated == 0
    # fixed compiled shapes: exactly one executable per bucket signature
    assert sorted(eng._step_fns) == [1, eng.scheduler.chunk]


def test_engine_parity_survives_preemption():
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    # each request grows to 26 tokens = 4 pages of 8; four slots want
    # 16 pages but the pool only has 9 usable — growth must evict
    prompts = [list(rng.randint(0, 128, 6)) for _ in range(5)]
    n_new = 20
    expect = [_dense_greedy(cfg, params, p, n_new) for p in prompts]
    serving.reset_stats()
    eng = serving.LLMEngine(cfg, params, max_running=4, chunk=4,
                            page_size=8, max_model_len=32, num_pages=10)
    rids = [eng.add_request(p, n_new) for p in prompts]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 2000
    for i, rid in enumerate(rids):
        assert eng.output_of(rid) == expect[i], f"request {i} diverged"
    assert serving.serving_stats()["requests_preempted"] > 0
    assert eng.kv.allocator.num_allocated == 0


def test_engine_serving_stats_and_profiler_summary():
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    serving.reset_stats()
    eng = serving.LLMEngine(cfg, params, max_running=2, chunk=4,
                            page_size=8, max_model_len=32)
    eng.add_request([1, 2, 3, 4, 5], 3)
    while eng.has_work():
        eng.step()
    st = serving.serving_stats()
    assert st["requests_finished"] == 1
    # 5-token prompt over chunk=4: one 4-token prefill chunk, then the
    # remaining prompt token and the generated ones flow as decode steps
    assert st["prefill_tokens"] == 4 and st["decode_tokens"] == 3
    lines = serving.summary_lines()
    assert any("Serving" in ln for ln in lines)
    from paddle_tpu import profiler as prof
    p = prof.Profiler(timer_only=True)
    p.start()
    p.stop()
    assert "Serving" in p.summary_table()
    # the pool reservation is visible to the memory profiler
    from paddle_tpu.profiler import xmem
    assert any(r["name"] == "serving.kv_pages"
               for r in xmem.reservations())
    eng.shutdown()
    assert not any(r["name"] == "serving.kv_pages"
                   for r in xmem.reservations())


def test_engine_drains_a_workload_in_two_buckets():
    """Half the requests up front, the rest arriving while the batch is
    in flight: every token comes out, through exactly the prefill and
    the decode bucket, and the SLO report has the TTFT tail."""
    cfg = llama.preset("llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_req, max_prompt, n_new, chunk = 6, 8, 4, 4
    eng = serving.LLMEngine(cfg, params, max_running=4, chunk=chunk,
                            max_model_len=max_prompt + n_new + chunk)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size,
                                rng.randint(2, max_prompt + 1)))
               for _ in range(n_req)]
    rids = [eng.add_request(p, n_new) for p in prompts[:n_req // 2]]
    pending = prompts[n_req // 2:]
    steps = 0
    while eng.has_work() or pending:
        if pending and steps % 2 == 1:
            rids.append(eng.add_request(pending.pop(0), n_new))
        eng.step()
        steps += 1
        assert steps < 1000, "serve loop did not converge"
    assert sum(len(eng.output_of(r)) for r in rids) == n_req * n_new
    assert len(eng._step_fns) == 2
    p50 = float(np.percentile(eng._ttft_s, 50))
    assert eng.slo_report()["ttft_p95_s"] >= p50 >= 0
    eng.shutdown()
