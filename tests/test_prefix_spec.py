"""Shared-prefix KV reuse (radix cache) + speculative decoding (ISSUE 12).

Covers the tentpole invariants end to end:
  * BlockAllocator refcounts: no page freed while shared, decref-only
    recycling, strict single-owner ``free``;
  * the radix trie under adversarial prefixes — page-boundary straddles,
    single-token divergence, duplicate donations;
  * copy-on-write forks of partially matched pages and their drained
    device copies;
  * LRU eviction that never touches a borrowed page;
  * the capacity audit ``free + unique + shared + cached_idle ==
    capacity`` under forced preemption;
  * bit-identical greedy parity with prefix cache and spec decode in
    every on/off combination, including across crash-recovery replay;
  * the refcount-aware chaos ``exhaust``/``release_exhausted`` path;
  * the shared-prefix workload (>50% prefill reduction at 8 requests
    over 2 system prompts) and pod_report's --prefix-hit-rate.
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
from paddle_tpu.serving.prefix_cache import PrefixCache
from paddle_tpu.serving.spec_decode import greedy_accept
from paddle_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


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


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


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


def _spec(cfg, params, k=3):
    # self-draft: target model as its own draft — acceptance is total,
    # which makes the spec path exercise every verify-chunk shape
    return serving.SpecDecodeConfig(cfg=cfg, params=params, k=k)


# ---------------------------------------------------------------------------
# BlockAllocator refcounts
# ---------------------------------------------------------------------------


def test_allocator_refcount_lifecycle():
    a = BlockAllocator(8, 4)
    pages = a.alloc(3, owner="r1")
    assert all(a.refcount(p) == 1 for p in pages)
    a.incref(pages[:2])
    assert a.refcount(pages[0]) == 2
    # no page is freed while shared: strict free refuses refcount != 1
    with pytest.raises(ValueError, match="refcount 2"):
        a.free(pages[:1])
    # first decref drops to 1, frees nothing
    assert a.decref(pages[:2]) == []
    assert a.num_free == 8 - 1 - 3
    # last reference drops -> exactly those pages recycle
    assert sorted(a.decref(pages)) == sorted(pages)
    assert a.num_free == 8 - 1 and a.num_allocated == 0


def test_allocator_refcount_guards():
    a = BlockAllocator(4, 4)
    (p,) = a.alloc(1)
    with pytest.raises(ValueError):
        a.incref([0])          # null page
    with pytest.raises(ValueError):
        a.incref([3])          # never allocated
    a.decref([p])
    with pytest.raises(ValueError):
        a.decref([p])          # already recycled
    # single-owner free keeps pre-refcount exactness (double free raises)
    (q,) = a.alloc(1)
    a.free([q])
    with pytest.raises(ValueError):
        a.free([q])


# ---------------------------------------------------------------------------
# radix trie: adversarial prefixes
# ---------------------------------------------------------------------------


def _trie(num_pages=32, page=4):
    a = BlockAllocator(num_pages, page)
    return a, PrefixCache(a, page)


def _donate(a, t, tokens):
    """Alloc pages for full chunks of ``tokens`` and insert them."""
    n = len(tokens) // t.page_size
    pages = a.alloc(n, owner="donor")
    t.insert(tokens[:n * t.page_size], pages)
    return pages


def test_trie_page_boundary_straddle_and_cap():
    a, t = _trie()
    toks = list(range(10, 21))                  # 11 tokens, 2 full pages
    _donate(a, t, toks)
    assert t.num_nodes == 2
    # identical prompt: cap = len-1 = 10 -> 2 full pages + partial 2
    pages, matched, partial = t.match(list(toks))
    assert matched == 8 and partial is None     # 10 < 12: no 3rd chunk
    # a prompt one token past the straddle reuses both pages and forks
    # the second only if it diverges mid-page — here pages are exact
    assert [a.refcount(p) for p in pages] == [2, 2]
    a.decref(pages)


def test_trie_partial_match_single_token_divergence():
    a, t = _trie()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    _donate(a, t, toks)
    # diverges inside the second page after one token: full page 1 +
    # partial (page 2, plen=1)
    q = [1, 2, 3, 4, 5, 99, 99, 99, 99]
    pages, matched, partial = t.match(q)
    assert matched == 4 and partial is not None
    src, plen = partial
    assert plen == 1 and a.refcount(src) == 2
    t.release_partial(src)
    # divergence at token 0: no hit at all
    pages2, matched2, partial2 = t.match([42] * 8)
    assert pages2 == [] and matched2 == 0 and partial2 is None
    a.decref(pages)
    assert t.stats.hit_tokens == 4 + 1


def test_trie_insert_dedup_keeps_one_page():
    a, t = _trie()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    first = _donate(a, t, toks)
    free_before = a.num_free
    dup = _donate(a, t, toks)           # duplicate donation
    assert t.num_nodes == 2
    assert t.stats.deduped_pages == 2
    assert a.num_free == free_before    # dup pages recycled immediately
    assert all(not a.is_held(p) for p in dup)
    # sibling chunks coexist under one parent
    _donate(a, t, [1, 2, 3, 4, 9, 9, 9, 9])
    assert t.num_nodes == 3
    assert {a.refcount(p) for p in first} == {1}


def test_trie_lru_eviction_is_leaf_only_and_skips_borrowed():
    a, t = _trie()
    toks = list(range(1, 13))           # 3-page chain
    chain = _donate(a, t, toks)
    # a borrower holds the whole chain: nothing is evictable
    pages, _, _ = t.match(toks + [99])
    assert pages == chain
    assert t.evict(3) == 0 and t.num_nodes == 3
    a.decref(pages)
    # multi-pass sweep: freeing the leaf exposes its parent
    assert t.evict(3) == 3
    assert t.num_nodes == 0 and a.num_allocated == 0
    assert t.stats.evicted_pages == 3


# ---------------------------------------------------------------------------
# PagedKVCache: COW forks, donation, audit
# ---------------------------------------------------------------------------


def test_kv_cache_cow_fork_and_drain():
    kv = PagedKVCache(num_pages=32, page_size=4, max_blocks=8)
    kv.enable_prefix_cache()
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    assert kv.grow("donor", 8)
    kv.commit("donor", 8)
    assert kv.donate("donor", toks, 8) == 2
    # borrower shares page 1, forks page 2 at plen=2
    q = [1, 2, 3, 4, 5, 6, 77, 77, 77]
    inherited = kv.match_prefix("r2", q)
    assert inherited == 6
    pairs = kv.drain_copies()
    assert len(pairs) == 1
    src, dst = pairs[0]
    assert src != dst
    assert kv.allocator.refcount(src) == 1      # trie only, post-drain
    assert kv.allocator.refcount(dst) == 1      # private to r2
    assert kv.prefix.stats.forks == 1
    audit = kv.audit()
    assert audit["ok"] and audit["shared"] == 1 and audit["cached_idle"] == 1
    kv.release("r2")
    audit = kv.audit()
    assert audit["ok"] and audit["cached_idle"] == 2
    # released-before-copy forks cancel their pending pair
    kv.match_prefix("r3", q)
    assert kv._pending_copies
    kv.release("r3")
    assert not kv._pending_copies and kv.audit()["ok"]


def test_kv_cache_donate_excludes_spec_scratch():
    kv = PagedKVCache(num_pages=32, page_size=4, max_blocks=8)
    kv.enable_prefix_cache()
    toks = list(range(1, 13))
    assert kv.grow("r", 12)             # 3 pages
    kv.commit("r", 12)
    # only 6 tokens are real kv (the rest is speculative scratch):
    # a single full page is donated, the other two recycle
    assert kv.donate("r", toks, 6) == 1
    assert kv.prefix.num_nodes == 1
    assert kv.allocator.num_allocated == 1 and kv.audit()["ok"]


# ---------------------------------------------------------------------------
# engine parity: prefix x spec matrix, preemption, crash recovery
# ---------------------------------------------------------------------------


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


def test_engine_parity_prefix_and_spec_matrix(model, shared_workload):
    """Bit-identical greedy output in every prefix x spec combination
    (ISSUE acceptance)."""
    cfg, params = model
    prompts, n_new, expect = shared_workload
    _eng, base = _run_engine(cfg, params, prompts, n_new)
    assert base == expect

    eng_p, out_p = _run_engine(cfg, params, prompts, n_new,
                               prefix_cache=True)
    assert out_p == expect
    st = eng_p.kv.prefix.stats
    assert st.hit_tokens > 0 and st.inserted_pages > 0
    assert eng_p.kv.audit()["ok"]

    serving.reset_stats()
    _eng_s, out_s = _run_engine(cfg, params, prompts, n_new,
                                spec=_spec(cfg, params))
    assert out_s == expect
    stats = serving.serving_stats()
    assert stats["spec_proposed"] > 0
    assert 0 < stats["spec_accepted"] <= stats["spec_proposed"]

    eng_b, out_b = _run_engine(cfg, params, prompts, n_new,
                               prefix_cache=True, spec=_spec(cfg, params))
    assert out_b == expect
    assert eng_b.kv.audit()["ok"]


def test_engine_prefix_off_leaves_pool_empty(model, shared_workload):
    """With the cache off the allocator drains to zero — the PR-10
    invariant is untouched by the refcount refactor."""
    cfg, params = model
    prompts, n_new, _ = shared_workload
    eng, _ = _run_engine(cfg, params, prompts[:3], n_new)
    assert eng.kv.allocator.num_allocated == 0
    assert eng.kv.prefix is None


def test_engine_audit_holds_under_forced_preemption(model, shared_workload):
    """Tiny pool forces evict-under-pressure and preemption; the
    capacity invariant holds at every step, preempted requests replay
    bit-identical, and replay re-hits the cache."""
    cfg, params = model
    prompts, n_new, expect = shared_workload
    serving.reset_stats()
    eng = serving.LLMEngine(cfg, params, max_running=4, chunk=8,
                            page_size=4, num_pages=20,
                            donate_pools=False, prefix_cache=True)
    rids = [eng.add_request(list(p), n_new) for p in prompts[:5]]
    steps = 0
    while eng.has_work():
        eng.step()
        audit = eng.kv.audit()
        assert audit["ok"], f"audit broke at step {steps}: {audit}"
        steps += 1
        assert steps < 2000
    assert [eng.output_of(r) for r in rids] == expect[:5]
    assert serving.serving_stats()["requests_preempted"] > 0
    st = eng.kv.prefix.stats
    assert st.hit_tokens > 0
    assert st.evicted_pages > 0          # pressure reclaimed cached pages


def test_prefix_spec_parity_survives_crash_recovery(model, shared_workload):
    """Injected fail@serve.step with prefix+spec on: the rebuild resets
    trie and draft pools, every stream replays bit-identical."""
    cfg, params = model
    prompts, n_new, expect = shared_workload
    serving.reset_stats()
    before = serving.serving_stats()["recoveries"]
    eng = serving.LLMEngine(cfg, params, max_running=4, chunk=8,
                            page_size=4, num_pages=200,
                            donate_pools=False, prefix_cache=True,
                            spec=_spec(cfg, params))
    rids = [eng.add_request(list(p), n_new) for p in prompts[:4]]
    with chaos.installed(chaos.Chaos("fail@serve.step:step=2,times=1")):
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
            assert steps < 2000
    assert [eng.output_of(r) for r in rids] == expect[:4]
    assert serving.serving_stats()["recoveries"] == before + 1
    assert eng.kv.audit()["ok"]


def test_chaos_exhaust_release_is_refcount_aware(model, shared_workload):
    """chaos `exhaust` under a populated prefix cache: the sweep grabs
    only free pages, release drops only chaos's own references, and the
    streams finish bit-identical with the audit intact."""
    cfg, params = model
    prompts, n_new, expect = shared_workload
    eng = serving.LLMEngine(cfg, params, max_running=2, chunk=8,
                            page_size=4, num_pages=40,
                            donate_pools=False, prefix_cache=True)
    rids = [eng.add_request(list(p), n_new) for p in prompts[:3]]
    with chaos.installed(
            chaos.Chaos("exhaust@serve.step:step=2,times=1")) as c:
        for _ in range(6):
            eng.step()
        assert eng.has_work()            # starved, not crashed
        cached = set(eng.kv.prefix.cached_pages())
        for _alloc, pages in c.rules[0].held_pages:
            assert cached.isdisjoint(pages)  # never stole a cached page
        # a cached page shared with chaos's tenant must survive release
        c.release_exhausted()
        while eng.has_work():
            eng.step()
    assert [eng.output_of(r) for r in rids] == expect[:3]
    assert eng.kv.audit()["ok"]


def test_chaos_release_skips_recycled_pages():
    """release_exhausted decrefs only pages chaos still holds — a page
    some other path already recycled is skipped, never double-freed."""
    a = BlockAllocator(8, 4)
    c = chaos.Chaos("exhaust@pool.x")
    c.hit("pool.x", pool=a)
    (rule,) = c.rules
    _alloc, pages = rule.held_pages[0]
    a.decref(pages[:1])                  # recycled out from under chaos
    c.release_exhausted()                # must not raise
    assert a.num_allocated == 0 and a.num_free == 7


# ---------------------------------------------------------------------------
# spec decode: greedy acceptance + verify bucket registration
# ---------------------------------------------------------------------------


def test_greedy_accept_prefix_of_agreement():
    # target row holds argmax at positions 0..k; drafts are the k
    # proposed tokens.  Emission = g0, then gi+1 while drafts agree.
    assert greedy_accept([5, 7], [5, 7, 9]) == [5, 7, 9]   # all accepted
    assert greedy_accept([5, 8], [5, 7, 9]) == [5, 7]      # 1 accepted
    assert greedy_accept([4, 7], [5, 7, 9]) == [5]         # 0 accepted
    assert greedy_accept([], [5]) == [5]                   # k=0 decode


def test_spec_verify_bucket_is_registered():
    names = {c[0] for c in pallas_ops.kernel_verify_cases()}
    assert "ragged_paged_attention_spec_verify" in names


def test_engine_rejects_bad_spec_config(model):
    import dataclasses
    cfg, params = model
    bad = dataclasses.replace(_tiny_cfg(), vocab_size=64)
    with pytest.raises(ValueError, match="vocab"):
        serving.LLMEngine(cfg, params, chunk=8,
                          spec=serving.SpecDecodeConfig(
                              cfg=bad, params=params, k=3))
    with pytest.raises(ValueError, match="spec.k"):
        serving.LLMEngine(cfg, params, chunk=4,
                          spec=serving.SpecDecodeConfig(
                              cfg=_tiny_cfg(), params=params, k=4))


# ---------------------------------------------------------------------------
# shared-prefix workload + pod_report capacity fold
# ---------------------------------------------------------------------------


def test_shared_prefix_workload_hits_and_accepts():
    """8 requests over 2 warm system prompts: more than half of the
    prompt tokens come from the radix cache, every hit token is a
    prefill token never fed, and the draft's proposals are accepted."""
    from paddle_tpu.serving import workloads

    cfg = llama.preset("llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_new, prompt_len, prefix_len = 6, 24, 18
    # prefix reuse is page-granular: toy prompts need toy pages, not the
    # engine's 128-token default. The draft IS the target, so every
    # proposal verifies.
    eng = serving.LLMEngine(
        cfg, params, max_running=8, chunk=8, page_size=16,
        max_model_len=prompt_len + n_new + 8, prefix_cache=True,
        spec=serving.SpecDecodeConfig(cfg=cfg, params=params, k=3))
    arrivals = workloads.generate(
        "shared-prefix", 8, prompt_len=prompt_len, prefix_len=prefix_len,
        n_groups=2, max_new_tokens=n_new, vocab=cfg.vocab_size)
    # system prompts are warm long before the traffic that is measured
    for head in {a.prompt[:prefix_len] for a in arrivals}:
        eng.add_request(list(head), 2)
    while eng.has_work():
        eng.step()
    base = serving.serving_stats()
    rids = [eng.add_request(list(a.prompt), a.max_new_tokens)
            for a in arrivals]
    while eng.has_work():
        eng.step()
    assert all(len(eng.output_of(r)) == n_new for r in rids)
    now = serving.serving_stats()
    hit, fed, proposed, accepted = (
        int(now[k] - base[k]) for k in
        ("prefix_hit_tokens", "prefill_tokens", "spec_proposed",
         "spec_accepted"))
    prompt_tokens = sum(len(a.prompt) for a in arrivals)
    assert hit / prompt_tokens > 0.5
    assert prompt_tokens - fed == hit > 0
    assert proposed > 0 and accepted > 0
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_pod_report_folds_prefix_hit_rate():
    import argparse

    from tools.pod_report import TPU_GENERATIONS, _parse_args, \
        _serving_section
    cfg = llama.preset("llama7b")
    gen = TPU_GENERATIONS["v5p"]
    args = argparse.Namespace(seq=2048, page_size=128, replicas=1,
                              prefix_hit_rate=0.5)
    plan = _serving_section(cfg, gen, args)
    # raw numbers stay alongside the effective ones
    assert plan["blocks_per_request"] == 16
    assert plan["effective_blocks_per_request"] == 8
    assert (plan["effective_max_concurrent_requests"]
            >= plan["max_concurrent_requests"])
    assert plan["prefix_hit_rate"] == 0.5
    # no flag -> no effective section (zero-reuse plan is the default)
    args2 = argparse.Namespace(seq=2048, page_size=128, replicas=1)
    assert "effective_blocks_per_request" not in _serving_section(
        cfg, gen, args2)
    assert _parse_args(["--prefix-hit-rate", "0.6"]).prefix_hit_rate == 0.6
    with pytest.raises(SystemExit):
        _serving_section(cfg, gen, argparse.Namespace(
            seq=2048, page_size=128, replicas=1, prefix_hit_rate=1.5))
