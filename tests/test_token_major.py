"""The token-major serve step: ``forward_paged(step_tokens=T)`` computes a
budget of fed tokens where the padded program computes ``R x Tc`` positions,
and ``Scheduler`` keeps every mixed step within that budget.

(a) the flat program against the padded one on ragged batches, both models
and int8 pages; (b) the scheduler's budget over random arrivals, and budgeted
engines against unbudgeted ones token for token; (c) the engine refuses a plan
over budget."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import jamba, llama
from paddle_tpu.models.step_layout import StepLayout
from paddle_tpu.serving import scheduler as sched
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.scheduler import Request, ScheduledSeq, Scheduler

PAGE = 8


def with_budget(eng, step_tokens):
    """``eng`` with a scheduler held to ``step_tokens``: the engine takes no
    budget of its own, it reads its scheduler's."""
    eng.scheduler = Scheduler(eng.kv, max_running=eng.max_running,
                              chunk=eng.chunk,
                              max_model_len=eng.max_model_len,
                              step_tokens=step_tokens)
    eng.scheduler.spec_k = eng._spec_k
    return eng


# -- the index maps -----------------------------------------------------------

@pytest.mark.parametrize("q_lens, T", [([0, 1, 4, 2, 0, 1], 8),
                                       ([0, 1, 4, 2, 0, 1], 11),
                                       ([0, 0, 0], 4), ([4, 4], 8)])
def test_the_layouts_maps_are_inverse_on_fed_tokens_and_zero_elsewhere(
        q_lens, T):
    q = np.asarray(q_lens, np.int32)
    R, Tc = len(q), 4
    lay = StepLayout(jnp.asarray(q), Tc, T)
    padded = np.arange(1, R * Tc + 1, dtype=np.float32).reshape(R, Tc, 1) \
        * np.ones((1, 1, 3), np.float32)
    fed = np.arange(Tc)[None, :] < q[:, None]
    flat = np.asarray(lay.flat(jnp.asarray(padded)))
    assert flat.shape == (T, 3)
    np.testing.assert_array_equal(flat[:q.sum()], padded[fed])
    assert not flat[q.sum():].any()
    back = np.asarray(lay.rows(jnp.asarray(flat)))
    np.testing.assert_array_equal(back, padded * fed[:, :, None])
    start = np.cumsum(q) - q
    for r in np.flatnonzero(q):
        assert int(lay.last[r]) == start[r] + q[r] - 1
    same = StepLayout(jnp.asarray(q), Tc)
    assert same.T == R * Tc and not same.compact
    np.testing.assert_array_equal(
        np.asarray(same.rows(same.flat(jnp.asarray(padded)))), padded)


# -- (a) the flat program against the padded one ------------------------------

R, TC, BLOCKS = 6, 8, 4
# the second step of the comparison, slot by slot: idle, a decode row, a full
# chunk, a partial chunk, a fresh row (its first tokens), a decode row
FIRST = [0, 9, 8, 3, 0, 5]
SECOND = [0, 1, 8, 5, 3, 1]


def llama_case(kv_dtype):
    cfg = llama.preset("llama-debug", num_key_value_heads=2,
                       dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache = llama.init_cache(cfg, R, 1 + R * BLOCKS, PAGE, kv_dtype)
    return cfg, params, cache, llama.SERVING.forward_paged


def jamba_case(_):
    cfg = jamba.preset("jamba-debug", dtype=jnp.float32)
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    cache = jamba.init_cache(cfg, R, 1 + R * BLOCKS, PAGE, jnp.float32)
    return cfg, params, cache, jamba.forward_paged


def run_steps(fwd, cfg, params, cache, step_tokens, seed=0):
    """Two steps over the same cache: ``FIRST`` (in chunks of at most TC)
    seeds pages and state, ``SECOND`` is the ragged batch compared.  Returns
    the second step's logits at its fed positions, row by row, and the
    cache."""
    rng = np.random.default_rng(seed)
    tbl = np.zeros((R, BLOCKS), np.int32)
    for r in range(R):
        tbl[r] = 1 + r * BLOCKS + np.arange(BLOCKS)
    lens = np.zeros((R,), np.int32)
    step = jax.jit(functools.partial(fwd, cfg), static_argnames="step_tokens")
    feeds = []
    left = np.asarray(FIRST)
    while left.any():
        feeds.append(np.minimum(left, TC))
        left = left - feeds[-1]
    feeds.append(np.asarray(SECOND))
    for q in feeds:
        q = q.astype(np.int32)
        tokens = np.zeros((R, TC), np.int32)
        for r in range(R):
            tokens[r, :q[r]] = rng.integers(1, cfg.vocab_size, q[r])
        lens = lens + q
        T = step_tokens if step_tokens is None else max(step_tokens, q.sum())
        logits, cache = step(params, jnp.asarray(tokens), cache,
                             jnp.asarray(tbl), jnp.asarray(lens * (q > 0)),
                             jnp.asarray(q), step_tokens=T)
    logits = np.asarray(logits)
    if step_tokens is None:
        assert logits.shape == (R, TC, cfg.vocab_size)
        fed = [logits[r, :q[r]] for r in range(R)]
    else:
        assert logits.shape == (T, cfg.vocab_size)
        start = np.cumsum(q) - q
        fed = [logits[start[r]:start[r] + q[r]] for r in range(R)]
    return fed, cache


@pytest.mark.parametrize("T", [sum(SECOND), sum(SECOND) + 7])
@pytest.mark.parametrize("case, kv_dtype", [
    (llama_case, jnp.float32), (llama_case, jnp.int8),
    (jamba_case, jnp.float32)], ids=["llama-gqa", "llama-int8-pages", "jamba"])
def test_the_flat_program_equals_the_padded_one_on_a_ragged_batch(
        case, kv_dtype, T):
    cfg, params, cache, fwd = case(kv_dtype)
    want, want_cache = run_steps(fwd, cfg, params, cache, None)
    got, got_cache = run_steps(fwd, cfg, params, cache, T)
    for r in range(R):
        assert got[r].shape == want[r].shape == (SECOND[r], cfg.vocab_size)
        np.testing.assert_allclose(got[r], want[r], atol=1e-5, rtol=0)
    # K/V pages (and their scales), convolution inputs and scan state
    leaves = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(leaves(got_cache), leaves(want_cache)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and np.abs(b).max() > 0, path
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=0, err_msg=str(path))


def test_a_row_that_is_not_a_number_stays_in_its_row():
    """Zeros, not a neighbour's values, fill what holds no token: a poisoned
    request cannot reach another row's state through the padding."""
    cfg, params, cache, fwd = jamba_case(None)
    bad = dict(params)
    tokens = np.ones((R, TC), np.int32)
    q = np.asarray([2, 1, 0, 3, 1, 1], np.int32)
    tbl = (1 + np.arange(R)[:, None] * BLOCKS
           + np.arange(BLOCKS)[None, :]).astype(np.int32)
    embed = np.asarray(bad["embed"]).copy()
    embed[7] = np.nan
    bad["embed"] = jnp.asarray(embed)
    tokens[3, :3] = 7                       # row 3 feeds the poisoned token
    logits, out = fwd(cfg, bad, jnp.asarray(tokens), cache, jnp.asarray(tbl),
                      jnp.asarray(q), jnp.asarray(q), step_tokens=8)
    ssm = np.asarray(out["ssm"])            # [M, N, R, E]
    assert np.isnan(ssm[:, :, 3]).any()
    assert np.isfinite(np.delete(ssm, 3, axis=2)).all()
    start = np.cumsum(q) - q
    # (the head is tied to the embedding: token 7's column is its own)
    logits = np.delete(np.asarray(logits), 7, axis=1)
    assert np.isnan(logits[start[3]:start[3] + 3]).all()
    for r in (0, 1, 4, 5):
        assert np.isfinite(logits[start[r]:start[r] + q[r]]).all()


# -- the budget itself --------------------------------------------------------

@pytest.mark.parametrize("max_running, chunk, spec_k, want", [
    (8, 16, 0, 128),        # smaller than the least budget: every position
    (48, 16, 0, 256), (128, 16, 0, 256),    # the benchmark's two engines
    (128, 16, 3, 640),      # 128 verify rows of 4 and a chunk: 528 -> 640
    (256, 32, 0, 384), (16, 64, 0, 256)])
def test_the_budget_follows_from_the_engines_shapes(max_running, chunk,
                                                   spec_k, want):
    kv = PagedKVCache(num_pages=9, page_size=PAGE, max_blocks=4)
    s = Scheduler(kv, max_running=max_running, chunk=chunk)
    s.spec_k = spec_k
    assert s.step_tokens == want
    assert want == max_running * chunk or (
        want % 128 == 0 and want >= sched._STEP_TOKENS
        and want >= max_running * (1 + spec_k) + chunk)


def test_a_budget_that_cannot_hold_the_decode_rows_and_a_chunk_is_refused():
    kv = PagedKVCache(num_pages=9, page_size=PAGE, max_blocks=4)
    s = Scheduler(kv, max_running=4, chunk=4, step_tokens=7)
    with pytest.raises(ValueError, match="step_tokens=7"):
        s.step_tokens
    assert Scheduler(kv, max_running=4, chunk=4, step_tokens=8).step_tokens \
        == 8
    # a budget of every position and more is the padded program's
    assert Scheduler(kv, max_running=4, chunk=4,
                     step_tokens=99).step_tokens == 16


# -- (b) the scheduler over random arrivals -----------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("spec_k", [0, 2])
def test_no_step_passes_the_budget_and_no_decode_row_ever_waits(seed, spec_k):
    rng = np.random.default_rng(seed)
    R_, chunk = 6, 4
    budget = R_ * (1 + spec_k) + chunk          # the least that is allowed
    kv = PagedKVCache(num_pages=1 + R_ * 8, page_size=PAGE, max_blocks=8)
    s = Scheduler(kv, max_running=R_, chunk=chunk, max_model_len=64,
                  step_tokens=budget)
    s.spec_k = spec_k
    todo = [Request(prompt=rng.integers(1, 99, rng.integers(1, 30)).tolist(),
                    max_new_tokens=int(rng.integers(1, 12)))
            for _ in range(40)]
    waited = {}                 # rid -> steps in a row it has been deferred
    longest = deferrals = steps = 0
    while todo or s.has_work():
        for _ in range(int(rng.integers(0, 4))):
            if todo:
                s.add(todo.pop())
        plan = s.schedule()
        steps += 1
        assert steps < 2000
        assert sum(q.q_len for q in plan.seqs) <= budget
        fed = {q.request.rid for q in plan.seqs}
        held = {r.rid for r in plan.deferred}
        running = {r.rid for r in s.slots if r is not None}
        assert fed | held == running and not fed & held
        for r in plan.deferred:
            assert r.num_known - r.fed > 1      # a prefill row, never decode
        assert bool(plan.seqs) or not running   # something is always fed
        deferrals += len(held)
        waited = {rid: waited.get(rid, 0) + 1 for rid in held}
        longest = max([longest, *waited.values()])
        # what the step "computed": accept one token of a verify chunk
        out = {q.slot: ([5] if q.spec else 5) for q in plan.seqs
               if q.produces}
        s.dispatch(plan)
        s.complete(plan, out)
    assert deferrals > 0, "the budget never bound: the test shows nothing"
    # a deferred row waits for the prefill rows admitted before it, at most
    # R - 1 of them with at most 30 / chunk chunks each
    assert longest <= (R_ - 1) * -(-30 // chunk)
    assert kv.audit()["ok"]


def drain(eng, prompts, n_new, arrive_every=2):
    """Feed ``prompts`` in as the engine steps; returns the outputs in the
    prompts' order, the most rows any step deferred and the buckets the
    engine compiled."""
    rids, pending, most = [], list(prompts), 0
    schedule = eng.scheduler.schedule

    def watching():
        nonlocal most
        plan = schedule()
        most = max(most, len(plan.deferred))
        return plan
    eng.scheduler.schedule = watching
    steps = 0
    while pending or eng.has_work():
        if pending and steps % arrive_every == 0:
            rids.append(eng.add_request(pending.pop(0), n_new))
        eng.step()
        steps += 1
        assert steps < 500
    assert eng.kv.audit()["ok"]
    outs, buckets = [eng.output_of(rid) for rid in rids], sorted(eng._step_fns)
    eng.shutdown()
    return outs, most, buckets


def served_model(name):
    if name == "jamba":
        cfg = jamba.preset("jamba-debug", dtype=jnp.float32)
        return cfg, jamba.init_params(cfg, jax.random.PRNGKey(0))
    cfg = llama.preset("llama-debug", dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("name, kw", [
    ("llama", {}), ("llama", {"kv_dtype": "int8"}),
    ("llama", {"prefix_cache": True}), ("llama", {"spec": 2}),
    ("jamba", {})], ids=["llama", "int8-pages", "prefix-cache", "spec",
                         "jamba"])
def test_a_budgeted_engine_serves_what_the_unbudgeted_one_serves(name, kw):
    cfg, params = served_model(name)
    kw = dict(kw)
    if "spec" in kw:
        draft = llama.preset("llama-debug", num_hidden_layers=1,
                             dtype=jnp.float32)
        kw["spec"] = serving.SpecDecodeConfig(
            draft, llama.init_params(draft, jax.random.PRNGKey(1)),
            k=kw["spec"])
    rng = np.random.default_rng(5)
    shared = rng.integers(1, 250, 9).tolist()
    prompts = [shared * (i % 2) + rng.integers(1, 250, n).tolist()
               for i, n in enumerate([23, 5, 17, 30, 2, 11, 26, 8])]
    build = functools.partial(
        serving.LLMEngine, cfg, params, max_running=4, chunk=8,
        page_size=PAGE, max_model_len=64, **kw)
    want, _, _ = drain(build(), prompts, 6)
    eng = build()
    assert eng.scheduler.step_tokens == 32       # every position
    k = eng._spec_k
    eng = with_budget(eng, 4 * (1 + k) + 8)
    got, most, buckets = drain(eng, prompts, 6)
    assert most > 0, "the budget never bound: the test shows nothing"
    assert got == want
    # (under speculation a decode row is a verify chunk: no Tc=1 step)
    assert buckets == ([8] if k else [1, 8])


def test_serving_stats_count_the_computed_positions_and_the_deferred_rows():
    serving.reset_stats()
    cfg, params = served_model("llama")
    eng = with_budget(serving.LLMEngine(
        cfg, params, max_running=4, chunk=8, page_size=PAGE,
        max_model_len=64), 12)
    plans = []
    schedule = eng.scheduler.schedule

    def recording():
        plans.append(schedule())
        return plans[-1]
    eng.scheduler.schedule = recording
    for n in (20, 18, 9):
        eng.add_request(list(range(1, n + 1)), 3)
    while eng.has_work():
        eng.step()
    eng.shutdown()
    stats = serving.serving_stats()
    assert stats["deferred_rows"] == sum(len(p.deferred) for p in plans) > 0
    assert stats["slot_tokens"] == sum(
        12 if p.bucket == 8 else 4 for p in plans if p.seqs)
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    assert 0 < fed <= stats["slot_tokens"]


# -- (c) the engine holds the plan to the program -----------------------------

def test_the_engine_raises_on_a_plan_over_its_programs_budget():
    cfg, params = served_model("llama")
    eng = with_budget(serving.LLMEngine(
        cfg, params, max_running=4, chunk=8, page_size=PAGE,
        max_model_len=64), 12)
    # a scheduler that feeds the rows its budget had deferred
    schedule, inner = eng.scheduler.schedule, eng.scheduler

    def loose():
        plan = schedule()
        for req in plan.deferred:
            q = inner._q_len(req)
            plan.seqs.append(ScheduledSeq(
                request=req, slot=inner._slot_of[req.rid], q_len=q,
                seq_len=req.fed + q, produces=False))
        return plan
    eng.scheduler.schedule = loose
    before = serving.serving_stats()
    for n in (20, 18):
        eng.add_request(list(range(1, n + 1)), 3)
    with pytest.raises(RuntimeError, match="feeds 16 tokens.*computes 12"):
        eng.step()
    after = serving.serving_stats()
    assert after["recoveries"] == before["recoveries"]   # no fault to recover
    assert after["steps"] == before["steps"]
    eng.shutdown()


def test_a_bisection_probe_keeps_within_the_budget_too():
    cfg, params = served_model("llama")
    eng = with_budget(serving.LLMEngine(
        cfg, params, max_running=4, chunk=8, page_size=PAGE,
        max_model_len=64), 12)
    group = [Request(prompt=list(range(1, 20)), max_new_tokens=2)
             for _ in range(4)]
    fed = []
    batch = eng._batch_arrays

    def watching(seqs, *a, **k):
        fed.append([s.q_len for s in seqs])
        return batch(seqs, *a, **k)
    eng._batch_arrays = watching
    assert eng._probe(group) is True
    assert fed == [[8, 2, 1, 1]] and sum(fed[0]) <= eng.scheduler.step_tokens
    eng.shutdown()
