"""``models/cohere2_moe.py`` (parallel blocks of window and global attention
beside sigmoid-routed experts and averaged shared ones), the ring module it
shares with ``models/phi4flash.py`` and what ``models/experts.py`` gained for
it, against the float32 reference ``benchmark/reference_cohere2_moe.py`` at a
debug width on the CPU: the full-sequence forward, the engine's ragged step
on its rings and its global pool, the shares of an expert layer, and
``LLMEngine`` serving it — slot reuse, preemption-replay, a rebuilt engine —
with what the engine refuses for such a model and what it counts for it.

Tolerances.  Everything here is float32 against float32 with "highest"
matmuls (conftest): ``forward_pure`` against the reference within 1e-6
absolute on logits of magnitude 1, the engine's step within 2e-5 (the other
models' limit: rounding in another order of summation, the sorted groups
against a loop).  Each variant of the model that the chip's check must tell
apart lands beyond 1e-3."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_cohere2_moe as reference
from paddle_tpu import serving
from paddle_tpu.models import cohere2_moe, experts, llama, rings
from paddle_tpu.ops import pallas_ops
from paddle_tpu.testing import chaos
import test_jamba
from test_jamba import close, drain, prompts_of
from test_spans import scopes_of

PAGE = 16      # the debug window is 20: a ring of 4 pages, 64 positions


@pytest.fixture(scope="module", autouse=True)
def _short_padding():
    """The reference pads its rows to 2,048 for the chip's sake; a test row
    is at most 512 long."""
    old, reference.PAD_TO = reference.PAD_TO, 128
    yield
    reference.PAD_TO = old


@pytest.fixture
def interpret():
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = False


def fields_of(cfg) -> dict:
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != "dtype"}


@pytest.fixture(scope="module")
def model():
    cfg = cohere2_moe.preset("cohere2-moe-debug", dtype=jnp.float32)
    params = cohere2_moe.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, fields_of(cfg)


def ref_logits(model, rows):
    _, params, fields = model
    return reference.logits(fields, params, rows)


# -- sizes --------------------------------------------------------------------

CELL = dict(num_hidden_layers=4, num_experts=16,
            experts_held=tuple(range(16)), num_experts_published=128,
            vocab_size=32768, vocab_size_published=262144)


@pytest.mark.parametrize("name, overrides, want", [
    ("cohere2-moe-debug", {}, None),
    ("command-a-plus", {}, 218_254_938_112),
    ("command-a-plus", CELL, 4_733_292_544)])
def test_param_count_equals_the_tree(name, overrides, want):
    cfg = cohere2_moe.preset(name, **overrides)
    params = jax.eval_shape(functools.partial(cohere2_moe.init_params, cfg),
                            jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == cohere2_moe.param_count(cfg)
    assert want is None or n == want


def test_the_presets_have_the_layers_they_say():
    debug, full = (cohere2_moe.preset(n)
                   for n in ("cohere2-moe-debug", "command-a-plus"))
    assert debug.layer_kinds() == 2 * (3 * ["window"] + ["global"])
    kinds = full.layer_kinds()
    assert (kinds.count("window"), kinds.count("global")) == (24, 8)
    assert (full.num_window_layers, full.num_global_layers) == (24, 8)
    assert rings.ring_pages(full.sliding_window, 128) == 34 \
        and rings.ring_pages(debug.sliding_window, PAGE) == 4
    assert debug.sliding_window < 2 * PAGE


def test_a_config_states_its_share_and_its_pattern_or_is_refused():
    with pytest.raises(ValueError, match="says which"):
        cohere2_moe.preset("command-a-plus", num_experts=16,
                           num_experts_published=128)
    with pytest.raises(ValueError, match="experts_held names"):
        cohere2_moe.preset("command-a-plus", num_experts=16,
                           experts_held=(0, 1), num_experts_published=128)
    with pytest.raises(ValueError, match="layer_switch"):
        cohere2_moe.preset("command-a-plus", layer_types=(
            "full_attention", "sliding_attention", "sliding_attention",
            "sliding_attention") * 8)
    with pytest.raises(ValueError, match="parallel blocks"):
        cohere2_moe.preset("command-a-plus", use_parallel_block=False)
    with pytest.raises(ValueError, match="periods"):
        cohere2_moe.preset("command-a-plus", num_hidden_layers=6)


def test_the_cache_is_a_global_pool_and_rings_of_the_window():
    cfg = cohere2_moe.preset("command-a-plus", **CELL)
    layout = cohere2_moe.cache_bytes(cfg)
    token = 2 * 8 * 128 * 2                  # K and V, a token and layer
    assert layout == {"per_token": token, "scales_per_page": 0,
                      "per_slot": 34 * 3 * 128 * token,
                      "fixed": 3 * 128 * token}
    cache = jax.eval_shape(lambda: cohere2_moe.init_cache(
        cfg, 32, 4481, 128, jnp.bfloat16))
    assert cache["k_pages"].shape == (1, 8, 4481, 128, 128)
    assert cache["kw_pages"].shape == (3, 8, 1 + 32 * 34, 128, 128)


# -- the whole sequence -----------------------------------------------------------

@pytest.fixture(scope="module")
def whole(model):
    """Two sequences three windows long, and ``forward_pure`` of them."""
    cfg, params, _ = model
    ids = jnp.asarray(prompts_of(60, 60, seed=1), jnp.int32)
    return ids, jax.jit(functools.partial(cohere2_moe.forward_pure, cfg))(
        params, ids)


def test_forward_pure_equals_the_reference_in_float32(model, whole):
    cfg, params, fields = model
    ids, got = whole
    want = reference.forward(fields, params, ids)
    assert got.shape == (2, 60, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-6


@pytest.mark.parametrize("variant", sorted(reference.VARIANTS))
def test_a_variant_of_the_model_is_further_off_than_the_tolerance(
        model, whole, variant):
    """What the chip's check has to tell apart, each a model one could have
    built by mistake: no window, rotary on the global layers, a softmax
    router, top-k weights not renormalised, the shared experts summed, a
    sequential block, every matrix in int8."""
    _, params, fields = model
    ids, got = whole
    want = reference.forward(dict(fields, **reference.VARIANTS[variant]),
                             params, ids)
    assert float(jnp.abs(got - want).max()) > 1e-3    # fifty times 2e-5


# -- the expert layer -------------------------------------------------------------

def test_the_router_scores_by_sigmoid_and_renormalises():
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (16, 8), jnp.float32)
    logits = x @ w
    weights, chosen = experts.route_top_k(x, w, 3, score="sigmoid",
                                          renormalize=True)
    s = jax.nn.sigmoid(logits)
    want = jnp.argsort(-s, axis=-1, stable=True)[:, :3]
    assert np.array_equal(chosen, want)
    kept = jnp.take_along_axis(s, want, axis=-1)
    assert np.allclose(weights, kept / kept.sum(-1, keepdims=True),
                       atol=1e-6)
    assert np.allclose(weights.sum(-1), 1.0, atol=1e-6)
    # the default is the softmax, the probabilities as they are
    plain, same = experts.route_top_k(x, w, 3)
    assert np.array_equal(same, want)               # a monotone map
    assert np.allclose(plain, jnp.take_along_axis(
        jax.nn.softmax(logits, -1), want, axis=-1), atol=1e-6)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        experts.route_top_k(x, w, 3, score="relu")


def test_shared_experts_are_one_wide_swiglu_summed_or_averaged():
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(k[0], (5, 16), jnp.float32)
    g, u = (jax.random.normal(kk, (16, 3 * 8), jnp.float32) for kk in k[1:3])
    d = jax.random.normal(k[3], (3 * 8, 16), jnp.float32)
    each = [(jax.nn.silu(x @ g[:, 8 * j:8 * j + 8]) * (x @ u[:, 8 * j:8 * j + 8]))
            @ d[8 * j:8 * j + 8] for j in range(3)]
    summed = experts.shared_experts(x, g, u, d, count=3)
    assert np.allclose(summed, sum(each), atol=1e-5)
    mean = experts.shared_experts(x, g, u, d, count=3, combine="average")
    assert np.allclose(mean, sum(each) / 3, atol=1e-5)
    with pytest.raises(ValueError, match="summed or averaged"):
        experts.shared_experts(x, g, u, d, count=3, combine="max")


def _layer(cfg, params, xn, l, stacks):
    lp = {n: w[l] for n, w in params["layers"].items()}
    return cohere2_moe._expert_layer(cfg, lp, stacks, xn, l, None)


def test_the_shares_of_a_layer_add_up_to_the_layer(model):
    """The guide's share test: the debug preset's 8 routed experts as 4
    shares of 2, each device routing over all 8.  Each share's routed part
    summed over the shares, and the shared experts, which every device
    computes alike, counted once, equal the reference's uncut expert layer;
    and the reference given a share computes that share."""
    cfg, params, fields = model
    ex = params["experts"]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(20, 128)),
                    jnp.float32)
    whole, rows = _layer(cfg, params, x, 1, ex)
    want = reference.expert_layer(fields, params, x, 1)
    assert float(np.abs(whole - want).max()) < 2e-5
    # the shared part: what a device that holds NO routed expert computes
    none = dataclasses.replace(cfg, num_experts=0, experts_held=(),
                               num_experts_published=8)
    shared, no_rows = _layer(none, params, x, 1,
                             {n: w[:, :0] for n, w in ex.items()})
    assert no_rows.shape == (0,)
    parts, seen = [], 0
    for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
        share = dataclasses.replace(cfg, num_experts=2, experts_held=held,
                                    num_experts_published=8)
        mine = {n: w[:, np.asarray(held)] for n, w in ex.items()}
        assert cohere2_moe.param_count(share) == sum(
            a.size for a in jax.tree_util.tree_leaves(jax.eval_shape(
                functools.partial(cohere2_moe.init_params, share),
                jax.random.PRNGKey(0))))
        part, got = _layer(share, params, x, 1, mine)
        assert np.array_equal(got, rows[np.asarray(held)])
        parts.append(part - shared)               # its routed part alone
        seen += int(got.sum())
        theirs = reference.expert_layer(
            dict(fields, num_experts=2, experts_held=held,
                 published={"num_experts": 8}),
            dict(params, experts=mine), x, 1)
        assert float(np.abs(part - theirs).max()) < 2e-5
    assert seen == 20 * cfg.num_experts_per_tok      # every pair once
    assert float(np.abs(sum(parts) + shared - want).max()) < 2e-5


# -- the ragged step, driven directly ----------------------------------------

def Rows(model, **kw):
    """``test_jamba.Rows`` on this model's step and cache."""
    return test_jamba.Rows(model, module=cohere2_moe, **kw)


@pytest.mark.parametrize("chunk", [16, 4])
def test_prefill_in_chunks_then_decode_equals_one_full_forward(model, chunk):
    """110 tokens on a ring of 64 positions: the ring wraps during the
    prefill and again while decoding."""
    (seq,) = prompts_of(110, seed=2)
    (want,) = ref_logits(model, [seq])
    rows, got, pos = Rows(model), [], 0
    while pos < 85:                              # the prompt, in chunks
        got.append(rows.feed(chunk, {0: seq[pos:pos + chunk][:85 - pos]})[0])
        pos += len(got[-1])
    for t in seq[85:]:                           # then one token a step
        got.append(rows.feed(1, {0: [t]})[0])
    close(np.concatenate(got), want)


def test_ragged_neighbours_and_a_decode_row_inside_a_chunk_bucket(model):
    a, b, c = prompts_of(78, 37, 9, seed=3)
    want = ref_logits(model, [a, b, c])
    rows = Rows(model)
    got = {0: [], 1: [], 2: []}

    def step(Tc, fed):
        for r, out in rows.feed(Tc, fed).items():
            got[r].append(out)

    step(16, {0: a[:16], 1: b[:5], 2: c[:8]})     # three lengths, one step
    step(16, {0: a[16:29], 1: b[5:21], 2: c[8:]})  # c decodes beside chunks
    step(16, {0: a[29:45], 1: b[21:37]})           # c sits idle
    step(16, {0: a[45:61]})
    step(16, {0: a[61:77]})                        # a's ring has wrapped
    step(16, {0: a[77:]})
    for r in range(3):
        close(np.concatenate(got[r]), want[r])


def test_padding_and_idle_rows_leave_the_rings_as_they_were(model):
    a, b = prompts_of(12, 20, seed=4)
    rows = Rows(model)
    rows.feed(16, {0: a, 1: b[:16]})
    before = jax.tree_util.tree_map(np.asarray, rows.cache)
    rows.feed(16, {1: b[16:]})                   # row 0 idle, row 2 never fed
    Wp = rings.ring_pages(rows.cfg.sliding_window, PAGE)
    for key in ("kw_pages", "vw_pages"):
        after = np.asarray(rows.cache[key])
        ring = lambda r: slice(1 + r * Wp, 1 + (r + 1) * Wp)  # noqa: E731
        assert np.array_equal(after[:, :, ring(0)], before[key][:, :, ring(0)])
        assert not np.array_equal(after[:, :, ring(1)],
                                  before[key][:, :, ring(1)])
        assert not after[:, :, ring(2)].any() and not after[:, :, 0].any()
        # row 0 wrote 12 positions of its first ring page and no padding
        assert after[:, :, 1, :12].any(axis=-1).all()
        assert not after[:, :, 1, 12:].any()
    # the padding of row 0's chunk never entered its ring or its pages:
    # continuing from it equals the reference
    (want,) = ref_logits(model, [a + b[:3]])
    close(rows.feed(16, {0: b[:3]})[0], want[12:])


def test_a_request_from_zero_in_a_used_slot_needs_no_reset(model):
    """A second request in slot 0 after a first one wrapped its rings: it
    rewrites every ring page before it reads it."""
    a, b = prompts_of(75, 40, seed=5)
    rows, pos = Rows(model), 0
    while pos < len(a):
        rows.feed(16, {0: a[pos:pos + 16]})
        pos += 16
    rows.restart(0)
    (want,) = ref_logits(model, [b])
    got = [rows.feed(16, {0: b[p:p + 16]})[0] for p in range(0, len(b), 16)]
    close(np.concatenate(got), want)


def test_the_flat_step_equals_the_padded_one(model):
    """``step_tokens`` (the engine's flat batch) against the padded rows."""
    cfg, params, _ = model
    a, b = prompts_of(30, 9, seed=6)
    R, Tc = 3, 16
    cache = cohere2_moe.init_cache(cfg, R, 1 + 3 * 8, PAGE, jnp.float32)
    tbl = jnp.asarray(1 + np.arange(R)[:, None] * 8 + np.arange(8)[None, :],
                      jnp.int32)
    tokens = np.zeros((R, Tc), np.int32)
    tokens[0], tokens[2, :9] = a[:16], b
    lens, qlens = jnp.asarray([16, 0, 9]), jnp.asarray([16, 0, 9])
    padded, _ = cohere2_moe.forward_paged(cfg, params, jnp.asarray(tokens),
                                          cache, tbl, lens, qlens)
    flat, _, counts = cohere2_moe.forward_paged(
        cfg, params, jnp.asarray(tokens), cache, tbl, lens, qlens,
        step_tokens=32, device_counts=True)
    close(np.asarray(flat[:16]), np.asarray(padded[0]))
    close(np.asarray(flat[16:25]), np.asarray(padded[2, :9]))
    hit, most, held = map(int, counts)
    assert held == 25 * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert 1 <= most <= 25 and cfg.num_hidden_layers <= hit \
        <= cfg.num_hidden_layers * cfg.num_experts


def test_the_layers_scopes_and_kernels_are_in_the_step(model, interpret):
    cfg = cohere2_moe.preset("cohere2-moe-debug", dtype=jnp.float32,
                             head_dim=128, hidden_size=256)
    params = jax.eval_shape(functools.partial(cohere2_moe.init_params, cfg),
                            jax.random.PRNGKey(0))
    R = 2
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = jax.eval_shape(lambda: cohere2_moe.init_cache(
        cfg, R, 5, 128, jnp.float32))
    found = scopes_of(functools.partial(cohere2_moe.forward_paged, cfg),
                      params, i32(R, 16), cache, i32(R, 2), i32(R), i32(R))
    paths = {s for _, s in found}
    for scope in ("embed", "layers/norm", "attn/attn_window",
                  "attn/attn_global", "attn_window/kv_write",
                  "attn_global/kv_write", "attn_window/attn_kernel",
                  "attn_global/attn_kernel", "layers/moe", "moe/moe_router",
                  "moe/moe_experts", "moe/moe_shared", "lm_head"):
        assert any(p.endswith(scope) for p in paths), scope
    assert any(p == "logistic" and s.endswith("moe/moe_router")
               for p, s in found)
    # a window layer's body and the global layer's each call the write,
    # the attention and the grouped experts (held share: both of the
    # expert layer's paths)
    calls = sorted(s.split("layers/")[-1] for p, s in found
                   if p == "pallas_call")
    assert [c for c in calls if "attn" in c] == sorted(
        f"attn/attn_{k}/{w}" for k in ("global", "window") for w in (
            "attn_kernel/pallas/_rpa_kernel", "kv_write/pallas/_kv_write_kernel"))
    assert {c for c in calls if "moe" in c} == {
        "moe/moe_experts/pallas/_moe_experts_kernel"}
    # the attention kernel's scope holds the kernel and no product of its
    # own (those inside the kernel's body are under its name)
    assert not [s for p, s in found if p == "dot_general"
                and s.endswith("attn_kernel")]


def test_step_counts_are_what_a_window_layer_reads(model):
    cfg = model[0]                                   # window 20
    got = cohere2_moe.step_counts(cfg, np.array([16, 50, 0, 21, 7]),
                                  np.array([16, 1, 0, 16, 0]))
    assert got == {"window_kv_tokens": 16 + 20 + 21,
                   "window_qk_pairs": sum(range(1, 17)) + 20
                   + sum(min(p + 1, 20) for p in range(5, 21))}


# -- LLMEngine ----------------------------------------------------------------

def engine(model, **kw):
    cfg, params, _ = model
    kw = dict(dict(max_running=3, chunk=16, page_size=PAGE,
                   max_model_len=128), **kw)
    return serving.LLMEngine(cfg, params, **kw)


def greedy_of(model, prompts, n_new):
    """The reference's own greedy streams, a token at a time."""
    out = []
    for p in prompts:
        seq = list(p)
        for _ in range(n_new):
            (rows,) = ref_logits(model, [seq])
            seq.append(int(rows[-1].argmax()))
        out.append(seq[len(p):])
    return out


@pytest.fixture(scope="module")
def workload(model):
    prompts = prompts_of(5, 37, 16, 90, 23, seed=6)
    return prompts, 6, greedy_of(model, prompts, 6)


def test_the_engine_serves_it_and_counts_what_the_model_counts(
        model, workload):
    prompts, n_new, expect = workload
    serving.reset_stats()
    eng = engine(model)                  # five requests on three slots
    assert not eng._model.recurrent_state and eng._model.rings
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect
    assert sorted(eng._step_fns) == [1, 16]
    stats = serving.serving_stats()
    layout = cohere2_moe.cache_bytes(eng.cfg, 4, PAGE)
    assert stats["state_bytes"] == eng._state_bytes \
        == 3 * layout["per_slot"] + layout["fixed"] > 0
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    cfg = eng.cfg
    assert fed <= stats["window_qk_pairs"] <= 20 * fed
    assert 0 < stats["window_kv_tokens"] <= stats["window_qk_pairs"]
    # every expert is held: each live pair lands on one
    assert stats["held_rows"] == fed * cfg.num_experts_per_tok \
        * cfg.num_hidden_layers
    assert 1 <= stats["expert_rows_max"] <= eng.scheduler.step_tokens
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference.served_checks(model[2], eng, model[1], served)
    assert verdict["logits_rel_err"] < 1e-5
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["route_flip_share"] == 0.0        # float32 on both sides
    assert verdict["replayed_prompt"] == 90      # the request of most tokens
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_the_pallas_kernels_serve_the_same_streams(interpret):
    """The windowed RPA kernel, the write kernel and the grouped experts
    under the interpreter, at the head width they need (128) and pages of
    128: a window of 40, so a ring of 3 pages (384 positions); the longer
    request wraps its rings and its walk starts past page 0."""
    cfg = cohere2_moe.preset(
        "cohere2-moe-debug", dtype=jnp.float32, hidden_size=256,
        head_dim=128, num_hidden_layers=4, sliding_window=40)
    params = cohere2_moe.init_params(cfg, jax.random.PRNGKey(1))
    prompts = prompts_of(420, 30, seed=9)
    eng = serving.LLMEngine(cfg, params, max_running=2, chunk=16,
                            max_model_len=512)
    assert pallas_ops.ragged_attention_available(
        None, eng._pools["kw_pages"].shape)
    assert pallas_ops.moe_experts_available(
        (128, cfg.hidden_size), params["experts"]["w_gate"].shape)
    rids = [eng.add_request(p, 2) for p in prompts]
    drain(eng)
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    old, reference.PAD_TO = reference.PAD_TO, 512
    try:
        verdict = reference.served_checks(fields_of(cfg), eng, params,
                                          served)
    finally:
        reference.PAD_TO = old
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["logits_rel_err"] < 1e-5


def test_preemption_replays_through_rewritten_rings(model):
    """chaos steals every free page while both requests decode towards a
    page boundary: the scheduler preempts, and the preempted request later
    replays its whole history from position 0, rewriting its rings; the
    streams are the uninterrupted ones."""
    prompts, n_new = prompts_of(14, 30, seed=7), 6
    expect = greedy_of(model, prompts, n_new)
    eng = engine(model, max_running=2)
    rids = [eng.add_request(p, n_new) for p in prompts]
    before = serving.serving_stats()["requests_preempted"]
    with chaos.installed(
            chaos.Chaos("exhaust@serve.step:step=2,times=1")) as c:
        for _ in range(8):
            eng.step()
        assert serving.serving_stats()["requests_preempted"] > before
        c.release_exhausted()
        drain(eng)
    assert [eng.output_of(r) for r in rids] == expect


def test_a_failed_step_rebuilds_the_cache_and_replays_the_same_streams(
        model, workload):
    prompts, n_new, expect = workload
    eng = engine(model)
    rids = [eng.add_request(p, n_new) for p in prompts[:3]]
    before = serving.serving_stats()["recoveries"]
    with chaos.installed(chaos.Chaos("fail@serve.step:step=3,times=1")):
        drain(eng)
    assert serving.serving_stats()["recoveries"] == before + 1
    assert [eng.output_of(r) for r in rids] == expect[:3]
    assert eng.kv.audit()["ok"]


@pytest.mark.parametrize("refused", ["prefix_cache", "spec"])
def test_the_engine_refuses_what_rings_cannot_do(model, refused):
    draft = llama.preset("llama-debug")
    kw = {"prefix_cache": dict(prefix_cache=True),
          "spec": dict(spec=serving.SpecDecodeConfig(
              cfg=draft, params=None, k=2))}[refused]
    with pytest.raises(ValueError, match="ring a slot"):
        engine(model, **kw)


def test_a_chunk_longer_than_a_page_is_refused(model):
    with pytest.raises(ValueError, match="longer than a page"):
        engine(model, page_size=8)._lower(16)
