"""``models/deepseek_v2.py`` (latent attention on a paged cache of latent
vectors, then expert layers that drop no token beside a shared expert) and
``models/experts.py`` against the float32 reference
``benchmark/reference_deepseek_v2.py``, at a debug width on the CPU: the
full-sequence forward (materialised heads), the engine's absorbed step on
chunked, ragged, flat and padded batches, the expert layer on its edge
cases and as a chip's share, and ``LLMEngine`` serving it through the model
protocol — preemption, the Pallas kernels under the interpreter, the prefix
cache on latent pages — with what the engine counts for it.

Tolerances.  Everything here is float32 against float32 with "highest"
matmuls (conftest): 2e-5 absolute on logits of magnitude 1, the other
models' limit, is rounding in another order of summation (absorbed against
materialised, sorted groups against a loop).  A departure from the model
must land beyond 1e-3."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_deepseek_v2 as reference
from paddle_tpu import serving
from paddle_tpu.models import deepseek_v2, experts
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import trace
from paddle_tpu.testing import chaos
from test_jamba import close, drain, prompts_of
from test_spans import scopes_of
from test_token_major import with_budget

PAGE = 16


@pytest.fixture(scope="module", autouse=True)
def _short_padding():
    """The reference pads its rows to 1,024 for the chip's sake; a test row
    is at most 256 long."""
    old, reference.PAD_TO = reference.PAD_TO, 128
    yield
    reference.PAD_TO = old


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != "dtype"}


@pytest.fixture(scope="module")
def model():
    cfg = deepseek_v2.preset("deepseek-v2-debug", dtype=jnp.float32)
    params = deepseek_v2.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, fields_of(cfg)


def ref_logits(model, rows):
    _, params, fields = model
    return reference.logits(fields, params, rows)


# -- sizes --------------------------------------------------------------------

@pytest.mark.parametrize("name, layers, want", [
    ("deepseek-v2-debug", 3, None),
    ("deepseek-v2-lite", 9, 5_179_222_528),
    ("deepseek-v2-lite", 27, 15_706_484_224)])
def test_param_count_equals_the_tree(name, layers, want):
    """From shapes, no arrays: the issue's arithmetic for the cut (1 dense +
    8 expert layers) and for the published depth."""
    cfg = deepseek_v2.preset(name, num_hidden_layers=layers)
    params = jax.eval_shape(functools.partial(deepseek_v2.init_params, cfg),
                            jax.random.PRNGKey(0))
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert count == deepseek_v2.param_count(cfg)
    assert want is None or count == want


def test_the_debug_preset_has_the_layers_it_says_and_kernel_widths():
    debug, lite = (deepseek_v2.preset(n) for n in ("deepseek-v2-debug",
                                                   "deepseek-v2-lite"))
    assert debug.first_k_dense_replace == 1 and debug.num_moe_layers >= 2
    assert (debug.n_routed_experts, debug.num_experts_per_tok,
            debug.n_shared_experts) == (8, 2, 1)
    assert debug.latent_lanes == 256 and lite.latent_lanes == 640
    # 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2, and cos and sin as they are
    assert lite.softmax_scale == pytest.approx(0.114721, rel=1e-5)
    inv_freq, factor = deepseek_v2.rope_frequencies(lite)
    assert factor == 1.0 and inv_freq.shape == (32,)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv_freq[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[24:], plain[24:] / 40, rtol=1e-6)


def test_the_cache_is_one_latent_a_token_and_nothing_else():
    lite = deepseek_v2.preset("deepseek-v2-lite", num_hidden_layers=9)
    layout = deepseek_v2.cache_bytes(lite)
    # 9 x (512 + 64) x 2 B, stored in five 128-lane tiles
    assert layout == {"per_token": 9 * 640 * 2, "scales_per_page": 0,
                      "per_slot": 0}
    assert serving.kv_bytes_per_token(lite) == 11_520
    cache = jax.eval_shape(lambda: deepseek_v2.init_cache(
        lite, 32, 2305, 128, jnp.bfloat16))
    assert {k: v.shape for k, v in cache.items()} \
        == {"latent": (9, 1, 2305, 128, 640)}


# -- the whole forward ----------------------------------------------------------

@pytest.fixture(scope="module")
def whole(model):
    cfg, params, _ = model
    ids = jnp.asarray(prompts_of(40, 40, seed=1), jnp.int32)
    return ids, jax.jit(functools.partial(deepseek_v2.forward_pure, cfg))(
        params, ids)


def test_forward_pure_equals_the_reference_in_float32(model, whole):
    cfg, params, fields = model
    ids, got = whole
    want = reference.forward(fields, params, ids)
    assert got.shape == (2, 40, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_the_reference_sums_experts_the_same_by_token_and_by_expert(
        model, whole, monkeypatch):
    _, params, fields = model
    ids, _ = whole
    by_token = reference.forward(fields, params, ids[:1])
    monkeypatch.setattr(reference, "BY_TOKEN_MAX", 0)
    by_expert = reference.forward(fields, params, ids[:1])
    assert float(jnp.abs(by_token - by_expert).max()) < 2e-5


def test_absorbed_equals_materialised(model, whole):
    """The engine's step on whole sequences as one chunk (the absorbed form
    over the paged latents) against ``forward_pure`` (materialised heads)."""
    cfg, params, _ = model
    ids, want = whole
    B, S = ids.shape
    cache = deepseek_v2.init_cache(cfg, B, 1 + B * 3, PAGE, jnp.float32)
    tbl = 1 + jnp.arange(B * 3, dtype=jnp.int32).reshape(B, 3)
    lens = jnp.full((B,), S, jnp.int32)
    got, cache = jax.jit(functools.partial(deepseek_v2.forward_paged, cfg))(
        params, ids, cache, tbl, lens, lens)
    assert float(jnp.abs(got - want).max()) < 2e-5
    # what was cached: the latent and the rotated key, zeros in the padding
    page = np.asarray(cache["latent"][:, 0, 1])
    used = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert np.abs(page[..., :used]).min() > 0 and not page[..., used:].any()


def _renormalised(monkeypatch):
    real = reference._route

    def route(h, router, f):
        p, chosen = real(h, router, f)
        return p / jnp.sum(p, -1, keepdims=True), chosen
    monkeypatch.setattr(reference, "_route", route)


@pytest.mark.parametrize("departure", ["no-yarn", "renormalised-top-k",
                                       "no-shared-expert", "one-expert-fewer"])
def test_a_departure_from_the_model_is_further_off_than_the_tolerance(
        model, whole, departure, monkeypatch):
    """What the comparison above can tell apart: each of these is a model
    one could have built by mistake, and none passes for the other."""
    cfg, params, fields = model
    ids, got = whole
    if departure == "no-yarn":
        fields = dict(fields, rope_scaling=None)
    elif departure == "renormalised-top-k":
        _renormalised(monkeypatch)
    elif departure == "no-shared-expert":
        params = dict(params, moe=dict(
            params["moe"], ws_down=jnp.zeros_like(params["moe"]["ws_down"])))
    else:
        fields = dict(fields,
                      num_experts_per_tok=cfg.num_experts_per_tok - 1)
    want = reference.forward(fields, params, ids)
    assert float(jnp.abs(got - want).max()) > 1e-3   # fifty tolerances


# -- the expert layer -----------------------------------------------------------

def _stacks(seed, E=6, D=16, F=24):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for s in ((E, D, F), (E, D, F), (E, F, D))]


def _by_token(x, weights, chosen, stacks, held=None):
    """The definition: a loop over tokens and their experts, in numpy."""
    x, weights, chosen = (np.asarray(a) for a in (x, weights, chosen))
    w_gate, w_up, w_down = (np.asarray(w, np.float64) for w in stacks)
    ids = list(range(w_gate.shape[0])) if held is None else list(held)
    y = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for k in range(chosen.shape[1]):
            if chosen[t, k] not in ids:
                continue
            e = ids.index(chosen[t, k])
            a, b = x[t] @ w_gate[e], x[t] @ w_up[e]
            y[t] += weights[t, k] * ((a / (1 + np.exp(-a)) * b) @ w_down[e])
    return y


@pytest.mark.parametrize("case", ["all-to-one-expert", "an-expert-left-empty",
                                  "padding-tokens", "uneven"])
def test_no_token_is_dropped_whatever_the_routing(case):
    """``routed_experts`` against the loop: every row sent to ONE expert
    (eight times a fair share: a capacity would drop most of them), an
    expert that gets none, tokens that are padding, a skewed draw."""
    T, K, E = 24, 2, 6
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.05, 0.5, (T, K)), jnp.float32)
    live = None
    if case == "all-to-one-expert":
        chosen = np.stack([np.full(T, 4), rng.integers(0, 4, T)], 1)
    elif case == "an-expert-left-empty":
        chosen = rng.choice([0, 1, 3, 4, 5], (T, K))
    else:
        chosen = np.minimum(rng.geometric(0.5, (T, K)) - 1, E - 1)
    if case == "padding-tokens":
        live = jnp.arange(T) < 17
    stacks = _stacks(4)
    got, rows = experts.routed_experts(
        x, weights, jnp.asarray(chosen, jnp.int32), *stacks, num_experts=E,
        live=live)
    if live is not None:
        weights = weights * live[:, None]
        assert int(rows.sum()) == 17 * K
    else:
        assert np.array_equal(rows, np.bincount(chosen.reshape(-1),
                                                minlength=E))
    if case == "all-to-one-expert":
        assert int(rows[4]) == T
    if case == "an-expert-left-empty":
        assert int(rows[2]) == 0
    np.testing.assert_allclose(got, _by_token(x, weights, chosen, stacks),
                               atol=2e-5)


def test_the_router_takes_the_largest_as_they_are_ties_to_the_lower_index():
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    # token 0: experts 1 and 3 tie for the lead, 0 and 2 tie behind them
    w = jnp.asarray([[0.0, 2.0, 0.0, 2.0], [0.3, 0.2, 0.1, 0.0]], jnp.float32)
    weights, chosen = experts.route_top_k(x, w, 3)
    assert chosen.tolist() == [[1, 3, 0], [0, 1, 2]]
    p = np.exp(np.asarray(w)) / np.exp(np.asarray(w)).sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, np.take_along_axis(
        p, np.asarray(chosen), -1), rtol=1e-6)       # not renormalised
    assert float(weights.sum(-1).max()) < 1.0


def test_the_shares_of_a_layer_add_up_to_the_layer(model):
    """The guide's share test: two devices, each holding half of the experts
    and routing over all of them, compute parts that add up to the whole
    layer when what both compute alike, the shared expert, is counted
    once; and the reference given a share computes that share."""
    cfg, params, fields = model
    lp = jax.tree_util.tree_map(lambda w: w[0], {
        n: w for n, w in params["moe"].items()
        if n not in deepseek_v2.EXPERT_LEAVES})
    stacks = {n: params["moe"][n] for n in deepseek_v2.EXPERT_LEAVES}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(20, 128)),
                    jnp.float32)
    whole, rows = deepseek_v2._expert_ffn(cfg, lp, stacks, x, 0, None)
    shared = deepseek_v2._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    parts, seen = [], 0
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        half = dataclasses.replace(cfg, experts_held=held)
        mine = {n: w[:, np.asarray(held)] for n, w in stacks.items()}
        assert deepseek_v2.param_count(half) == sum(
            a.size for a in jax.tree_util.tree_leaves(jax.eval_shape(
                functools.partial(deepseek_v2.init_params, half),
                jax.random.PRNGKey(0))))
        part, got = deepseek_v2._expert_ffn(half, lp, mine, x, 0, None)
        assert np.array_equal(got, rows[np.asarray(held)])
        parts.append(part)
        seen += int(got.sum())
        # the reference, given the same share, computes the same part
        p, chosen = reference._route(x, lp["router"], fields)
        theirs = shared + reference._experts_by_token(
            x, p, reference._local(dict(fields, experts_held=held), chosen),
            tuple(mine[n] for n in deepseek_v2.EXPERT_LEAVES), 0)
        assert float(jnp.abs(part - theirs).max()) < 2e-5
    assert seen == 20 * cfg.num_experts_per_tok        # every pair, once
    assert float(jnp.abs(parts[0] + parts[1] - shared - whole).max()) < 2e-5


# -- the ragged step, driven directly -------------------------------------------

class Rows:
    """``forward_paged`` on a cache of ``R`` slots, fed by hand: each call of
    ``feed`` is one engine step over ``{slot: tokens}``, on the padded
    layout or, with ``flat``, on a flat batch of that many positions."""
    module = deepseek_v2        # whose ``init_cache`` and ``forward_paged``

    def __init__(self, model, R=3, blocks=8, flat=None, page=PAGE):
        self.cfg, self.params, _ = model
        self.R, self.flat = R, flat
        self.cache = self.module.init_cache(self.cfg, R, 1 + R * blocks, page,
                                            jnp.float32)
        self.tbl = 1 + np.arange(R * blocks, dtype=np.int32).reshape(R, blocks)
        self.lens = np.zeros((R,), np.int32)
        self.fwd = jax.jit(functools.partial(self.module.forward_paged,
                                             self.cfg),
                           static_argnames=("step_tokens",))

    def feed(self, Tc, rows):
        tokens = np.zeros((self.R, Tc), np.int32)
        qlens = np.zeros((self.R,), np.int32)
        for r, toks in rows.items():
            tokens[r, :len(toks)] = toks
            qlens[r] = len(toks)
            self.lens[r] += len(toks)
        T = self.flat if Tc > 1 else None      # the decode step is padded
        logits, self.cache = self.fwd(
            self.params, jnp.asarray(tokens), self.cache,
            jnp.asarray(self.tbl), jnp.asarray(self.lens * (qlens > 0)),
            jnp.asarray(qlens), step_tokens=T)
        if T is None:
            return {r: np.asarray(logits[r, :len(t)])
                    for r, t in rows.items()}
        assert logits.shape[0] == T
        start = np.cumsum(qlens) - qlens
        return {r: np.asarray(logits[start[r]:start[r] + len(t)])
                for r, t in rows.items()}


@pytest.mark.parametrize("chunk, flat", [(16, None), (4, None), (16, 24),
                                         (4, 7)])
def test_prefill_in_chunks_then_decode_equals_one_full_forward(model, chunk,
                                                               flat):
    (seq,) = prompts_of(75, seed=2)
    (want,) = ref_logits(model, [seq])
    rows, got, pos = Rows(model, flat=flat), [], 0
    while pos < 61:                              # the prompt, in chunks
        got.append(rows.feed(chunk, {0: seq[pos:pos + chunk][:61 - pos]})[0])
        pos += len(got[-1])
    for t in seq[61:]:                           # then one token a step
        got.append(rows.feed(1, {0: [t]})[0])
    close(np.concatenate(got), want)


@pytest.mark.parametrize("flat", [None, 40])
def test_ragged_neighbours_and_a_decode_row_inside_a_chunk_bucket(model,
                                                                  flat):
    a, b, c = prompts_of(78, 37, 9, seed=3)
    want = ref_logits(model, [a, b, c])
    rows = Rows(model, flat=flat)
    got = {0: [], 1: [], 2: []}

    def step(Tc, fed):
        for r, out in rows.feed(Tc, fed).items():
            got[r].append(out)

    step(16, {0: a[:16], 1: b[:5], 2: c[:8]})     # three lengths, one step
    step(16, {0: a[16:29], 1: b[5:21], 2: c[8:]})  # c decodes beside chunks
    step(16, {0: a[29:45], 1: b[21:37]})           # c sits idle
    step(16, {0: a[45:61]})
    step(16, {0: a[61:77]})
    step(1, {0: a[77:]})
    for r in range(3):
        close(np.concatenate(got[r]), want[r])


def test_idle_rows_and_padding_write_nothing(model):
    a, b = prompts_of(12, 20, seed=4)
    rows = Rows(model)
    rows.feed(16, {0: a, 1: b[:16]})
    before = np.asarray(rows.cache["latent"])
    rows.feed(16, {1: b[16:]})                   # row 0 idle, row 2 never fed
    after = np.asarray(rows.cache["latent"])
    mine = lambda r: slice(1 + 8 * r, 9 + 8 * r)       # noqa: E731
    assert np.array_equal(after[:, :, mine(0)], before[:, :, mine(0)])
    assert not np.array_equal(after[:, :, mine(1)], before[:, :, mine(1)])
    assert not after[:, :, mine(2)].any() and not after[:, :, 0].any()
    # row 0 wrote 12 positions of its first page and no padding
    assert after[:, 0, 1, :12].any(axis=-1).all()
    assert not after[:, 0, 1, 12:].any()


def test_the_layers_scopes_are_in_the_step(model):
    cfg, params, _ = model
    R, Tc = 2, 4
    cache = jax.eval_shape(lambda: deepseek_v2.init_cache(cfg, R, 5, PAGE,
                                                          jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = (params, i32(R, Tc), cache, i32(R, 2), i32(R), i32(R))
    found = scopes_of(functools.partial(deepseek_v2.forward_paged, cfg),
                      *args)
    paths = {s for _, s in found}
    for scope in ("embed", "layers/attn_mla", "attn_mla/mla_core",
                  "attn_mla/kv_write", "layers/mlp", "layers/moe",
                  "moe/moe_router", "moe/moe_experts", "moe/moe_shared",
                  "lm_head"):
        assert any(p.endswith(scope) for p in paths), scope
    # the grouped products are under moe_experts and nothing else is; the
    # softmax over the experts is the router's
    ragged = [s for p, s in found if p == "ragged_dot_general"]
    assert len(ragged) == 3 and all(s.endswith("moe/moe_experts")
                                    for s in ragged)
    assert not {p for p, s in found if "moe_experts" in s.split("/")} & {
        "sort", "gather", "scatter", "top_k", "reduce_sum", "exp"}
    assert any(p == "top_k" and s.endswith("moe/moe_router")
               for p, s in found)
    # where the kernels serve (pages of 128, under the interpreter as on
    # the chip) each call sits under its scope, with its own name below
    pallas_ops._INTERPRET = True
    try:
        cache = jax.eval_shape(lambda: deepseek_v2.init_cache(
            cfg, R, 5, 128, jnp.float32))
        found = scopes_of(functools.partial(deepseek_v2.forward_paged, cfg),
                          params, i32(R, 16), cache, i32(R, 2), i32(R),
                          i32(R))
    finally:
        pallas_ops._INTERPRET = False
    calls = sorted(s.split("layers/")[-1] for p, s in found
                   if p == "pallas_call")
    assert calls == sorted(2 * [
        "attn_mla/kv_write/pallas/_kv_write_kernel",
        "attn_mla/mla_core/pallas/_rpa_kernel_latent"] + [
        "moe/moe_experts/pallas/_moe_experts_kernel"])


def test_step_counts_are_what_the_layers_work_on(model):
    cfg = model[0]                       # 2 expert layers, 2 experts a token
    got = deepseek_v2.step_counts(cfg, np.array([16, 50, 0, 21, 7]),
                                  np.array([16, 1, 0, 16, 0]))
    assert got == {"moe_pairs": 33 * 2 * 2, "latent_kv_tokens": 16 + 50 + 21,
                   "latent_qk_pairs": 16 * 16 + 50 + 16 * 21}


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
    return prompts, 5, greedy_of(model, prompts, 5)


def test_the_engine_serves_it_and_counts_what_the_model_counts(
        model, workload):
    prompts, n_new, expect = workload
    serving.reset_stats()
    eng = engine(model)                  # five requests on three slots
    assert not eng._model.recurrent_state and eng._state_bytes == 0
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect
    assert sorted(eng._step_fns) == [1, 16]
    stats = serving.serving_stats()
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    cfg = eng.cfg
    assert stats["moe_pairs"] == fed * cfg.num_experts_per_tok \
        * cfg.num_moe_layers
    assert fed <= stats["latent_kv_tokens"] <= stats["latent_qk_pairs"]
    # from the device: every step hit at least the experts one token takes
    # in each layer, and no expert got more rows than the step fed tokens
    assert stats["steps"] * cfg.num_moe_layers * cfg.num_experts_per_tok \
        <= stats["experts_hit"] \
        <= stats["steps"] * cfg.num_moe_layers * cfg.n_routed_experts
    assert 1 <= stats["expert_rows_max"] <= eng.scheduler.step_tokens
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference.served_checks(model[2], eng, model[1], served)
    assert verdict["logits_rel_err"] < 1e-5
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["route_flip_share"] == 0.0        # float32 on both sides
    assert verdict["replayed_prompt"] == 90      # the request of most tokens
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_the_counts_are_on_the_engine_step_span(model):
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_tpu_trace": True})
    trace.clear()
    try:
        eng = engine(model)
        eng.add_request(prompts_of(30, seed=8)[0], 2)
        drain(eng)
        calls = [e for e in trace.events()
                 if e["name"] == "serve/engine_step"]
    finally:
        paddle.set_flags({"FLAGS_tpu_trace": False})
        trace.clear()
    # what the host counted is on the span of the call that dispatched the
    # step, what the device counted on the next one, which fetched it
    steps = [e for e in calls if "fed_tokens" in e]
    fetched = [e for e in calls if "experts_hit" in e]
    assert len(steps) == len(fetched) == 3
    assert [calls.index(e) for e in fetched] == \
        [calls.index(e) + 1 for e in steps]
    first, last = steps[0], steps[-1]
    assert "experts_hit" not in first and "fed_tokens" not in fetched[-1]
    assert first["moe_pairs"] == 16 * 2 * 2
    assert first["latent_kv_tokens"] == first["kv_tokens"] == 16
    assert first["latent_qk_pairs"] == first["qk_pairs"] == 256
    # 16 tokens, 2 experts each, in each of 2 layers of 8 experts
    assert 2 * 2 <= fetched[0]["experts_hit"] <= 2 * 8
    assert 16 * 2 / 8 <= fetched[0]["expert_rows_max"] <= 16
    assert last["bucket"] == 1 and fetched[-1]["experts_hit"] == 2 * 2 \
        and fetched[-1]["expert_rows_max"] == 1


def test_chunk_4_on_a_flat_budget_serves_the_same_streams(model, workload):
    prompts, n_new, expect = workload
    eng = with_budget(engine(model, chunk=4), 9)
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert eng._positions(4) == 9
    assert [eng.output_of(r) for r in rids] == expect


def test_the_pallas_kernels_serve_the_same_streams(model):
    """The latent walk, the latent's write and the grouped experts under the
    interpreter, at pages of 128: a request of two pages beside a short one,
    a decode row inside the chunk bucket."""
    cfg, params, fields = model
    prompts = prompts_of(150, 20, seed=9)
    pallas_ops._INTERPRET = True
    try:
        eng = serving.LLMEngine(cfg, params, max_running=2, chunk=16,
                                max_model_len=256)
        assert pallas_ops.ragged_attention_available(
            None, eng._pools["latent"].shape)
        assert pallas_ops.moe_experts_available(
            (128, cfg.hidden_size), params["moe"]["w_gate"].shape)
        rids = [eng.add_request(p, 3) for p in prompts]
        drain(eng)
        served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
        old, reference.PAD_TO = reference.PAD_TO, 256
        try:
            verdict = reference.served_checks(fields, eng, params, served)
        finally:
            reference.PAD_TO = old
    finally:
        pallas_ops._INTERPRET = False
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["logits_rel_err"] < 1e-5


def test_preemption_replays_the_same_streams(model):
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


def test_the_prefix_cache_serves_latent_pages(model):
    """Latent pages are per token and position, like K/V pages: a prompt
    that shares two pages with an earlier one skips them, forks the page it
    diverges in, and streams what it streams without the cache."""
    base = prompts_of(40, seed=10)[0]
    prompts = [base, base[:36] + [7, 8, 9], base[:33]]
    expect = greedy_of(model, prompts, 4)
    serving.reset_stats()
    eng = engine(model, prefix_cache=True)
    got = []
    for p in prompts:                        # one after the other: hits
        rid = eng.add_request(p, 4)
        drain(eng)
        got.append(eng.output_of(rid))
    assert got == expect
    assert serving.serving_stats()["prefix_hit_tokens"] >= 2 * 2 * PAGE
    assert eng.kv.audit()["ok"]
