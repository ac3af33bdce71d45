"""``models/mellum.py`` (sequential blocks of window layers with plain rotary
and full layers with YaRN beside softmax-routed experts, renormalised), the
rotary module it shares with ``models/deepseek_v2.py`` and the period scan
it shares with ``models/cohere2_moe.py``, against the float32 reference
``benchmark/reference_mellum.py`` at a debug width on the CPU: the
full-sequence forward, the engine's ragged step on its rings and its full
pool past the debug preset's stretched YaRN range, and ``LLMEngine`` serving
it — slot reuse, preemption-replay, a rebuilt engine — with what the engine
refuses for such a model and what it counts for it.

Tolerances.  Everything here is float32 against float32 with "highest"
matmuls (conftest): ``forward_pure`` against the reference within 1e-6
absolute on logits of magnitude 1 (the reference's rotary is computed in
float64 and cast, the program's in float32: 7.0e-7 at the debug preset's
110 positions), the engine's step within 2e-5 (the other models' limit:
rounding in another order of summation, the sorted groups against a loop).
Each variant of the model that the chip's check must tell apart lands
beyond 1e-3.  The YaRN frequencies against HF's rule transcribed in float64
within a float32 rounding (rtol 1e-7)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_mellum as reference
from paddle_tpu import serving
from paddle_tpu.models import deepseek_v2, experts, llama, mellum, rings, \
    rotary
from paddle_tpu.ops import pallas_ops
from paddle_tpu.testing import chaos
import test_jamba
from test_jamba import close, drain, prompts_of
from test_spans import scopes_of

PAGE = 16      # the debug window is 20: a ring of 4 pages, 64 positions
FULL = "full_attention"


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
    cfg = mellum.preset("mellum-debug", dtype=jnp.float32)
    params = mellum.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, fields_of(cfg)


def ref_logits(model, rows):
    _, params, fields = model
    return reference.logits(fields, params, rows)


# -- sizes --------------------------------------------------------------------

@pytest.mark.parametrize("name, overrides, want", [
    ("mellum-debug", {}, None),
    ("mellum2-12b-a2.5b", {}, 12_149_915_904),
    ("mellum2-12b-a2.5b", {"num_hidden_layers": 12}, 5_465_956_608)])
def test_param_count_equals_the_tree(name, overrides, want):
    cfg = mellum.preset(name, **overrides)
    params = jax.eval_shape(functools.partial(mellum.init_params, cfg),
                            jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == mellum.param_count(cfg)
    assert want is None or n == want


def test_the_presets_have_the_layers_they_say():
    debug, full = (mellum.preset(n)
                   for n in ("mellum-debug", "mellum2-12b-a2.5b"))
    assert debug.layer_kinds() == 2 * (3 * ["window"] + ["global"])
    assert (full.num_window_layers, full.num_global_layers,
            full.period) == (21, 7, 4)
    cut = mellum.preset("mellum2-12b-a2.5b", num_hidden_layers=12)
    assert (cut.num_window_layers, cut.num_global_layers) == (9, 3)
    assert rings.ring_pages(full.sliding_window, 128) == 10 \
        and rings.ring_pages(debug.sliding_window, PAGE) == 4
    assert debug.sliding_window < 2 * PAGE
    assert debug.stretched_from == 64 and full.stretched_from == 8192


@pytest.mark.parametrize("bad", [
    dict(use_qk_norm=True), dict(attention_bias=True),
    dict(num_shared_experts=1), dict(partial_rotary_factor=0.5),
    dict(mlp_layer_types=("dense",) + ("sparse",) * 27),
    dict(tie_word_embeddings=True), dict(norm_topk_prob=False)])
def test_a_block_it_was_not_written_for_is_refused(bad):
    with pytest.raises(ValueError, match="written for"):
        mellum.preset("mellum2-12b-a2.5b", **bad)


def test_layer_types_are_read_as_given_and_must_be_whole_periods():
    W, F = "sliding_attention", FULL
    cfg = mellum.preset("mellum-debug", num_hidden_layers=6,
                        layer_types=(W, W, F) * 2)
    assert cfg.period == 3 and cfg.layer_kinds() == ["window"] * 2 \
        + ["global"] + ["window"] * 2 + ["global"]
    for types, L in (((F, W, W, W) * 7, 28), ((W, W, W, F) * 7, 6),
                     ((W,) * 28, 28)):
        with pytest.raises(ValueError, match="whole periods"):
            mellum.preset("mellum2-12b-a2.5b", num_hidden_layers=L,
                          layer_types=types)


def test_the_cache_is_a_full_pool_and_rings_of_the_window():
    cfg = mellum.preset("mellum2-12b-a2.5b", num_hidden_layers=12)
    layout = mellum.cache_bytes(cfg)
    token = 2 * 4 * 128 * 2                  # K and V, a token and layer
    assert layout == {"per_token": 3 * token, "scales_per_page": 0,
                      "per_slot": 10 * 9 * 128 * token,
                      "fixed": 9 * 128 * token}
    cache = jax.eval_shape(lambda: mellum.init_cache(
        cfg, 24, 24 * 168 + 1, 128, jnp.bfloat16))
    assert cache["k_pages"].shape == (3, 4, 4033, 128, 128)
    assert cache["kw_pages"].shape == (9, 4, 1 + 24 * 10, 128, 128)


# -- YaRN, written once -------------------------------------------------------------

MELLUM_YARN = mellum.PUBLISHED_ROPE[FULL]
DEEPSEEK_YARN = deepseek_v2.YARN_LITE


@pytest.mark.parametrize("theta, width, rope", [
    (500000, 128, MELLUM_YARN),
    (10000, 64, DEEPSEEK_YARN),
    (10000, 32, mellum.PRESETS["mellum-debug"]["rope_parameters"][FULL]),
    (10000, 16, dict(DEEPSEEK_YARN, original_max_position_embeddings=16)),
    (10000, 64, {k: v for k, v in DEEPSEEK_YARN.items()
                 if not k.startswith("mscale")})],
    ids=["mellum", "deepseek-v2-lite", "mellum-debug", "deepseek-v2-debug",
         "no-mscale"])
def test_yarn_is_hfs_rule(theta, width, rope):
    """``rotary.frequencies`` against HF's ``_compute_yarn_parameters``
    transcribed in float64 (the reference's ``yarn_parameters``), at
    Mellum's and DeepSeek-V2-Lite's parameters, the debug presets', and a
    dict with neither ``attention_factor`` nor ``mscale`` (HF's default
    factor)."""
    got, scale = rotary.frequencies(theta, width, rope)
    want, factor = reference.yarn_parameters(theta, width, rope)
    assert got.dtype == np.float32 and got.shape == (width // 2,)
    np.testing.assert_allclose(got, want, rtol=1e-7)
    assert scale == pytest.approx(factor, rel=1e-12)


def test_yarn_at_mellums_parameters():
    """The ramp covers pairs 18-35 of 64: below it the plain frequencies,
    above it a sixteenth of them; the attention factor is as given."""
    inv_freq, scale = rotary.frequencies(500000, 128, MELLUM_YARN)
    plain, _ = rotary.frequencies(500000, 128, {"rope_type": "default"})
    np.testing.assert_array_equal(inv_freq[:19], plain[:19])
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(inv_freq[19:35] < plain[19:35]) \
        and np.all(inv_freq[19:35] > plain[19:35] / 16)
    assert scale == 1.2772588722239782 == pytest.approx(
        rotary.yarn_mscale(16), rel=1e-12)
    assert deepseek_v2.rope_frequencies(deepseek_v2.preset(
        "deepseek-v2-lite"))[1] == 1.0
    with pytest.raises(ValueError, match="of type"):
        rotary.frequencies(10000, 64, {"rope_type": "longrope"})


# -- the whole sequence -----------------------------------------------------------

@pytest.fixture(scope="module")
def whole(model):
    """Two sequences 110 long, past the debug preset's stretch (64) and five
    windows, and ``forward_pure`` of them."""
    cfg, params, _ = model
    ids = jnp.asarray(prompts_of(110, 110, seed=1), jnp.int32)
    return ids, jax.jit(functools.partial(mellum.forward_pure, cfg))(
        params, ids)


def test_forward_pure_equals_the_reference_in_float32(model, whole):
    cfg, params, fields = model
    ids, got = whole
    want = reference.forward(fields, params, ids)
    assert got.shape == (2, 110, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-6


@pytest.mark.parametrize("variant", sorted(reference.VARIANTS))
def test_a_variant_of_the_model_is_further_off_than_the_tolerance(
        model, whole, variant):
    """What the chip's check has to tell apart, each a model one could have
    built by mistake: every matrix in int8, plain rotary on the full layers,
    YaRN without its attention factor, no window, top-k weights not
    renormalised, a sigmoid router, a q/k norm."""
    _, params, fields = model
    ids, got = whole
    want = reference.forward(dict(fields, **reference.VARIANTS[variant]),
                             params, ids)
    assert float(jnp.abs(got - want).max()) > 1e-3    # fifty times 2e-5


# -- the expert layer -------------------------------------------------------------

def test_the_router_takes_the_softmax_and_renormalises():
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (16, 8), jnp.float32)
    weights, chosen = experts.route_top_k(x, w, 3, score="softmax",
                                          renormalize=True)
    p = jax.nn.softmax(x @ w, axis=-1)
    want = jnp.argsort(-p, axis=-1, stable=True)[:, :3]
    assert np.array_equal(chosen, want)
    kept = jnp.take_along_axis(p, want, axis=-1)
    assert np.allclose(weights, kept / kept.sum(-1, keepdims=True),
                       atol=1e-6)
    assert np.allclose(weights.sum(-1), 1.0, atol=1e-6)


def test_the_expert_layer_equals_a_loop_over_the_experts(model):
    cfg, params, fields = model
    x = jnp.asarray(np.random.default_rng(5).normal(size=(20, 128)),
                    jnp.float32)
    lp = {n: w[1] for n, w in params["layers"].items()}
    got, rows = mellum._expert_layer(cfg, lp, params["experts"], x, 1, None)
    assert int(rows.sum()) == 20 * cfg.num_experts_per_tok
    f = reference._thawed(reference._frozen(fields))
    with jax.default_matmul_precision("highest"):
        want, _ = reference._moe(f, params, x, jnp.int32(1), 20)
    assert float(np.abs(got - want).max()) < 2e-5


# -- the ragged step, driven directly ----------------------------------------

def Rows(model, **kw):
    """``test_jamba.Rows`` on this model's step and cache."""
    return test_jamba.Rows(model, module=mellum, **kw)


@pytest.mark.parametrize("chunk", [16, 4])
def test_prefill_in_chunks_then_decode_equals_one_full_forward(model, chunk):
    """110 tokens on a ring of 64 positions, past the stretch at 64: the
    ring wraps during the prefill and again while decoding."""
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
    step(16, {0: a[77:]})                          # and a is past 64
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
        assert after[:, :, 1, :12].any(axis=-1).all()
        assert not after[:, :, 1, 12:].any()
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
    cache = mellum.init_cache(cfg, R, 1 + 3 * 8, PAGE, jnp.float32)
    tbl = jnp.asarray(1 + np.arange(R)[:, None] * 8 + np.arange(8)[None, :],
                      jnp.int32)
    tokens = np.zeros((R, Tc), np.int32)
    tokens[0], tokens[2, :9] = a[:16], b
    lens, qlens = jnp.asarray([16, 0, 9]), jnp.asarray([16, 0, 9])
    padded, _ = mellum.forward_paged(cfg, params, jnp.asarray(tokens),
                                     cache, tbl, lens, qlens)
    flat, _, counts = mellum.forward_paged(
        cfg, params, jnp.asarray(tokens), cache, tbl, lens, qlens,
        step_tokens=32, device_counts=True)
    close(np.asarray(flat[:16]), np.asarray(padded[0]))
    close(np.asarray(flat[16:25]), np.asarray(padded[2, :9]))
    hit, most, held = map(int, counts)
    assert held == 25 * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert 1 <= most <= 25 and cfg.num_hidden_layers <= hit \
        <= cfg.num_hidden_layers * cfg.num_experts


def test_the_layers_scopes_and_kernels_are_in_the_step(model, interpret):
    cfg = mellum.preset("mellum-debug", dtype=jnp.float32, head_dim=128,
                        hidden_size=256)
    params = jax.eval_shape(functools.partial(mellum.init_params, cfg),
                            jax.random.PRNGKey(0))
    R = 2
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = jax.eval_shape(lambda: mellum.init_cache(
        cfg, R, 5, 128, jnp.float32))
    found = scopes_of(functools.partial(mellum.forward_paged, cfg),
                      params, i32(R, 16), cache, i32(R, 2), i32(R), i32(R))
    paths = {s for _, s in found}
    for scope in ("embed", "rope", "layers/norm", "attn/attn_window",
                  "attn/attn_global", "attn_window/kv_write",
                  "attn_global/kv_write", "attn_window/attn_kernel",
                  "attn_global/attn_kernel", "layers/moe", "moe/moe_router",
                  "moe/moe_experts", "lm_head"):
        assert any(p.endswith(scope) for p in paths), scope
    assert not any("moe_shared" in p for p in paths)
    # the sines of both kinds' tables, and nothing else, under "rope"
    assert sorted(p for p, s in found if s.endswith("rope")
                  and p in ("sin", "cos")) == ["cos", "cos", "sin", "sin"]
    calls = sorted(s.split("layers/")[-1] for p, s in found
                   if p == "pallas_call")
    assert [c for c in calls if "attn" in c] == sorted(
        f"attn/attn_{k}/{w}" for k in ("global", "window") for w in (
            "attn_kernel/pallas/_rpa_kernel", "kv_write/pallas/_kv_write_kernel"))
    assert {c for c in calls if "moe" in c} == {
        "moe/moe_experts/pallas/_moe_experts_kernel"}
    assert not [s for p, s in found if p == "dot_general"
                and s.endswith("attn_kernel")]


def test_step_counts_are_what_a_window_layer_reads_and_the_stretch(model):
    cfg = model[0]                       # window 20, YaRN from position 64
    got = mellum.step_counts(cfg, np.array([16, 70, 0, 21, 7, 80]),
                             np.array([16, 1, 0, 16, 0, 16]))
    assert got == {"window_kv_tokens": 16 + 20 + 21 + 35,
                   "window_qk_pairs": sum(range(1, 17)) + 20
                   + sum(min(p + 1, 20) for p in range(5, 21)) + 16 * 20,
                   "stretched_tokens": 1 + 16}


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
    layout = mellum.cache_bytes(eng.cfg, 4, PAGE)
    assert stats["state_bytes"] == eng._state_bytes \
        == 3 * layout["per_slot"] + layout["fixed"] > 0
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    cfg = eng.cfg
    assert fed <= stats["window_qk_pairs"] <= 20 * fed
    assert 0 < stats["window_kv_tokens"] <= stats["window_qk_pairs"]
    # the 90-token prompt and its outputs run past position 64
    assert stats["stretched_tokens"] == 90 + 5 - 64
    assert stats["held_rows"] == fed * cfg.num_experts_per_tok \
        * cfg.num_hidden_layers
    assert 1 <= stats["expert_rows_max"] <= eng.scheduler.step_tokens
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference.served_checks(model[2], eng, model[1], served)
    assert verdict["logits_rel_err"] < 1e-5
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["route_flip_share"] == 0.0        # float32 on both sides
    assert verdict["replayed_prompt"] == 90      # the request of most tokens
    assert verdict["replayed_past_stretch"] is True
    assert verdict["tokens"] == 5 * n_new
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_the_pallas_kernels_serve_the_same_streams(interpret):
    """The windowed RPA kernel at rep 8, the write kernel and the grouped
    experts under the interpreter, at the head width they need (128) and
    pages of 128: a window of 40, so a ring of 3 pages (384 positions); the
    longer request wraps its rings, its walk starts past page 0, and it runs
    past the stretch."""
    cfg = mellum.preset("mellum-debug", dtype=jnp.float32, hidden_size=256,
                        head_dim=128, num_hidden_layers=4, sliding_window=40)
    params = mellum.init_params(cfg, jax.random.PRNGKey(1))
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
