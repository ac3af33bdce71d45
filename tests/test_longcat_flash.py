"""``models/longcat_flash.py`` (two latent-attention sublayers and two dense
FFNs a layer beside one shortcut-connected expert layer, zero-compute experts,
a query latent), the shared ``models/mla.py`` and what ``models/experts.py``
and ``ops/pallas_ops.py`` gained for it, against the float32 reference
``benchmark/reference_longcat_flash.py`` at a debug width on the CPU.

Tolerances.  Everything here is float32 against float32 with "highest"
matmuls (conftest): 2e-5 absolute on logits of magnitude 1, the other
models' limit, is rounding in another order of summation (absorbed against
materialised, sorted groups against a loop, the width's blocks against one
product).  A departure from the model must land beyond 1e-3."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_longcat_flash as reference
from paddle_tpu import serving
from paddle_tpu.models import deepseek_v2, experts, longcat_flash, mla
from paddle_tpu.ops import pallas_ops
from paddle_tpu.testing import chaos
import test_deepseek_v2
from test_deepseek_v2 import fields_of
from test_jamba import close, drain, prompts_of
from test_spans import scopes_of

PAGE = 16


@pytest.fixture(scope="module", autouse=True)
def _short_padding():
    old, reference.PAD_TO = reference.PAD_TO, 128
    yield
    reference.PAD_TO = old


@pytest.fixture
def interpret():
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = False


@pytest.fixture(scope="module")
def model():
    """The debug preset with a router bias that is not zero, so that the
    choice by ``p + b`` is not the choice by ``p``."""
    cfg = longcat_flash.preset("longcat-flash-debug", dtype=jnp.float32)
    params = longcat_flash.init_params(cfg, jax.random.PRNGKey(0))
    bias = params["layers"]["router_bias"]
    assert not np.asarray(bias).any()            # seeded: the source's zeros
    params["layers"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(9), bias.shape, jnp.float32)
    return cfg, params, fields_of(cfg)


def ref_logits(model, rows):
    _, params, fields = model
    return reference.logits(fields, params, rows)


# -- sizes --------------------------------------------------------------------

CELL = dict(num_layers=4, n_routed_experts=16, experts_held=tuple(range(16)),
            n_routed_experts_published=512, vocab_size=16384,
            vocab_size_published=131072)


@pytest.mark.parametrize("name, overrides, want", [
    ("longcat-flash-debug", {}, None),
    ("longcat-flash-chat", {}, 560_664_980_480),
    ("longcat-flash-chat", CELL, 5_172_749_312)])
def test_param_count_equals_the_tree(name, overrides, want):
    """From shapes, no arrays: the published model's 560.7 B and the cell's
    5,173 M (4 layers, 16 of 512 experts, an eighth of the vocabulary)."""
    cfg = longcat_flash.preset(name, **overrides)
    params = jax.eval_shape(functools.partial(longcat_flash.init_params, cfg),
                            jax.random.PRNGKey(0))
    count = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert count == longcat_flash.param_count(cfg)
    assert want is None or count == want


def test_the_sublayers_and_the_expert_have_the_issues_sizes():
    cfg = longcat_flash.preset("longcat-flash-chat", **CELL)
    shapes = jax.eval_shape(functools.partial(longcat_flash.init_params, cfg),
                            jax.random.PRNGKey(0))["layers"]
    sizes = lambda tree, skip=(): sum(                    # noqa: E731
        int(np.prod(a.shape[2:])) for n, a in tree.items() if n not in skip)
    assert sizes(shapes["attn"], ("ln",)) == 90_572_800
    assert sizes(shapes["mlp"], ("ln",)) == 226_492_416
    assert shapes["router"].shape == (4, 6144, 768)
    assert sizes(shapes["experts"]) == 37_748_736
    assert shapes["experts"]["w_down"].shape == (4, 16, 2048, 6144)


def test_the_cache_is_two_latents_a_token_and_layer():
    cfg = longcat_flash.preset("longcat-flash-chat", **CELL)
    assert longcat_flash.cache_bytes(cfg) == {
        "per_token": 10_240, "scales_per_page": 0, "per_slot": 0}
    assert serving.kv_bytes_per_token(cfg) == 10_240
    cache = jax.eval_shape(lambda: longcat_flash.init_cache(
        cfg, 64, 2561, 128, jnp.bfloat16))
    assert {k: v.shape for k, v in cache.items()} \
        == {"latent": (8, 1, 2561, 128, 640)}
    assert cfg.softmax_scale == 192 ** -0.5
    assert (mla.q_scale(cfg), mla.kv_scale(cfg)) == (2.0, 12 ** 0.5)


def test_a_config_states_its_share_or_is_refused():
    with pytest.raises(ValueError, match="says which"):
        longcat_flash.preset("longcat-flash-chat", n_routed_experts=16,
                             n_routed_experts_published=512)
    with pytest.raises(ValueError, match="experts_held names"):
        longcat_flash.preset("longcat-flash-chat", n_routed_experts=16,
                             experts_held=(0, 1))
    with pytest.raises(ValueError, match="query latent"):
        longcat_flash.preset("longcat-flash-chat", q_lora_rank=None)
    with pytest.raises(ValueError, match="holds? no query latent"):
        deepseek_v2.preset("deepseek-v2-lite", q_lora_rank=1536)
    fields = dict(fields_of(longcat_flash.preset("longcat-flash-chat")),
                  n_routed_experts=16, experts_held=list(range(16)),
                  vocab_size=16384, num_layers=4, dtype="bfloat16",
                  published={"n_routed_experts": 512, "vocab_size": 131072,
                             "num_layers": 28}, other_key=1)
    del fields["n_routed_experts_published"], fields["vocab_size_published"]
    assert longcat_flash.config_from_fields(fields) \
        == longcat_flash.preset("longcat-flash-chat", **CELL)


# -- the whole forward ----------------------------------------------------------

@pytest.fixture(scope="module")
def whole(model):
    cfg, params, _ = model
    ids = jnp.asarray(prompts_of(40, 40, seed=1), jnp.int32)
    return ids, jax.jit(functools.partial(longcat_flash.forward_pure, cfg))(
        params, ids)


def test_forward_pure_equals_the_reference_in_float32(model, whole):
    cfg, params, fields = model
    ids, got = whole
    want = reference.forward(fields, params, ids)
    assert got.shape == (2, 40, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(np.abs(got - want).max()) < 2e-5


def test_absorbed_equals_materialised_with_a_query_latent_and_both_scales(
        model, whole):
    """The engine's step on whole sequences as one chunk (the absorbed form
    over the paged latents, ``sqrt(D / r)`` on the absorbed query and on the
    output) against ``forward_pure`` (materialised heads)."""
    cfg, params, _ = model
    assert mla.q_scale(cfg) == 2 ** 0.5 and mla.kv_scale(cfg) == 1.0
    # the debug preset's latent is as wide as its hidden size: a model whose
    # latent is narrower scales it, as the published one does
    ids, _ = whole
    B, S = ids.shape
    for c, p in ((cfg, params), _narrow_latent(cfg)):
        want = jax.jit(functools.partial(longcat_flash.forward_pure, c))(
            p, ids)
        cache = longcat_flash.init_cache(c, B, 1 + B * 3, PAGE, jnp.float32)
        tbl = 1 + jnp.arange(B * 3, dtype=jnp.int32).reshape(B, 3)
        lens = jnp.full((B,), S, jnp.int32)
        got, cache = jax.jit(functools.partial(
            longcat_flash.forward_paged, c))(p, ids, cache, tbl, lens, lens)
        assert float(jnp.abs(got - want).max()) < 2e-5
        # what was cached, in each of the 2 L sublayers: the normed latent
        # WITHOUT its scale and the rotated key, zeros in the padding
        page = np.asarray(cache["latent"][:, 0, 1])
        assert page.shape[0] == 2 * c.num_layers
        used = c.kv_lora_rank + c.qk_rope_head_dim
        assert np.abs(page[..., :used]).min() > 0 \
            and not page[..., used:].any()
        rms = np.sqrt((page[..., :c.kv_lora_rank] ** 2).mean(-1))
        np.testing.assert_allclose(rms, 1.0, rtol=1e-3)   # norm weights 1


def _narrow_latent(cfg):
    narrow = dataclasses.replace(cfg, kv_lora_rank=32)
    assert mla.kv_scale(narrow) == 2.0
    return narrow, longcat_flash.init_params(narrow, jax.random.PRNGKey(3))


@pytest.mark.parametrize("departure", [
    "no-zero-experts", "shortcut-after-the-first-sublayer", "no-mla-scales",
    "one-expert-fewer", "no-bias"])
def test_a_departure_from_the_model_is_further_off_than_the_tolerance(
        model, whole, departure, monkeypatch, request):
    """What the comparison above can tell apart: each of these is a model
    one could have built by mistake, and none passes for the other."""
    cfg, params, fields = model
    ids, got = whole
    # the reference's pieces are jitted: one traced before the patch would
    # be found again, and one traced under it must not outlive the test
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    if departure == "no-zero-experts":
        # the identity experts' sum left out: they are chosen and add nothing
        real = reference._moe

        def moe(f, layers, h, l, length):
            y, flips = real(f, layers, h, l, length)
            E = reference.scored_experts(f)
            router = reference._at(layers["router"], l)
            p, chosen = reference._choose(
                f, h @ router, reference._at(layers["router_bias"], l))
            w = jnp.take_along_axis(p, chosen, -1) \
                * f["routed_scaling_factor"]
            return y - jnp.sum(jnp.where(chosen >= E, w, 0.0),
                               -1)[:, None] * h, flips
        monkeypatch.setattr(reference, "_moe", moe)
    elif departure == "shortcut-after-the-first-sublayer":
        # s added before the second sublayer instead of at the layer's end
        real_first = reference._first_half_jit.__wrapped__

        def first(frozen, layers, x, l, length):
            x, s, flips = real_first(frozen, layers, x, l, length)
            return x + s, jnp.zeros_like(s), flips
        monkeypatch.setattr(reference, "_first_half_jit", first)
    elif departure == "no-mla-scales":
        fields = dict(fields, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    elif departure == "one-expert-fewer":
        fields = dict(fields, moe_topk=cfg.moe_topk - 1)
    else:
        params = dict(params, layers=dict(
            params["layers"], router_bias=jnp.zeros_like(
                params["layers"]["router_bias"])))
    want = reference.forward(fields, params, ids)
    assert float(np.abs(got - want).max()) > 1e-3   # fifty tolerances


# -- the router and the expert layer ------------------------------------------

def test_a_bias_moves_the_choice_and_not_the_weight():
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    w = jnp.asarray([[0.0, 2.0, 0.0, 2.0], [0.3, 0.2, 0.1, 0.0]], jnp.float32)
    p = np.exp(np.asarray(w)) / np.exp(np.asarray(w)).sum(-1, keepdims=True)
    plain_w, plain = experts.route_top_k(x, w, 2)
    assert plain.tolist() == [[1, 3], [0, 1]]            # ties: lower index
    zero_w, zero = experts.route_top_k(x, w, 2, bias=jnp.zeros((4,)))
    assert zero.tolist() == plain.tolist()
    np.testing.assert_array_equal(zero_w, plain_w)
    # expert 2 is lifted over everyone, expert 0 level with the leaders of
    # token 0 (p 0.4404 against 0.0596: a bias of their difference ties them,
    # and the tie goes to the lower index)
    bias = jnp.asarray([p[0, 1] - p[0, 0], 0.0, 1.0, 0.0], jnp.float32)
    weights, chosen = experts.route_top_k(x, w, 2, bias=bias)
    assert chosen.tolist() == [[2, 0], [2, 0]]
    np.testing.assert_allclose(weights, np.take_along_axis(
        p, np.asarray(chosen), -1), rtol=1e-6)           # p, not p + b


def _stacks(seed, E=6, D=16, F=24):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
            for s in ((E, D, F), (E, D, F), (E, F, D))]


def _by_token(x, weights, chosen, stacks, ids, num_experts):
    """The definition: a loop over tokens and their experts, in numpy; an id
    past ``num_experts`` is the identity."""
    x, weights, chosen = (np.asarray(a, np.float64)
                          for a in (x, weights, chosen))
    w_gate, w_up, w_down = (np.asarray(w, np.float64) for w in stacks)
    y = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for k in range(chosen.shape[1]):
            e = int(chosen[t, k])
            if e >= num_experts:
                y[t] += weights[t, k] * x[t]
            elif e in ids:
                i = ids.index(e)
                a, b = x[t] @ w_gate[i], x[t] @ w_up[i]
                y[t] += weights[t, k] * ((a / (1 + np.exp(-a)) * b)
                                         @ w_down[i])
    return y


@pytest.mark.parametrize("case", ["every-expert-held", "a-share-short-path",
                                  "a-share-over-its-cap", "padding-tokens"])
def test_a_zero_compute_choice_adds_the_weighted_token(case):
    """``routed_experts`` with zero-compute ids against the loop: with every
    expert held, as a chip's share on the short path (the first ``cap`` pairs
    of the sorted order) and with more pairs on the held experts than ``cap``
    (all pairs, no drop), and with padding tokens."""
    T, K, E, Z = 160, 3, 24, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, 16)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.05, 0.5, (T, K)), jnp.float32)
    chosen = np.stack([rng.permutation(E + Z)[:K] for _ in range(T)])
    held, live = None, None
    if case != "every-expert-held":
        held = (3, 11, 20)
        assert experts._held_rows_cap(T * K, 3, E + Z) == 256 < T * K
    if case == "a-share-over-its-cap":
        chosen[:110] = [11, 3, 20]           # 330 pairs on the three held
    if case == "padding-tokens":
        live = jnp.arange(T) < 100
    ids = list(range(E)) if held is None else list(held)
    stacks = [w[np.asarray(ids)] for w in _stacks(4, E=E)]
    got, rows = jax.jit(functools.partial(
        experts.routed_experts, num_experts=E, held=held, zero_experts=Z))(
        x, weights, jnp.asarray(chosen, jnp.int32), *stacks, live=live)
    counted = chosen if live is None else chosen[:100]
    assert np.array_equal(rows, [np.sum(counted == e) for e in ids])
    if case == "a-share-over-its-cap":
        assert int(rows.sum()) > 256
    elif held is not None:
        assert 0 < int(rows.sum()) <= 256
    if live is not None:
        weights = weights * live[:, None]
    np.testing.assert_allclose(
        got, _by_token(x, weights, chosen, stacks, ids, E), atol=2e-5)
    assert (chosen >= E).any()


def test_the_shares_of_a_layer_add_up_to_the_layer(model):
    """The guide's share test: the debug preset's 8 routed experts as 4
    shares of 2, each device routing over all 12 outputs.  Each share's
    routed part summed over the shares, and the zero-compute part, which
    every device computes alike, counted once, equal the reference's uncut
    expert layer; and the reference given a share computes that share."""
    cfg, params, fields = model
    lw = params["layers"]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(20, 128)),
                    jnp.float32)
    layer = lambda c, stacks: longcat_flash._expert_layer(   # noqa: E731
        c, lw["router"][1], lw["router_bias"][1], stacks, x, 1, None)
    whole, rows, zero_pairs = layer(cfg, lw["experts"])
    want = reference.expert_layer(fields, params, x, 1)
    assert float(np.abs(whole - want).max()) < 2e-5
    # the zero-compute part: what a device that holds NO expert computes
    # (the pairs of every routed expert fall to no one)
    none = dataclasses.replace(cfg, n_routed_experts=0, experts_held=(),
                               n_routed_experts_published=8)
    nothing = {n: w[:, :0] for n, w in lw["experts"].items()}
    zero_part, no_rows, same = layer(none, nothing)
    assert no_rows.shape == (0,) and int(same) == int(zero_pairs) > 0
    parts, seen = [], 0
    for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
        share = dataclasses.replace(cfg, n_routed_experts=2,
                                    experts_held=held,
                                    n_routed_experts_published=8)
        mine = {n: w[:, np.asarray(held)] for n, w in lw["experts"].items()}
        assert longcat_flash.param_count(share) == sum(
            a.size for a in jax.tree_util.tree_leaves(jax.eval_shape(
                functools.partial(longcat_flash.init_params, share),
                jax.random.PRNGKey(0))))
        part, got, zeros = layer(share, mine)
        assert np.array_equal(got, rows[np.asarray(held)])
        assert int(zeros) == int(zero_pairs)
        parts.append(part - zero_part)               # its routed part alone
        seen += int(got.sum())
        theirs = reference.expert_layer(
            dict(fields, n_routed_experts=2, experts_held=held,
                 published={"n_routed_experts": 8}),
            dict(params, layers=dict(lw, experts=mine)), x, 1)
        assert float(np.abs(part - theirs).max()) < 2e-5
    # every pair once: on a held expert of some share, or zero-compute
    assert seen + int(zero_pairs) == 20 * cfg.moe_topk
    assert float(np.abs(sum(parts) + zero_part - want).max()) < 2e-5


# -- the expert kernel's width in blocks ----------------------------------------

@pytest.mark.parametrize("blocks", [1, 2, 3])
def test_the_width_tiled_expert_kernel_equals_the_ragged_products(
        blocks, interpret, monkeypatch):
    """``_moe_experts_call`` under the interpreter with the VMEM budget
    shrunk until an expert of 128 x ``blocks`` lanes takes ``blocks`` turns:
    empty experts (the first, one in the middle, the last), a group that
    crosses a row tile, rows of no group at the end."""
    D, F, E, L = 128, 128 * blocks, 6, 2
    fixed = 128 * D * (2 * 4 + 2 * 4 + 4)
    per_lane = 2 * 3 * D * 4 + 3 * 128 * 4
    monkeypatch.setattr(pallas_ops, "_MOE_BUDGET", fixed + per_lane * 128)
    assert pallas_ops._moe_width_block(D, F, 4) == 128
    monkeypatch.setattr(pallas_ops, "_MOE_BUDGET", fixed + per_lane * 127)
    assert pallas_ops._moe_width_block(D, F, 4) is None
    assert not pallas_ops.moe_experts_available((256, D), (L, E, D, F),
                                                jnp.float32)
    if blocks > 1:
        monkeypatch.setattr(pallas_ops, "_MOE_BUDGET",
                            fixed + per_lane * 128)
    else:
        monkeypatch.undo()
        pallas_ops._INTERPRET = True
    rng = np.random.default_rng(blocks)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(L, E, D, F)) * 0.1,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(L, E, F, D)) * 0.1, jnp.float32)
    sizes = jnp.asarray([0, 100, 90, 0, 41, 0], jnp.int32)   # 231 of 256
    xs = jnp.asarray(rng.normal(size=(256, D)), jnp.float32)
    layer = jnp.int32(1)
    got = jax.jit(pallas_ops._moe_experts_call)(xs, sizes, w_gate, w_up,
                                                w_down, layer)
    want = pallas_ops._moe_experts_jnp(xs, sizes, w_gate, w_up, w_down, layer)
    np.testing.assert_allclose(got[:231], want[:231], atol=2e-5)
    assert not np.asarray(got[231:]).any()       # the tile was visited


# -- the latent walk at many heads ------------------------------------------------

def test_the_latent_walk_in_query_blocks_equals_its_jnp_body(interpret,
                                                             monkeypatch):
    """``latent_paged_attention`` at 32 heads x 16 tokens = 512 query rows,
    two blocks of ``_RPA_Q_BLOCK``: a chunk that fills both, one that fills
    the first alone, a decode row (the short path), an idle row."""
    rep, Tc, lanes, v_lanes, page, R, L = 32, 16, 256, 128, 128, 4, 2
    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.normal(size=(L, 1, 9, page, lanes)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(R, 1, Tc * rep, lanes)), jnp.float32)
    tbl = jnp.asarray(1 + np.arange(R * 2).reshape(R, 2), jnp.int32)
    lens = jnp.asarray([200, 135, 77, 0], jnp.int32)
    qlens = jnp.asarray([16, 5, 1, 0], jnp.int32)
    kw = dict(rep=rep, v_lanes=v_lanes, scale=0.11, layer=jnp.int32(1))
    seen = []
    real = pallas_ops._rpa_kernel_latent
    monkeypatch.setattr(
        pallas_ops, "_rpa_kernel_latent",
        functools.wraps(real)(lambda *a, **k: (seen.append(k["qblk"]),
                                               real(*a, **k))[1]))
    got = pallas_ops.latent_paged_attention(q, pages, tbl, lens, qlens, **kw)
    assert seen == [256]
    want = pallas_ops._ragged_attention_jnp(
        q, pages, pages[..., :v_lanes], tbl, lens, qlens, rep, layer=1,
        scale=0.11)
    live = np.arange(Tc * rep)[None, :] < np.asarray(qlens)[:, None] * rep
    np.testing.assert_allclose(np.asarray(got)[:, 0][live],
                               np.asarray(want)[:, 0][live], atol=2e-5)
    assert not np.asarray(got)[:, 0][~live].any()
    # 16 heads x 16 tokens stay one block, on the limit they had
    seen.clear()
    pallas_ops.latent_paged_attention(q[:, :, :256], pages, tbl, lens, qlens,
                                      **dict(kw, rep=16))
    assert seen == [None]


# -- the ragged step, driven directly -------------------------------------------

class Rows(test_deepseek_v2.Rows):
    """``test_deepseek_v2.Rows`` on this model: ``forward_paged`` on a cache
    of ``R`` slots, fed by hand, a call of ``feed`` an engine step."""
    module = longcat_flash


@pytest.mark.parametrize("chunk, flat", [(16, None), (4, 7)])
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


def test_the_layers_scopes_and_kernels_are_in_the_step(model, interpret):
    cfg, params, _ = model
    R = 2
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = jax.eval_shape(lambda: longcat_flash.init_cache(
        cfg, R, 5, 128, jnp.float32))
    found = scopes_of(functools.partial(longcat_flash.forward_paged, cfg),
                      params, i32(R, 16), cache, i32(R, 2), i32(R), i32(R))
    paths = {s for _, s in found}
    for scope in ("embed", "layers/attn_mla", "attn_mla/mla_core",
                  "attn_mla/kv_write", "layers/mlp", "layers/moe",
                  "moe/moe_router", "moe/moe_experts", "moe/moe_zero",
                  "lm_head"):
        assert any(p.endswith(scope) for p in paths), scope
    assert any(p == "top_k" and s.endswith("moe/moe_router")
               for p, s in found)
    # both sublayers' two kernels, one expert layer's one, a layer
    calls = sorted(s.split("layers/")[-1] for p, s in found
                   if p == "pallas_call")
    assert calls == sorted(2 * [
        "attn_mla/kv_write/pallas/_kv_write_kernel",
        "attn_mla/mla_core/pallas/_rpa_kernel_latent"] + [
        "moe/moe_experts/pallas/_moe_experts_kernel"])
    # the two dense FFNs: six products under mlp; nothing of the layer's
    # work outside a scope but the scan's own
    dots = [s for p, s in found if p == "dot_general"
            and s.endswith("layers/mlp")]
    assert len(dots) == 6


def test_the_deepseek_v2_step_lowers_to_the_kernels_it_had(interpret):
    """``models/mla.py`` and the new arguments of ``models/experts.py`` leave
    DeepSeek-V2's step what it was: the same three kernels, the one-block
    expert kernel, the walk in one block on the default limit, and no
    conditional in the expert layer (the short path is a share's)."""
    cfg = deepseek_v2.preset("deepseek-v2-debug", dtype=jnp.float32)
    params = jax.eval_shape(functools.partial(deepseek_v2.init_params, cfg),
                            jax.random.PRNGKey(0))
    R = 2
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    cache = jax.eval_shape(lambda: deepseek_v2.init_cache(
        cfg, R, 5, 128, jnp.float32))
    found = scopes_of(functools.partial(deepseek_v2.forward_paged, cfg),
                      params, i32(R, 16), cache, i32(R, 2), i32(R), i32(R))
    kernels = [s.rsplit("/", 1)[-1] for p, s in found if p == "pallas_call"]
    # the dense layer's two and, in the scan's body, an expert layer's three
    assert sorted(kernels) == sorted(2 * [
        "_kv_write_kernel", "_rpa_kernel_latent"] + ["_moe_experts_kernel"])
    assert not [s for p, s in found if p == "cond" and s.endswith("moe")]


def test_step_counts_are_what_the_layers_work_on(model):
    cfg = model[0]                       # 2 layers, 3 experts a token
    got = longcat_flash.step_counts(cfg, np.array([16, 50, 0, 21, 7]),
                                    np.array([16, 1, 0, 16, 0]))
    assert got == {"moe_pairs": 33 * 3 * 2, "latent_kv_tokens": 16 + 50 + 21,
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


def test_the_engine_serves_it_and_counts_what_the_model_counts(model):
    prompts, n_new = prompts_of(5, 37, 16, 90, 23, seed=6), 4
    expect = greedy_of(model, prompts, n_new)
    serving.reset_stats()
    eng = engine(model)                  # five requests on three slots
    assert not eng._model.recurrent_state and eng._state_bytes == 0
    assert eng._pools["latent"].shape[0] == 2 * eng.cfg.num_layers
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect
    assert sorted(eng._step_fns) == [1, 16]
    stats = serving.serving_stats()
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    cfg = eng.cfg
    assert stats["moe_pairs"] == fed * cfg.moe_topk * cfg.num_layers
    assert fed <= stats["latent_kv_tokens"] <= stats["latent_qk_pairs"]
    # from the device: a pair is on a held expert (all are held) or chose a
    # zero-compute one
    assert stats["held_rows"] + stats["zero_pairs"] == stats["moe_pairs"]
    assert 0 < stats["zero_pairs"] < stats["held_rows"]
    assert stats["steps"] * cfg.num_layers <= stats["experts_hit"] \
        <= stats["steps"] * cfg.num_layers * cfg.n_routed_experts
    assert 1 <= stats["expert_rows_max"] <= eng.scheduler.step_tokens
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference.served_checks(model[2], eng, model[1], served)
    assert verdict["logits_rel_err"] < 1e-5
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["route_flip_share"] == 0.0        # float32 on both sides
    assert verdict["replayed_prompt"] == 90      # the request of most tokens
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_the_pallas_kernels_serve_the_same_streams(model, interpret):
    """The latent walk, the latent's write and the grouped experts under the
    interpreter, at pages of 128: a request of two pages beside a short one,
    a decode row inside the chunk bucket."""
    cfg, params, fields = model
    prompts = prompts_of(150, 20, seed=9)
    eng = serving.LLMEngine(cfg, params, max_running=2, chunk=16,
                            max_model_len=256)
    assert pallas_ops.ragged_attention_available(
        None, eng._pools["latent"].shape)
    assert pallas_ops.moe_experts_available(
        (128, cfg.hidden_size), params["layers"]["experts"]["w_gate"].shape)
    rids = [eng.add_request(p, 3) for p in prompts]
    drain(eng)
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    old, reference.PAD_TO = reference.PAD_TO, 256
    try:
        verdict = reference.served_checks(fields, eng, params, served)
    finally:
        reference.PAD_TO = old
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
