"""``models/phi4flash.py`` (Mamba-1 and window attention interleaved, then
gated memory units and cross layers on one full layer's K/V) against the
float32 reference ``benchmark/reference_phi4flash.py``, at a debug width on
the CPU: the full-sequence forward, the engine's ragged step with its window
rings and recurrent state per slot, and ``LLMEngine`` serving it through the
model protocol — slot reuse, preemption-replay, a rebuilt engine — with what
the engine refuses for such a model and what it counts for it."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_phi4flash as reference
from paddle_tpu import serving
from paddle_tpu.models import llama, phi4flash
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import trace, xmem
from paddle_tpu.testing import chaos
import test_jamba
from test_jamba import close, drain, prompts_of
from test_spans import scopes_of

PAGE = 16      # the debug window is 20: a ring of 4 pages, 64 positions


@pytest.fixture(scope="module", autouse=True)
def _short_padding():
    """The reference pads its rows to 1,024 for the chip's sake; a test row
    is at most 128 long."""
    old, reference.PAD_TO = reference.PAD_TO, 128
    yield
    reference.PAD_TO = old


@pytest.fixture(scope="module")
def model():
    cfg = phi4flash.preset("phi4flash-debug", dtype=jnp.float32)
    params = phi4flash.init_params(cfg, jax.random.PRNGKey(0))
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
              if f.name != "dtype"}
    return cfg, params, fields


def ref_logits(model, rows):
    _, params, fields = model
    return reference.logits(fields, params, rows)


@pytest.mark.parametrize("name", ["phi4flash-debug", "phi4-mini-flash"])
def test_param_count_equals_the_tree(name):
    cfg = phi4flash.preset(name)
    params = jax.eval_shape(functools.partial(phi4flash.init_params, cfg),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == phi4flash.param_count(cfg)


def test_the_presets_have_the_layers_they_say():
    debug, full = (phi4flash.preset(n)
                   for n in ("phi4flash-debug", "phi4-mini-flash"))
    assert debug.layer_kinds() == ["mamba", "window", "mamba", "window",
                                   "mamba", "global", "gmu", "cross"]
    kinds = full.layer_kinds()
    assert [kinds.count(k) for k in ("mamba", "window", "global", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ["mamba", "global", "gmu", "cross"]
    assert phi4flash.param_count(full) == 3_852_562_944
    assert debug.sliding_window < 2 * PAGE and full.ring_pages(128) == 6


@pytest.fixture(scope="module")
def whole(model):
    """Two sequences longer than the window, and ``forward_pure`` of them."""
    cfg, params, _ = model
    ids = jnp.asarray(prompts_of(28, 28, seed=1), jnp.int32)
    return ids, jax.jit(functools.partial(phi4flash.forward_pure, cfg))(
        params, ids)


def test_forward_pure_equals_the_reference_in_float32(model, whole):
    cfg, params, fields = model
    ids, got = whole
    want = reference.forward(fields, params, ids)
    assert got.shape == (2, 28, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 2e-5


def _memory_after_the_gate(monkeypatch):
    real = reference._mamba

    def gated(x, lp, f, length):
        out, m, last = real(x, lp, f, length)
        z = jnp.split(reference._layer_norm(
            x, lp["ln1_w"], lp["ln1_b"], f["layer_norm_eps"]) @ lp["w_in"],
            2, axis=-1)[1]
        return out, m * jax.nn.silu(z), last
    monkeypatch.setattr(reference, "_mamba", gated)


def _cross_layers_on_other_keys(monkeypatch):
    real = reference._self_attention

    def other(x, lp, f, i, window):
        out, k, v = real(x, lp, f, i, window)
        # what the later layers are handed: another pair-head's K and V
        return out, jnp.roll(k, 2, axis=1), jnp.roll(v, 2, axis=1)
    monkeypatch.setattr(reference, "_self_attention", other)


@pytest.mark.parametrize("departure", ["window-ignored", "wrong-pool",
                                       "lambda-dropped", "m-after-the-gate"])
def test_a_departure_from_the_model_is_further_off_than_the_tolerance(
        model, whole, departure, monkeypatch):
    """What the comparison above can tell apart: each of these is a model
    one could have built by mistake (the first and third on the program's
    side, the others on the reference's), and none passes for the other."""
    cfg, params, fields = model
    ids, got = whole
    if departure == "window-ignored":
        fields = dict(fields, sliding_window=10**6)
    elif departure == "lambda-dropped":
        monkeypatch.setattr(
            phi4flash, "_lambdas",
            lambda lp, i, real=phi4flash._lambdas: (0.0, real(lp, i)[1]))
        got = jax.jit(functools.partial(phi4flash.forward_pure, cfg))(
            params, ids[:1, :24])
        ids = ids[:1, :24]
    elif departure == "wrong-pool":
        _cross_layers_on_other_keys(monkeypatch)
    else:
        _memory_after_the_gate(monkeypatch)
    want = reference.forward(fields, params, ids)
    off = float(jnp.abs(got - want).max())
    assert off > 1e-3                # fifty times the tolerance above


# -- the ragged step, driven directly ----------------------------------------

def Rows(model):
    """``test_jamba.Rows`` on this model's step and cache."""
    return test_jamba.Rows(model, module=phi4flash)


@pytest.mark.parametrize("chunk", [16, 4])
def test_prefill_in_chunks_then_decode_equals_one_full_forward(model, chunk):
    """110 tokens on a ring of 64 positions: the ring wraps during the
    prefill and again while decoding (W + 2 x page is 52)."""
    (seq,) = prompts_of(110, seed=2)
    (want,) = ref_logits(model, [seq])
    rows, got, pos = Rows(model), [], 0
    while pos < 85:                              # the prompt, in chunks
        got.append(rows.feed(chunk, {0: seq[pos:pos + chunk][:85 - pos]})[0])
        pos += len(got[-1])
    for t in seq[85:]:                           # then one token a step
        got.append(rows.feed(1, {0: [t]})[0])
    close(np.concatenate(got), want)


def test_the_scan_kernel_prefills_in_chunks_then_decodes(model):
    """The same through ``_ssm_scan_kernel`` under the interpreter (8 slots:
    one group of rows), a shorter request decoding beside the chunks."""
    a, b = prompts_of(70, 20, seed=2)
    want = ref_logits(model, [a, b])
    pallas_ops._INTERPRET = True
    try:
        rows, got = test_jamba.Rows(model, R=8, module=phi4flash), \
            {0: [], 5: []}
        assert pallas_ops.ssm_scan_available(rows.cache["ssm"].shape,
                                             jnp.float32, 16)

        def step(Tc, fed):
            for r, out in rows.feed(Tc, fed).items():
                got[r].append(out)

        step(16, {0: a[:16], 5: b[:11]})
        step(16, {0: a[16:32], 5: b[11:12]})      # a decode row in the group
        step(16, {0: a[32:48], 5: b[12:13]})
        step(16, {0: a[48:63]})                   # row 5 idle
        for i in range(7):
            step(1, {0: [a[63 + i]], 5: [b[13 + i]]})
    finally:
        pallas_ops._INTERPRET = False
    close(np.concatenate(got[0]), want[0])
    close(np.concatenate(got[5]), want[1])


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
    for r, seq in enumerate((a, b, c)):
        close(np.concatenate(got[r]), want[r])


def test_padding_and_idle_rows_leave_rings_and_state_as_they_were(model):
    a, b = prompts_of(12, 20, seed=4)
    rows = Rows(model)
    rows.feed(16, {0: a, 1: b[:16]})
    before = jax.tree_util.tree_map(np.asarray, rows.cache)
    rows.feed(16, {1: b[16:]})                   # row 0 idle, row 2 never fed
    Wp = rows.cfg.ring_pages(PAGE)
    for key in ("conv", "ssm"):
        after = np.asarray(rows.cache[key])
        assert np.array_equal(after[:, :, 0], before[key][:, :, 0])
        assert not np.array_equal(after[:, :, 1], before[key][:, :, 1])
        assert not after[:, :, 2].any()
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
    # the padding of row 0's chunk (12 of 16 positions) never entered its
    # state or its ring: continuing from it equals the reference
    (want,) = ref_logits(model, [a + b[:3]])
    close(rows.feed(16, {0: b[:3]})[0], want[12:])


def test_a_chunk_that_starts_at_zero_resets_the_slot(model):
    a, b = prompts_of(75, 40, seed=5)
    rows, pos = Rows(model), 0
    while pos < len(a):                          # a wrapped ring, a live state
        rows.feed(16, {0: a[pos:pos + 16]})
        pos += 16
    rows.restart(0)                              # a second request, slot 0
    (want,) = ref_logits(model, [b])
    got = [rows.feed(16, {0: b[p:p + 16]})[0] for p in range(0, len(b), 16)]
    close(np.concatenate(got), want)


def test_the_layers_scopes_are_in_the_step(model):
    cfg, params, _ = model
    R, Tc = 2, 4
    cache = jax.eval_shape(lambda: phi4flash.init_cache(cfg, R, 5, PAGE,
                                                        jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    found = scopes_of(functools.partial(phi4flash.forward_paged, cfg), params,
                      i32(R, Tc), cache, i32(R, 2), i32(R), i32(R))
    paths = {s for _, s in found}
    for scope in ("mamba/ssm_conv", "mamba/ssm_scan", "mamba", "gmu", "mlp",
                  "attn/attn_window/kv_write", "attn/attn_global/kv_write",
                  "attn/attn_window", "attn/attn_global", "attn/attn_cross",
                  "lm_head", "embed"):
        assert any(p.endswith(scope) for p in paths), scope
    assert not any("attn_cross/kv_write" in p for p in paths)
    # the recurrence's exponentials (A and one a position) are under
    # ssm_scan in the two bodies that hold a Mamba layer and nowhere else
    scans = [s for p, s in found if p == "exp" and "ssm_scan" in s]
    assert len(scans) == 2 * (1 + Tc)
    assert all(s.endswith("mamba/ssm_scan") for s in scans)
    assert not any("ssm" in s for _, s in found if "gmu" in s.split("/"))
    # where the kernel serves (8 rows, under the interpreter as on the chip)
    # its call sits under the same scope, with its own name below
    pallas_ops._INTERPRET = True
    try:
        cache = jax.eval_shape(lambda: phi4flash.init_cache(
            cfg, 8, 5, PAGE, jnp.float32))
        found = scopes_of(functools.partial(phi4flash.forward_paged, cfg),
                          params, i32(8, Tc), cache, i32(8, 2), i32(8),
                          i32(8))
    finally:
        pallas_ops._INTERPRET = False
    calls = [s for p, s in found if p == "pallas_call"]
    assert len(calls) == 2 and all(
        s.endswith("mamba/ssm_scan/pallas/_ssm_scan_kernel") for s in calls)


def test_step_counts_are_what_the_window_layers_read(model):
    cfg = model[0]                                   # window 20
    got = phi4flash.step_counts(cfg, np.array([16, 50, 0, 21, 7]),
                                np.array([16, 1, 0, 16, 0]))
    # rows: a first chunk (keys 0..15), a decode row at 49 (20 keys), an
    # idle row, a chunk at 5..20 (21 keys held, 35 would be seen), a row
    # that holds tokens and feeds none
    assert got["window_kv_tokens"] == 16 + 20 + 21
    assert got["window_qk_pairs"] == sum(range(1, 17)) + 20 \
        + sum(min(p + 1, 20) for p in range(5, 21))


# -- LLMEngine ---------------------------------------------------------------

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
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect
    assert sorted(eng._step_fns) == [1, 16]
    stats = serving.serving_stats()
    assert stats["state_resets"] == len(prompts)
    layout = phi4flash.cache_bytes(eng.cfg, 4, PAGE)
    assert stats["state_bytes"] == eng._state_bytes \
        == 3 * layout["per_slot"] + layout["fixed"] > 0
    (held,) = [r for r in xmem.reservations() if r["name"] == "serving.state"]
    assert held["bytes"] == eng._state_bytes and held["slots"] == 3
    # the model's own counters, summed over the steps: every fed token saw
    # at most a window of keys and at least itself
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    assert fed <= stats["window_qk_pairs"] <= 20 * fed
    assert fed < stats["scan_positions"] <= 8 * fed
    assert 0 < stats["window_kv_tokens"] <= stats["window_qk_pairs"]
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference.served_checks(model[2], eng, model[1], served)
    assert verdict["logits_rel_err"] < 1e-5
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["state_rel_err"] < 1e-5
    assert verdict["state_slow_rel_err"] < 1e-5
    assert verdict["replayed_prompt"] == 90      # the request of most tokens
    assert len(verdict["state_rel_err_by_layer"]) == eng.cfg.num_mamba_layers
    assert eng.kv.audit()["ok"]
    eng.shutdown()
    assert serving.serving_stats()["state_bytes"] == 0


def test_the_models_counts_are_on_the_engine_step_span(model):
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_tpu_trace": True})
    trace.clear()
    try:
        eng = engine(model)
        eng.add_request(prompts_of(30, seed=8)[0], 2)
        drain(eng)
        steps = [e for e in trace.events()
                 if e["name"] == "serve/engine_step" and "fed_tokens" in e]
    finally:
        paddle.set_flags({"FLAGS_tpu_trace": False})
        trace.clear()
    first = steps[0]
    assert first["window_kv_tokens"] == 16 and first["kv_tokens"] == 16
    assert first["window_qk_pairs"] == sum(range(1, 17))
    assert first["scan_positions"] == 8 * 16 and steps[-1]["bucket"] == 1 \
        and steps[-1]["scan_positions"] == 8
    second = steps[1]                                # positions 16..29
    assert second["window_kv_tokens"] == 30          # min(30, 20 + 14 - 1)
    assert second["window_qk_pairs"] == sum(min(p + 1, 20)
                                            for p in range(16, 30))


def test_chunk_4_serves_the_same_streams(model, workload):
    prompts, n_new, expect = workload
    eng = engine(model, chunk=4)
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect


def test_the_pallas_kernels_serve_the_same_streams(workload):
    """The windowed RPA kernel and the write kernel under the interpreter,
    at the head width they need (a pair-head of 128) and pages of 128: a
    window of 40, so a ring of 3 pages; the longer request's walk starts
    past page 0 (the ring's wrap is the kernel tests' and the tests' above)."""
    cfg = phi4flash.preset("phi4flash-debug", dtype=jnp.float32,
                           hidden_size=256, num_attention_heads=4,
                           num_key_value_heads=2, sliding_window=40,
                           max_position_embeddings=1024)
    params = phi4flash.init_params(cfg, jax.random.PRNGKey(1))
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
              if f.name != "dtype"}
    prompts = prompts_of(200, 30, seed=9)
    pallas_ops._INTERPRET = True
    try:
        eng = serving.LLMEngine(cfg, params, max_running=2, chunk=16,
                                max_model_len=256)
        assert pallas_ops.ragged_attention_available(
            None, eng._pools["kw_pages"].shape)
        rids = [eng.add_request(p, 2) for p in prompts]
        drain(eng)
        served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    finally:
        pallas_ops._INTERPRET = False
    old, reference.PAD_TO = reference.PAD_TO, 256
    try:
        verdict = reference.served_checks(fields, eng, params, served)
    finally:
        reference.PAD_TO = old
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["logits_rel_err"] < 1e-5 > verdict["state_rel_err"]


def test_preemption_replays_through_rewritten_rings_and_a_zeroed_state(model):
    """chaos steals every free page while both requests decode towards a
    page boundary: the scheduler preempts, and the preempted request later
    replays its whole history from position 0, which zeroes its slot's
    state and rewrites its rings; the streams are the uninterrupted ones."""
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
def test_the_engine_refuses_what_rings_and_state_cannot_do(model, refused):
    draft = llama.preset("llama-debug")
    kw = {"prefix_cache": dict(prefix_cache=True),
          "spec": dict(spec=serving.SpecDecodeConfig(
              cfg=draft, params=None, k=2))}[refused]
    with pytest.raises(ValueError, match="recurrent state"):
        engine(model, **kw)


def test_a_chunk_longer_than_a_page_is_refused(model):
    with pytest.raises(ValueError, match="longer than a page"):
        engine(model, page_size=8)._lower(16)


def test_the_capacity_plan_takes_the_layout_from_the_model():
    cfg = phi4flash.preset("phi4-mini-flash")
    assert serving.kv_bytes_per_token(cfg) == 5120        # 1 of 32 layers
    layout = phi4flash.cache_bytes(cfg)
    ring = 8 * 6 * 128 * 5120                             # 31.5 MB
    state = 9 * 5120 * (16 * 4 + 3 * 2)                   # 3.23 MB
    assert layout == {"per_token": 5120, "scales_per_page": 0,
                      "per_slot": ring + state, "fixed": 8 * 128 * 5120}
    plan = serving.plan_capacity(cfg, hbm_bytes=16 * 10**9,
                                 max_model_len=11264)
    assert plan["weights_bytes"] == 2 * 3_852_562_944
    assert plan["state_bytes_per_slot"] == ring + state
    per_request = 88 * 128 * 5120 + ring + state
    usable = plan["usable_kv_bytes"]
    assert usable == int(16e9 * 0.9) - plan["weights_bytes"] - layout["fixed"]
    assert plan["max_concurrent_requests"] \
        == (usable - 128 * 5120) // per_request
