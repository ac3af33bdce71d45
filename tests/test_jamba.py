"""``models/jamba.py`` (Mamba-1 layers with an attention layer among them)
against the float32 reference ``benchmark/reference_jamba.py``, at a debug
width on the CPU: the full-sequence forward, the engine's ragged step with
its per-slot recurrent state, and ``LLMEngine`` serving it through the model
protocol — slot reuse, preemption-replay, a rebuilt engine — with what the
engine refuses for such a model."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_jamba
from paddle_tpu import serving
from paddle_tpu.models import jamba, llama
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import xmem
from paddle_tpu.testing import chaos
from test_spans import interpret, scopes_of  # noqa: F401 (a fixture)

PAGE = 16


@pytest.fixture(scope="module")
def model():
    cfg = jamba.preset("jamba-debug", dtype=jnp.float32)
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
              if f.name != "dtype"}
    return cfg, params, fields


def prompts_of(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def ref_logits(model, rows):
    _, params, fields = model
    return reference_jamba.logits(fields, params, rows)


def test_the_debug_preset_and_the_published_model_have_the_layers_they_say():
    debug, full = jamba.preset("jamba-debug"), jamba.preset("jamba2-3b")
    assert debug.layer_runs() == [("mamba", 0, 2), ("attn", 0),
                                  ("mamba", 2, 5)]
    assert full.attn_layers == (7, 21)
    assert full.layer_runs() == [("mamba", 0, 7), ("attn", 0),
                                 ("mamba", 7, 20), ("attn", 1),
                                 ("mamba", 20, 26)]
    assert jamba.param_count(full) == 3_029_337_472
    params = jax.eval_shape(functools.partial(jamba.init_params, debug),
                            jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == jamba.param_count(debug)


def test_forward_pure_equals_the_reference_in_float32(model):
    cfg, params, fields = model
    ids = jnp.asarray(prompts_of(24, 24, seed=1), jnp.int32)
    got = jax.jit(functools.partial(jamba.forward_pure, cfg))(params, ids)
    want = reference_jamba.forward(fields, params, ids)
    assert got.shape == (2, 24, cfg.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 2e-5


# -- the ragged step, driven directly ----------------------------------------

class Rows:
    """``forward_paged`` of ``module`` on a cache of ``R`` slots, fed by hand:
    each call of ``feed`` is one engine step over ``{slot: tokens}``; it
    returns the logits of each slot's fed positions."""

    def __init__(self, model, R=3, blocks=8, module=jamba):
        self.cfg, self.params, _ = model
        self.R, self.blocks = R, blocks
        self.cache = module.init_cache(self.cfg, R, 1 + R * blocks, PAGE,
                                       jnp.float32)
        self.tbl = np.zeros((R, blocks), np.int32)
        for r in range(R):
            self.tbl[r] = 1 + r * blocks + np.arange(blocks)
        self.lens = np.zeros((R,), np.int32)
        self.fwd = jax.jit(functools.partial(module.forward_paged, self.cfg))

    def feed(self, Tc, rows):
        tokens = np.zeros((self.R, Tc), np.int32)
        qlens = np.zeros((self.R,), np.int32)
        for r, toks in rows.items():
            tokens[r, :len(toks)] = toks
            qlens[r] = len(toks)
            self.lens[r] += len(toks)
        logits, self.cache = self.fwd(
            self.params, jnp.asarray(tokens), self.cache,
            jnp.asarray(self.tbl), jnp.asarray(self.lens * (qlens > 0)),
            jnp.asarray(qlens))
        return {r: np.asarray(logits[r, :len(t)]) for r, t in rows.items()}

    def restart(self, r):
        self.lens[r] = 0


def close(got, want, tol=2e-5):
    assert got.shape == want.shape
    assert np.abs(got - want).max() < tol


@pytest.mark.parametrize("chunk", [16, 4])
def test_prefill_in_chunks_then_decode_equals_one_full_forward(model, chunk):
    (seq,) = prompts_of(45, seed=2)
    (want,) = ref_logits(model, [seq])
    rows, got, pos = Rows(model), [], 0
    while pos < 37:                              # the prompt, in chunks
        got.append(rows.feed(chunk, {0: seq[pos:pos + chunk][:37 - pos]})[0])
        pos += len(got[-1])
    for t in seq[37:]:                           # then one token a step
        got.append(rows.feed(1, {0: [t]})[0])
    close(np.concatenate(got), want)


def test_the_scan_kernel_prefills_in_chunks_then_decodes(model, interpret):
    """The same through ``_ssm_scan_kernel`` under the interpreter (8 slots:
    one group of rows): a prompt in chunks beside a shorter one, which
    decodes while the first still prefills, then both a token a step."""
    a, b = prompts_of(45, 20, seed=2)
    want = ref_logits(model, [a, b])
    rows, got = Rows(model, R=8, blocks=4), {0: [], 3: []}
    assert pallas_ops.ssm_scan_available(rows.cache["ssm"].shape,
                                         jnp.float32, 16)

    def step(Tc, fed):
        for r, out in rows.feed(Tc, fed).items():
            got[r].append(out)

    step(16, {0: a[:16], 3: b[:11]})
    step(16, {0: a[16:32], 3: b[11:12]})          # a decode row in the group
    step(16, {0: a[32:37], 3: b[12:13]})
    for i in range(7):
        step(1, {0: [a[37 + i]], 3: [b[13 + i]]})
    step(1, {0: [a[44]]})                         # row 3 idle
    close(np.concatenate(got[0]), want[0])
    close(np.concatenate(got[3]), want[1])
    assert not np.asarray(rows.cache["ssm"])[:, :, [1, 2, 4, 5, 6, 7]].any()


def test_ragged_neighbours_and_a_decode_row_inside_a_chunk_bucket(model):
    a, b, c = prompts_of(30, 21, 9, seed=3)
    want = ref_logits(model, [a, b, c])
    rows = Rows(model)
    got = {0: [], 1: [], 2: []}

    def step(Tc, fed):
        for r, out in rows.feed(Tc, fed).items():
            got[r].append(out)

    step(16, {0: a[:16], 1: b[:5], 2: c[:8]})     # three lengths, one step
    step(16, {0: a[16:29], 1: b[5:21], 2: c[8:]})  # c decodes beside chunks
    step(16, {0: a[29:]})                          # b and c sit idle
    for r, seq in enumerate((a, b, c)):
        close(np.concatenate(got[r]), want[r])


def test_padding_and_idle_rows_leave_the_state_as_it_was(model):
    a, b = prompts_of(12, 20, seed=4)
    rows = Rows(model)
    rows.feed(16, {0: a, 1: b[:16]})
    before = jax.tree_util.tree_map(np.asarray, rows.cache)
    rows.feed(16, {1: b[16:]})                   # row 0 idle, row 2 never fed
    for key in ("conv", "ssm"):
        after = np.asarray(rows.cache[key])
        assert np.array_equal(after[:, :, 0], before[key][:, :, 0])
        assert np.array_equal(after[:, :, 2], before[key][:, :, 2])
        assert not np.array_equal(after[:, :, 1], before[key][:, :, 1])
        assert not after[:, :, 2].any()
    # the padding of row 0's chunk (12 of 16 positions) never entered its
    # state: continuing from it equals the reference's full forward
    (want,) = ref_logits(model, [a + b[:3]])
    close(rows.feed(16, {0: b[:3]})[0], want[12:])


def test_a_chunk_that_starts_at_zero_zeroes_the_slots_state(model):
    a, b = prompts_of(20, 11, seed=5)
    rows = Rows(model)
    rows.feed(16, {0: a[:16]})
    rows.feed(16, {0: a[16:]})
    rows.restart(0)                              # a second request, slot 0
    (want,) = ref_logits(model, [b])
    close(rows.feed(16, {0: b})[0], want)


def test_the_mixers_scopes_are_in_the_step(model):
    cfg, params, _ = model
    R, Tc = 2, 4
    cache = jax.eval_shape(lambda: jamba.init_cache(cfg, R, 5, PAGE,
                                                    jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    found = scopes_of(functools.partial(jamba.forward_paged, cfg), params,
                      i32(R, Tc), cache, i32(R, 2), i32(R), i32(R))
    paths = {s for _, s in found}
    for scope in ("mamba/ssm_conv", "mamba/ssm_scan", "mamba", "mlp",
                  "attn/kv_write", "attn", "lm_head", "embed"):
        assert any(p.endswith(scope) for p in paths), scope
    # the recurrence's exponentials: A and one a position, under ssm_scan
    assert sum(p == "exp" and s.endswith("mamba/ssm_scan")
               for p, s in found) == 2 * (1 + Tc)       # two runs of layers
    # each state's slice out of its stack and its write-back are inside the
    # scope that is timed against the state's bytes, not beside it
    for prim in ("dynamic_slice", "dynamic_update_slice"):
        at = [s for p, s in found if p == prim and "mamba" in s
              and not s.endswith("mamba")]     # mamba itself: weight slices
        assert sorted(s.rsplit("/", 1)[1] for s in at) == [
            "ssm_conv", "ssm_conv", "ssm_scan", "ssm_scan"], (prim, at)
    # where the kernel serves (8 rows, under the interpreter as on the chip)
    # its call sits under the same scope, with its own name below
    pallas_ops._INTERPRET = True
    try:
        cache = jax.eval_shape(lambda: jamba.init_cache(cfg, 8, 5, PAGE,
                                                        jnp.float32))
        found = scopes_of(functools.partial(jamba.forward_paged, cfg), params,
                          i32(8, Tc), cache, i32(8, 2), i32(8), i32(8))
    finally:
        pallas_ops._INTERPRET = False
    calls = [s for p, s in found if p == "pallas_call"]
    assert len(calls) == 2 and all(
        s.endswith("mamba/ssm_scan/pallas/_ssm_scan_kernel") for s in calls)


# -- LLMEngine ---------------------------------------------------------------

def engine(model, **kw):
    cfg, params, _ = model
    kw = dict(dict(max_running=3, chunk=16, page_size=PAGE,
                   max_model_len=128), **kw)
    return serving.LLMEngine(cfg, params, **kw)


def drain(eng):
    while eng.has_work():
        eng.step()


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
    prompts = prompts_of(5, 37, 16, 50, 23, seed=6)
    return prompts, 6, greedy_of(model, prompts, 6)


def test_the_engine_serves_it_and_a_reused_slot_starts_from_zero(
        model, workload):
    prompts, n_new, expect = workload
    serving.reset_stats()
    eng = engine(model)                  # five requests on three slots
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect
    stats = serving.serving_stats()
    assert stats["state_resets"] == len(prompts)
    # three slots are one group of 8 for the scan: a step walks 8 x its
    # longest chunk, so at least the padded slots and at most 8 a fed token
    fed = stats["prefill_tokens"] + stats["decode_tokens"]
    assert fed < stats["scan_positions"] <= 8 * fed
    assert stats["scan_positions"] % 8 == 0
    assert stats["state_bytes"] == eng._state_bytes \
        == 3 * jamba.cache_bytes(eng.cfg)["per_slot"] > 0
    (held,) = [r for r in xmem.reservations() if r["name"] == "serving.state"]
    assert held["bytes"] == eng._state_bytes and held["slots"] == 3
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference_jamba.served_checks(model[2], eng, model[1], served)
    assert verdict["logits_rel_err"] < 1e-5
    assert verdict["token_gap_sigma"] == 0.0
    assert verdict["state_rel_err"] < 1e-5
    assert verdict["state_slow_rel_err"] < 1e-5
    assert verdict["replayed_prompt"] == 50      # the request of most tokens
    assert len(verdict["state_rel_err_by_layer"]) == eng.cfg.num_mamba_layers
    assert eng.kv.audit()["ok"]
    eng.shutdown()
    assert serving.serving_stats()["state_bytes"] == 0


def test_the_state_check_sees_a_state_kept_in_bfloat16(model, workload,
                                                       monkeypatch):
    """The precision the recurrent state is stored in between steps, which
    the logits hardly show, is what ``state_rel_err`` reads."""
    fresh = jamba.mamba_state

    def fresh_bf16(cfg, rows):
        state = fresh(cfg, rows)
        return dict(state, ssm=state["ssm"].astype(jnp.bfloat16))

    monkeypatch.setattr(jamba, "mamba_state", fresh_bf16)
    prompts, n_new, _ = workload
    eng = engine(model)
    assert eng._pools["ssm"].dtype == jnp.bfloat16
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    served = [(p, eng.output_of(r)) for p, r in zip(prompts, rids)]
    verdict = reference_jamba.served_checks(model[2], eng, model[1], served)
    eng.shutdown()
    assert verdict["state_slow_rel_err"] > 1e-3 \
        > verdict["logits_rel_err"] * 10


def test_chunk_4_serves_the_same_streams(model, workload):
    prompts, n_new, expect = workload
    eng = engine(model, chunk=4)
    rids = [eng.add_request(p, n_new) for p in prompts]
    drain(eng)
    assert [eng.output_of(r) for r in rids] == expect


def test_preemption_replays_through_a_zeroed_state(model):
    """chaos steals every free page while both requests decode towards a
    page boundary: the scheduler preempts, and the preempted request later
    replays its whole history from position 0, which zeroes its slot's
    state; the streams are the uninterrupted ones."""
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
def test_the_engine_refuses_what_recurrent_state_cannot_do(model, refused):
    draft = llama.preset("llama-debug")
    kw = {"prefix_cache": dict(prefix_cache=True),
          "spec": dict(spec=serving.SpecDecodeConfig(
              cfg=draft, params=None, k=2))}[refused]
    with pytest.raises(ValueError, match="recurrent state"):
        engine(model, **kw)


def test_the_capacity_plan_takes_the_layout_from_the_model():
    cfg = jamba.preset("jamba2-3b")
    assert serving.kv_bytes_per_token(cfg) == 1024        # 2 of 28 layers
    assert jamba.cache_bytes(cfg)["per_slot"] == 9_318_400
    plan = serving.plan_capacity(cfg, hbm_bytes=16 * 10**9,
                                 max_model_len=3072)
    assert plan["weights_bytes"] == 2 * 3_029_337_472
    assert plan["state_bytes_per_slot"] == 9_318_400
    per_request = 24 * 128 * 1024 + 9_318_400
    usable = plan["usable_kv_bytes"]
    assert plan["max_concurrent_requests"] \
        == (usable - 128 * 1024) // per_request
    # what is left after every slot's state is pages
    assert plan["num_pages"] == (
        usable - plan["max_concurrent_requests"] * 9_318_400) // (128 * 1024)
    # Llama's plan is what it was: nothing a slot, every layer holds K/V
    dense = llama.preset("llama7b")
    assert serving.kv_bytes_per_token(dense) == 2 * 32 * 32 * 128 * 2
    assert serving.plan_capacity(
        dense, hbm_bytes=96 << 30)["state_bytes_per_slot"] == 0
