"""What a decoder layer keeps for its backward pass (``llama.saved_residuals``
and ``run_layer_stack(save=)``): the set as a function of shapes and a given
memory limit, the same loss and gradients whatever is kept, the names in the
traced layer and the flash forward run once, the default blocks, and the
``train/step`` span's account of it."""
import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.plan import Plan
from paddle_tpu.models import llama
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import trace

# the train cell's shape: hidden 2048, 24 layers, 16 heads x 128, 5504, bf16;
# B=4 x S=2048 on one device; 6 B a parameter of weights and Adam moments
CELL = llama.LlamaConfig(
    vocab_size=32256, hidden_size=2048, intermediate_size=5504,
    num_hidden_layers=24, num_attention_heads=16, num_key_value_heads=16)
CELL_PARAMS = 2 * 1_346_471_936
V5E = 16_909_336_576
ALL = llama.SAVED_NAMES


def rule(limit, cfg=CELL, tokens=4 * 2048, seq=2048, **kw):
    return llama.saved_residuals(cfg, tokens, seq, CELL_PARAMS,
                                 2 * CELL_PARAMS, limit, **kw)


@pytest.mark.parametrize("limit,want", [
    (None, ALL),                                   # a CPU: no limit
    (64 << 30, ALL),
    (V5E, ("attn_out", "attn_q", "attn_k", "attn_v")),
    (14 << 30, ("attn_out", "attn_q")),
    (12 << 30, ()),                                # the floor: full remat
])
def test_saved_set_follows_the_limit(limit, want):
    names, kept, estimate = rule(limit)
    assert names == want
    assert (kept == 0) == (not names)
    if limit is not None:
        assert estimate <= limit or not names


INTERNLM2 = llama.LlamaConfig(
    vocab_size=92544, hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=24, num_attention_heads=16, num_key_value_heads=8)
WIDE = llama.LlamaConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=11008,
    num_hidden_layers=6, num_attention_heads=32, num_key_value_heads=32)


@pytest.mark.parametrize("cfg,state,kw,kept,peak,rel", [
    # the cell: q, k, v and the attention's output kept; nothing kept
    (CELL, 8_078_897_664, {}, 4, 15_246_198_272, 0.002),
    (CELL, 8_078_897_664, {"limit": 1}, 0, 12_046_038_528, 0.002),
    # the cell under Plan(sp=2): a ring's scores, half the tokens a device
    (CELL, 8_078_897_664, {"tokens": 4096, "names": ALL[1:]}, 4,
     15_668_102_144, 0.02),
    # hidden 4096 at 6 layers: 1.4% low, inside the margin
    (WIDE, 8_858_853_888, {}, 5, 16_038_684_160, 0.02),
    # internlm2-1.8b (GQA, intermediate 8192): nothing fits; 9% high
    (INTERNLM2, 11_334_726_144, {}, 0, 16_722_516_480, 0.09),
])
def test_estimate_is_the_compiled_peak(cfg, state, kw, kept, peak, rel):
    """``peak`` is libtpu's for the step of B=4 x S=2048 with what the
    rule keeps on a v5e's limit, compiled for a described v5e with
    ``state`` bytes of weights and Adam moments (PERF.md section 4, PR
    36). The estimate is fitted at the cell's width; elsewhere it may
    err, and high is the safe side."""
    kw = {"tokens": 8192, "limit": V5E, "names": ALL, **kw}
    names, _, estimate = llama.saved_residuals(
        cfg, kw["tokens"], 2048, state // 3, 2 * state // 3, kw["limit"],
        names=kw["names"])
    assert names == kw["names"][:kept]
    assert estimate == pytest.approx(peak, rel=rel)
    assert estimate > (1 - llama._MEMORY_MARGIN) * peak


def test_saved_set_is_monotone_in_the_limit():
    sets = [rule(gib << 30)[0] for gib in range(8, 40)]
    for small, large in zip(sets, sets[1:]):
        assert large[:len(small)] == small
    assert sets[0] == () and sets[-1] == ALL


@pytest.mark.parametrize("names,at_v5e,scores", [
    # a ring, a CPU: XLA's attention holds float32 scores [B, nh, S, S],
    # their probabilities and both cotangents, which here leaves no room
    (ALL[1:], (), 4 * 4 * 16 * 2048 * 2048 * 4),
    # a fused attention block beside an unfused feed-forward: room that
    # the attention's names would have taken goes to gate
    (("mlp_gate", "mlp_up"), ("mlp_gate",), 0),
    ((), (), 0)])                                 # fused blocks
def test_only_what_the_layer_names_is_charged(names, at_v5e, scores):
    """The set is a prefix of the names the traced layer has, and the
    bytes of the others take no room."""
    got, kept, estimate = rule(None, names=names)
    assert got == names
    assert estimate - kept == rule(1)[2] + scores
    assert rule(V5E, names=names)[0] == at_v5e
    # a ring over two devices halves the scores: q, k and v fit again
    assert rule(V5E, names=names, tokens=2 * 2048)[0][:3] == names[:3]


def test_moe_keeps_the_capacity_buffers():
    dense = rule(None)[1]
    moe = rule(None, cfg=dataclasses.replace(
        CELL, moe_num_experts=8, moe_top_k=2, moe_capacity_factor=1.25))[1]
    w, L, T, I = 2, 24, 8192, 5504
    assert moe - dense == L * 2 * (int(1.25 * T * 2) - T) * I * w


@pytest.mark.parametrize("dp,want", [
    (1, ("attn_out", "attn_q", "attn_k", "attn_v")), (4, ALL)])
def test_step_decides_on_the_batch_a_device_holds(dp, want, monkeypatch):
    """B=4 on one device keeps four names at the cell's depth; split over
    dp=4 a device holds a quarter of the tokens and keeps everything."""
    monkeypatch.setattr(llama, "_memory_limit", lambda mesh: V5E)
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    step_fn, _ = Plan(dp=dp).train_step(CELL, jax.devices()[:dp],
                                        verify=False)
    names, kept, _ = step_fn.residuals((4, 2048))
    assert names == want
    assert kept == rule(V5E, tokens=4 * 2048 // dp)[1]


def on_chip(monkeypatch, on):
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: on)


@pytest.mark.parametrize("plan,cfg,chip,want", [
    (Plan(), CELL, True, ALL),
    (Plan(), CELL, False, ALL[1:]),               # XLA attention: no output
    (Plan(sp=2), CELL, True, ALL[1:]),            # the ring names q, k, v
    (Plan(dp=2, mp=2), CELL, True, ALL),
    (Plan(dp=2, overlap=True), CELL, True, ALL[1:]),   # no kernel in there
    (Plan(), dataclasses.replace(CELL, fused_blocks="on"), True, ()),
    (Plan(dp=2, mp=2), dataclasses.replace(CELL, fused_blocks="on"), True,
     ALL),                                        # fused blocks need mp == 1
    (Plan(), dataclasses.replace(CELL, fused_blocks="on", moe_num_experts=4),
     True, ("mlp_gate", "mlp_up")),
])
def test_step_reports_only_names_its_layers_have(plan, cfg, chip, want,
                                                 monkeypatch):
    """What the span and the log say is kept is a subset of what the
    traced layer names: nothing under the fused blocks, no attention
    output where no flash forward runs."""
    on_chip(monkeypatch, chip)
    step_fn, _ = plan.train_step(cfg, jax.devices()[:plan.world_size],
                                 verify=False)
    names, kept, _ = step_fn.residuals((4, 2048))
    assert names == want and (kept > 0) == bool(want)


def test_limit_is_asked_of_a_device_this_process_addresses():
    """In a multi-process run the mesh's first device belongs to one
    process only, and jaxlib raises from another's memory_stats()."""
    class Device:
        def __init__(self, mine):
            self.mine = mine

        def memory_stats(self):
            if not self.mine:
                raise RuntimeError("MemoryStats is only supported for "
                                   "addressable PjRt devices.")
            return {"bytes_limit": V5E}

    theirs, mine = Device(False), Device(True)
    mesh = types.SimpleNamespace(devices=np.array([theirs, mine]),
                                 local_devices=[mine])
    assert llama._memory_limit(mesh) == V5E
    cpu = Plan(dp=2).topology(jax.devices()[:2]).mesh
    assert llama._memory_limit(cpu) is None


def test_step_without_remat_asks_nothing_of_the_device(monkeypatch):
    """use_remat=False keeps everything by itself: no rule, no limit
    read, nothing to report."""
    def never(mesh):
        raise AssertionError("no rule to size")
    monkeypatch.setattr(llama, "_memory_limit", never)
    cfg = llama.preset("llama-debug", use_remat=False)
    step_fn, init_fn = Plan().train_step(cfg, jax.devices()[:1],
                                         verify=False)
    assert not hasattr(step_fn, "residuals")
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = {k: jax.device_put(v, step_fn.batch_shardings[k])
             for k, v in tiny_batch(32).items()}
    _, _, metrics = step_fn(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))


# --- the same step whatever is kept ---------------------------------------

TINY = llama.LlamaConfig(
    vocab_size=256, hidden_size=256, intermediate_size=512,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
    max_position_embeddings=256, dtype=jnp.float32)


def tiny_batch(seq=256):
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, seq + 1), 0, 256)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def loss_and_grads(cfg, save, batch):
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return jax.value_and_grad(
        lambda p: llama.loss_fn(cfg, p, batch, save=save)[0])(params)


def assert_same_step(got, want):
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("interpreted", [False, True])
@pytest.mark.parametrize("save", [
    (), ("attn_out", "attn_q"), ("attn_q", "attn_k", "attn_v", "mlp_up")])
def test_loss_and_gradients_do_not_depend_on_what_is_kept(
        save, interpreted, monkeypatch):
    monkeypatch.setattr(pallas_ops, "_INTERPRET", interpreted)
    batch = tiny_batch()
    assert_same_step(loss_and_grads(TINY, save, batch),
                     loss_and_grads(TINY, ALL, batch))


def test_no_remat_is_the_same_step():
    batch = tiny_batch(64)
    assert_same_step(
        loss_and_grads(dataclasses.replace(TINY, use_remat=False), ALL,
                       batch),
        loss_and_grads(TINY, ALL, batch))


# --- the names in the traced layer, and the flash forward once ------------

def lowered_backward(cfg, save, monkeypatch):
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    batch = tiny_batch()
    fn = jax.grad(lambda p: llama.loss_fn(cfg, p, batch, save=save)[0])
    return fn, params


@pytest.mark.parametrize("moe", [0, 4])
def test_traced_layer_holds_the_six_names(moe, monkeypatch):
    cfg = dataclasses.replace(TINY, moe_num_experts=moe)
    fn, params = lowered_backward(cfg, ALL, monkeypatch)
    text = str(jax.make_jaxpr(fn)(params))
    assert set(re.findall(r"name\[name=(\w+)\]", text)) == set(ALL)


def test_ring_attention_layer_names_q_k_v_and_no_output(monkeypatch):
    """A context-parallel layer's ring has no backward rule whose
    residual an output could be: it names what feeds it."""
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    mesh = Plan(sp=2).topology(jax.devices()[:2]).mesh
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    batch = tiny_batch()
    with jax.set_mesh(mesh):
        text = str(jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(
            TINY, p, batch, cp_mesh=mesh)[0]))(params))
    assert set(re.findall(r"name\[name=(\w+)\]", text)) == set(ALL[1:])
    x = jax.ShapeDtypeStruct((2, 256, 256), TINY.dtype)
    assert llama.layer_names(TINY, x, cp_mesh=mesh) == ALL[1:]
    assert llama.layer_names(TINY, x) == ALL


@pytest.mark.parametrize("save,again", [
    (ALL, False), (("attn_out",), False),
    (("attn_q", "attn_k", "attn_v"), True), ((), True)])
def test_flash_forward_runs_again_only_without_its_output(save, again,
                                                          monkeypatch):
    """With the output and its log-sum-exp kept, no flash forward sits
    under ``rematted_computation``; the backward kernels never do."""
    fn, params = lowered_backward(TINY, save, monkeypatch)
    text = jax.jit(fn).lower(params).as_text(debug_info=True)
    kernels = {}
    for loc in re.findall(r'loc\("([^"]*pallas/[^"]*)"', text):
        name = loc.split("pallas/")[1].split("/")[0]
        kernels.setdefault(name, set()).add("rematted_computation" in loc)
    assert set(kernels) == {"_flash_fwd_kernel_resident",
                            "_flash_bwd_dq_kernel_resident",
                            "_flash_bwd_dkv_kernel_resident"}
    assert (True in kernels["_flash_fwd_kernel_resident"]) == again
    assert kernels["_flash_bwd_dq_kernel_resident"] == {False}
    assert kernels["_flash_bwd_dkv_kernel_resident"] == {False}


def test_kept_log_sum_exp_is_one_lane_a_row(monkeypatch):
    """The forward rule's residual is [B x H, S], not the kernel's
    [B x H, S, 128] with every lane the same."""
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 2, 128))
    _, res = pallas_ops._fwd(q, q, q)
    assert [r.shape for r in res] == [(2, 256, 128)] * 4 + [(2, 256)]


# --- which blocks the default step runs ------------------------------------

@pytest.mark.parametrize("fused_blocks,want", [
    (None, (False, False)), ("off", (False, False)), ("on", (True, True))])
def test_default_blocks_are_unfused_on_the_chip_too(fused_blocks, want,
                                                    monkeypatch):
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    kw = {} if fused_blocks is None else {"fused_blocks": fused_blocks}
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        **kw)
    x = jnp.zeros((1, 256, 256), cfg.dtype)
    assert llama._fused_block_modes(cfg, x, None, False) == want


def test_no_option_selects_what_is_kept():
    assert "remat_policy" not in {
        f.name for f in dataclasses.fields(llama.LlamaConfig)}


def test_fused_blocks_has_no_auto():
    with pytest.raises(AssertionError):
        llama.LlamaConfig(fused_blocks="auto")


# --- the span ---------------------------------------------------------------

@pytest.fixture
def trace_on():
    trace.clear()
    paddle.set_flags({"FLAGS_tpu_trace": True})
    yield
    paddle.set_flags({"FLAGS_tpu_trace": False})
    trace.clear()


@pytest.mark.parametrize("limit", [None, 1])
def test_train_step_span_says_what_the_layers_keep(limit, trace_on,
                                                   monkeypatch, caplog):
    monkeypatch.setattr(llama, "_memory_limit", lambda mesh: limit)
    cfg = llama.preset("llama-debug")
    step_fn, init_fn = Plan().train_step(cfg, jax.devices()[:1],
                                         verify=False)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = {k: jax.device_put(v, step_fn.batch_shardings[k])
             for k, v in tiny_batch(32).items()}
    with caplog.at_level("INFO", logger=llama.__name__):
        for _ in range(2):
            params, opt_state, _ = step_fn(params, opt_state, batch)
    spans = [e for e in trace.events() if e["name"] == "train/step"]
    names, kept, estimate = step_fn.residuals((2, 32))
    want = ",".join(names)
    # on the CPU the XLA attention serves: no flash output to keep
    assert names == (ALL[1:] if limit is None else ())
    assert [s["saved"] for s in spans] == [want, want]
    assert spans[0]["saved_bytes"] == kept and (kept > 0) == bool(want)
    said = [r.getMessage() for r in caplog.records if "layers keep" in
            r.getMessage()]
    assert len(said) == 1 and str(estimate) in said[0]
    assert (want or "nothing") in said[0]


def test_pipelined_step_has_no_rule_to_report(trace_on):
    cfg = llama.preset("llama-debug", dtype=jnp.float32)
    step_fn, _ = Plan(pp=2, schedule="1f1b", n_microbatches=2).train_step(
        cfg, jax.devices()[:2], verify=False)
    assert not hasattr(step_fn, "residuals")
