"""Serving resilience: SLOs + admission control, crash recovery via
replay, and health-driven multi-replica failover (ISSUE 11).

The acceptance bar: the bounded queue sheds with a typed retriable
error and never loses an admitted request; deadlines/cancellation are
terminal at step boundaries; a raising user callback cannot kill the
step loop; an injected ``fail@serve.step`` quarantines exactly the
poisoned request via bisection while every other stream recovers —
bit-identical to an uninterrupted reference — through pool-rebuild
replay; a hung step past the watchdog deadline takes the same recovery
path; and the router fails a killed replica's in-flight streams over
to the survivor with bit-identical, idempotent continuations.
"""
import signal
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import llama
from paddle_tpu.models.decoding import init_kv_cache
from paddle_tpu.ops import pallas_ops
from paddle_tpu.runtime import watchdog as wdog
from paddle_tpu.runtime.health import HeartbeatTracker
from paddle_tpu.serving.errors import (AdmissionRejected,
                                       DeadlineExceeded,
                                       ReplicaUnavailable,
                                       RequestQuarantined)
from paddle_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


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
def workload(model):
    cfg, params = model
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, 128, rng.randint(3, 10)))
               for _ in range(6)]
    n_new = 6
    expect = [_dense_greedy(cfg, params, p, n_new) for p in prompts]
    return prompts, n_new, expect


def _engine(cfg, params, **kw):
    kw.setdefault("max_running", 4)
    kw.setdefault("chunk", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_model_len", 32)
    return serving.LLMEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# errors taxonomy + request lifecycle (clock, deadlines, cancel, shed)
# ---------------------------------------------------------------------------


def test_error_taxonomy_retriable_flags():
    assert AdmissionRejected("x").retriable
    assert ReplicaUnavailable("x").retriable
    assert not DeadlineExceeded("x").retriable
    assert not RequestQuarantined("x").retriable
    assert isinstance(AdmissionRejected("x"), serving.ServingError)
    assert isinstance(AdmissionRejected("x"), RuntimeError)


def test_engine_uses_injected_monotonic_clock(model):
    cfg, params = model
    clk = _FakeClock(100.0)
    eng = _engine(cfg, params, clock=clk)
    rid = eng.add_request([1, 2, 3], 2)
    assert eng._requests[rid].arrival_s == 100.0
    clk.advance(0.25)
    while eng.has_work():
        eng.step()
    req = eng._requests[rid]
    assert req.first_token_s == 100.25 and req.finish_s == 100.25
    rep = eng.slo_report()
    assert rep["ttft_p95_s"] == pytest.approx(0.25)
    assert rep["latency_p95_s"] == pytest.approx(0.25)


def test_bounded_admission_sheds_with_hysteresis(model):
    cfg, params = model
    eng = _engine(cfg, params, max_running=1, max_queue=4)
    # fill: 1 running + 4 waiting is the bound (no steps yet -> all wait)
    rids = [eng.add_request([1, 2, 3], 2) for _ in range(4)]
    with pytest.raises(AdmissionRejected) as ei:
        eng.add_request([1, 2, 3], 2)
    assert ei.value.retriable
    assert serving.serving_stats()["shed"] >= 1
    # hysteresis: still shedding while the queue sits above half
    while eng.scheduler.num_waiting > 3:
        eng.step()
    with pytest.raises(AdmissionRejected):
        eng.add_request([1, 2, 3], 2)
    # at/below half -> admission resumes, nothing was lost
    while eng.scheduler.num_waiting > 2:
        eng.step()
    eng.add_request([1, 2, 3], 2)
    while eng.has_work():
        eng.step()
    assert all(len(eng.output_of(r)) == 2 for r in rids)


def test_deadline_expires_as_typed_failure(model):
    cfg, params = model
    clk = _FakeClock()
    eng = _engine(cfg, params, clock=clk)
    fast = eng.add_request([1, 2, 3], 4, deadline_s=100.0)
    slow = eng.add_request([4, 5, 6], 4, deadline_s=0.5)
    eng.step()
    clk.advance(1.0)  # past slow's deadline, inside fast's
    while eng.has_work():
        eng.step()
    assert eng.state_of(fast).value == "finished"
    assert eng.state_of(slow).value == "failed"
    assert isinstance(eng.error_of(slow), DeadlineExceeded)
    assert not eng.error_of(slow).retriable
    assert serving.serving_stats()["deadline_expired"] >= 1
    assert eng.kv.allocator.num_allocated == 0


def test_slo_config_default_deadline(model):
    cfg, params = model
    clk = _FakeClock()
    eng = _engine(cfg, params, clock=clk,
                  slo=serving.SLOConfig(deadline_s=2.0))
    rid = eng.add_request([1, 2, 3], 4)
    assert eng._requests[rid].deadline_s == 2.0


def test_cancel_waiting_and_running(model):
    cfg, params = model
    eng = _engine(cfg, params, max_running=1)
    running = eng.add_request([1, 2, 3], 6)
    waiting = eng.add_request([4, 5, 6], 6)
    eng.step()  # seats `running`, `waiting` queues behind it
    assert eng.cancel(waiting)
    assert eng.state_of(waiting).value == "cancelled"
    assert eng.cancel(running)
    assert eng.kv.allocator.num_allocated == 0  # pages freed
    assert not eng.has_work()
    assert not eng.cancel(running)  # already terminal


def test_raising_callback_cannot_kill_the_stream(model, workload):
    cfg, params = model
    prompts, n_new, expect = workload
    eng = _engine(cfg, params)
    calls = []

    def bad(rid, tok, done):
        calls.append(tok)
        raise RuntimeError("user callback bug")

    before = serving.serving_stats()["callback_errors"]
    rid = eng.add_request(prompts[0], n_new, on_token=bad)
    ok = eng.add_request(prompts[1], n_new)
    while eng.has_work():
        eng.step()
    # one raise, disarmed, both streams completed exactly
    assert len(calls) == 1
    assert serving.serving_stats()["callback_errors"] == before + 1
    assert eng.output_of(rid) == expect[0]
    assert eng.output_of(ok) == expect[1]


# ---------------------------------------------------------------------------
# pool exhaustion: admission waits, mid-decode self-preemption
# ---------------------------------------------------------------------------


def test_pool_exhaustion_at_admission_waits_then_admits(model, workload):
    """Satellite: total page-pool exhaustion must leave the request
    queued (not crashed or dropped), count an admission wait, and admit
    once pages free."""
    cfg, params = model
    prompts, n_new, expect = workload
    eng = _engine(cfg, params)
    # an external tenant (chaos) holds every free page before admission
    held = eng.kv.allocator.alloc(eng.kv.allocator.num_free,
                                  owner="__tenant__")
    before = serving.serving_stats()["admission_waits"]
    rid = eng.add_request(prompts[0], n_new)
    for _ in range(3):
        eng.step()
    assert eng.state_of(rid).value == "waiting"  # queued, not dropped
    assert serving.serving_stats()["admission_waits"] > before
    eng.kv.allocator.free(held)
    while eng.has_work():
        eng.step()
    assert eng.output_of(rid) == expect[0]


def test_mid_decode_exhaustion_self_preempts_and_replays(model, workload):
    """chaos `exhaust@serve.step` steals every free page mid-decode:
    the scheduler self-preempts instead of raising, and the streams
    finish bit-identical once the pages come back."""
    cfg, params = model
    prompts, n_new, expect = workload
    eng = _engine(cfg, params, max_running=2)
    rids = [eng.add_request(p, n_new) for p in prompts[:2]]
    with chaos.installed(
            chaos.Chaos("exhaust@serve.step:step=2,times=1")) as c:
        eng.step()
        eng.step()
        eng.step()  # fires: pool drained under the running batch
        for _ in range(4):
            eng.step()  # self-preempted, waiting on pages — no crash
        assert eng.has_work()
        assert serving.serving_stats()["requests_preempted"] >= 1
        c.release_exhausted()
        while eng.has_work():
            eng.step()
    assert [eng.output_of(r) for r in rids] == expect[:2]


def test_oversized_request_rejected_at_add(model):
    cfg, params = model
    eng = _engine(cfg, params, num_pages=3)  # 2 usable pages = 16 toks
    with pytest.raises(ValueError, match="exceeds pool capacity"):
        eng.add_request(list(range(20)), 10)


# ---------------------------------------------------------------------------
# step-failure recovery: classification, replay, bisection quarantine
# ---------------------------------------------------------------------------


def test_failure_classification():
    classify = serving.LLMEngine._classify
    from paddle_tpu.profiler.numerics import NonFiniteError
    assert classify(wdog.PhaseTimeout("serve.step", 2, 1)) == "hang"
    assert classify(NonFiniteError("nan")) == "non_finite"
    assert classify(chaos.ChaosError("x")) == "injected"
    assert classify(RuntimeError("xla")) == "device_error"
    assert classify(OSError("io")) == "device_error"
    assert classify(ValueError("?")) == "unknown"


def test_step_that_cannot_be_lowered_raises_with_nothing_quarantined(model):
    """Recovery is for run-time faults. A step function that fails while
    being traced/lowered/compiled is a broken program: it raises out of
    ``step()`` — no recovery, no bisection, no quarantined request."""
    cfg, params = model
    eng = _engine(cfg, params)

    def refused(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    eng._model = types.SimpleNamespace(**dict(vars(eng._model),
                                              forward_paged=refused))
    rid = eng.add_request([5, 6, 7], 4)
    before = serving.serving_stats()
    with pytest.raises(RuntimeError, match="Mosaic"):
        eng.step()
    after = serving.serving_stats()
    assert after["recoveries"] == before["recoveries"]
    assert after["quarantined"] == before["quarantined"]
    assert eng.state_of(rid).value == "running"
    assert eng.error_of(rid) is None


def test_transient_step_failure_recovers_bit_identical(model, workload):
    """Injected fail@serve.step (once): pools rebuild, every stream
    replays through the unified fed/known path and finishes identical
    to the uninterrupted reference; incident + recovery metric land."""
    cfg, params = model
    prompts, n_new, expect = workload
    wdog.clear_incidents()
    before = serving.serving_stats()["recoveries"]
    eng = _engine(cfg, params)
    rids = [eng.add_request(p, n_new) for p in prompts]
    with chaos.installed(chaos.Chaos("fail@serve.step:step=2,times=1")):
        while eng.has_work():
            eng.step()
    assert [eng.output_of(r) for r in rids] == expect
    assert serving.serving_stats()["recoveries"] == before + 1
    assert serving.serving_stats()["quarantined"] == 0
    recs = [r for r in wdog.incidents()
            if r["kind"] == "serve_step_failure"]
    assert recs and recs[-1]["failure"] == "injected"
    assert recs[-1]["culprit"] is None
    assert eng.kv.allocator.num_allocated == 0


def test_poison_request_quarantined_by_bisection(model, workload):
    """fail@serve.step:rid=K keeps blaming request K: bisection
    quarantines exactly it (typed, terminal) and every other stream
    recovers bit-identical (ISSUE acceptance)."""
    cfg, params = model
    prompts, n_new, expect = workload
    eng = _engine(cfg, params)
    rids = [eng.add_request(p, n_new) for p in prompts]
    poison = rids[2]
    before = serving.serving_stats()["quarantined"]
    with chaos.installed(chaos.Chaos(f"fail@serve.step:rid={poison}")):
        steps = 0
        while eng.has_work():
            eng.step()
            steps += 1
            assert steps < 500
    assert eng.state_of(poison).value == "failed"
    assert isinstance(eng.error_of(poison), RequestQuarantined)
    assert serving.serving_stats()["quarantined"] == before + 1
    for i, rid in enumerate(rids):
        if rid != poison:
            assert eng.output_of(rid) == expect[i], f"stream {i} diverged"
    assert eng.kv.allocator.num_allocated == 0


def test_hung_step_past_watchdog_deadline_recovers(model, workload):
    """chaos hang (bounded) + a serve.step deadline below it: the
    returning-but-late device call converts to PhaseTimeout and takes
    the pool-rebuild replay path, classified as a hang (no bisection —
    probing a hang would hang recovery)."""
    cfg, params = model
    prompts, n_new, expect = workload
    wd = wdog.Watchdog(deadlines={"serve.step": 0.01}, dump=False)
    eng = _engine(cfg, params, watchdog=wd)
    rids = [eng.add_request(p, n_new) for p in prompts[:3]]
    with chaos.installed(
            chaos.Chaos("hang@serve.step:step=1,times=1,secs=0.05")):
        while eng.has_work():
            eng.step()
    assert [eng.output_of(r) for r in rids] == expect[:3]
    recs = [r for r in wdog.incidents()
            if r["kind"] == "serve_step_failure"]
    assert recs and recs[-1]["failure"] == "hang"


# ---------------------------------------------------------------------------
# router: placement, liveness, failover, drain
# ---------------------------------------------------------------------------


def _router_pair(cfg, params, **kw):
    a = _engine(cfg, params)
    b = _engine(cfg, params)
    kw.setdefault("heartbeat_timeout", 1e6)
    return serving.Router([("a", a), ("b", b)], **kw), a, b


def test_router_places_by_load_and_locality(model):
    cfg, params = model
    router, a, b = _router_pair(cfg, params)
    g1 = router.submit([1, 2, 3, 4], 2)
    g2 = router.submit([9, 8, 7, 6], 2)
    # least-loaded: the two streams land on different replicas
    assert {router._requests[g1].replica,
            router._requests[g2].replica} == {"a", "b"}
    # locality: the shared prefix beats the load tie and co-locates
    g3 = router.submit([1, 2, 3, 4], 2)
    assert (router._requests[g3].replica
            == router._requests[g1].replica)
    router.run(max_steps=200)
    assert all(router.is_finished(g) for g in (g1, g2, g3))


def test_router_kill_one_of_two_replicas_failover_bit_identical(
        model, workload):
    """ISSUE acceptance (in-process): kill 1 of 2 replicas mid-decode —
    every in-flight stream fails over and completes bit-identical to
    the uninterrupted single-engine reference, without re-streaming any
    delivered token."""
    cfg, params = model
    prompts, n_new, expect = workload
    router, a, b = _router_pair(cfg, params)
    streamed = {}

    def on_tok(gid, tok, done):
        streamed.setdefault(gid, []).append(tok)

    gids = [router.submit(p, n_new, on_token=on_tok) for p in prompts]
    before = serving.serving_stats()["failovers"]
    with chaos.installed(
            chaos.Chaos("kill@serve.replica.a.step:step=3")):
        out = router.run(max_steps=500)
    assert router.replica_states()["a"] == "dead"
    assert serving.serving_stats()["failovers"] > before
    for i, g in enumerate(gids):
        assert out[g] == expect[i], f"stream {i} diverged after failover"
        # idempotent replay: the callback saw each token exactly once
        assert streamed[g] == expect[i]
    mig = [router._requests[g].migrations for g in gids]
    assert sum(mig) > 0


def test_router_drain_migrates_and_stops_placement(model, workload):
    cfg, params = model
    prompts, n_new, expect = workload
    router, a, b = _router_pair(cfg, params)
    gids = [router.submit(p, n_new) for p in prompts[:4]]
    router.step()
    moved = router.drain("a")
    assert router.replica_states()["a"] == "draining"
    # drained replica holds nothing and receives nothing new
    g_new = router.submit(prompts[4], n_new)
    assert router._requests[g_new].replica == "b"
    assert not a.has_work()
    out = router.run(max_steps=500)
    for i, g in enumerate(gids):
        assert out[g] == expect[i]
    assert moved + sum(1 for g in gids
                       if router._requests[g].migrations == 0) >= len(gids)


def test_router_sigterm_drains(model, workload):
    cfg, params = model
    prompts, n_new, expect = workload
    router, a, b = _router_pair(cfg, params)
    gids = [router.submit(p, n_new) for p in prompts[:3]]
    prev = signal.getsignal(signal.SIGTERM)
    try:
        router.install_sigterm_drain("a")
        signal.raise_signal(signal.SIGTERM)
        assert router.replica_states()["a"] == "draining"
        out = router.run(max_steps=500)
        for i, g in enumerate(gids):
            assert out[g] == expect[i]
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_router_heartbeat_staleness_marks_dead(model):
    """Observer-clock liveness for externally-driven replicas: a beat
    counter that stalls past the timeout kills the replica and fails
    its streams over — no cross-host clock involved."""
    cfg, params = model
    clk = _FakeClock()
    a, b = _engine(cfg, params), _engine(cfg, params)
    router = serving.Router([("a", a), ("b", b)], clock=clk,
                            heartbeat_timeout=5.0)
    gid = router.submit([1, 2, 3], 4)
    victim = router._requests[gid].replica
    other = "b" if victim == "a" else "a"
    router.check_health()          # baseline observation at t=0
    clk.advance(3.0)
    router.observe_beat(other)     # other keeps beating...
    assert router.check_health() == []
    clk.advance(3.0)               # victim silent for 6s > 5s
    assert router.check_health() == [victim]
    assert router.replica_states()[victim] == "dead"
    # the stream was failed over to the survivor
    assert router._requests[gid].replica == other
    router.run(max_steps=200)
    assert router.is_finished(gid)


def test_router_no_live_replica_is_typed(model):
    cfg, params = model
    router, a, b = _router_pair(cfg, params)
    router._mark_dead("a", reason="test")
    router._mark_dead("b", reason="test")
    with pytest.raises(ReplicaUnavailable) as ei:
        router.submit([1, 2, 3], 2)
    assert ei.value.retriable


def test_router_all_replicas_shedding_propagates_rejection(model):
    cfg, params = model
    a = _engine(cfg, params, max_running=1, max_queue=1)
    b = _engine(cfg, params, max_running=1, max_queue=1)
    router = serving.Router([("a", a), ("b", b)],
                            heartbeat_timeout=1e6)
    # keep submitting until every replica sheds: the router must
    # propagate the typed retriable rejection, not crash or spin
    with pytest.raises(AdmissionRejected) as ei:
        for _ in range(10):
            router.submit([1, 2, 3], 2)
    assert ei.value.retriable


# ---------------------------------------------------------------------------
# shared machinery: HeartbeatTracker, pod_report aggregate, summary
# ---------------------------------------------------------------------------


def test_heartbeat_tracker_observer_clock_rule():
    clk = _FakeClock()
    t = HeartbeatTracker(2.0, clock=clk)
    assert t.observe("r", 0) == 0.0
    clk.advance(1.5)
    assert t.observe("r", 0) == 1.5      # counter stalled
    assert not t.is_stale("r")
    assert t.observe("r", 1) == 0.0      # progress resets silence
    clk.advance(2.5)
    assert t.observe("r", 1) == 2.5
    assert t.is_stale("r") and t.stale() == ["r"]
    t.forget("r")
    assert not t.stale()


def test_pod_report_serving_section_router_aggregate():
    import argparse

    from tools.pod_report import TPU_GENERATIONS, _serving_section
    cfg = llama.preset("llama7b")
    gen = TPU_GENERATIONS["v5p"]
    args = argparse.Namespace(seq=2048, page_size=128, replicas=4)
    plan = _serving_section(cfg, gen, args)
    assert plan["replicas"] == 4
    agg = plan["aggregate"]
    assert (agg["max_concurrent_requests"]
            == 4 * plan["max_concurrent_requests"])
    assert agg["num_pages"] == 4 * plan["num_pages"]
    # --replicas is wired into the CLI
    from tools.pod_report import _parse_args
    assert _parse_args(["--replicas", "3"]).replicas == 3


def test_serving_summary_has_resilience_lines(model):
    cfg, params = model
    _engine(cfg, params)
    text = "\n".join(serving.summary_lines())
    assert "resilience:" in text and "recoveries" in text
    assert "failovers" in text and "callback errors" in text
