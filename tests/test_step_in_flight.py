"""One step in flight: ``LLMEngine.step`` dispatches step N+1, planned from
the scheduler's counts, before it fetches step N.  The order is held, token
for token, to the order that fetches every step before it plans the next
(depth 0): the one an engine with a draft model or a prefix cache keeps, and
the one a test gives any engine by naming a reason in ``eng._sync``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import serving
from paddle_tpu.models import deepseek_v2, jamba, llama, phi4flash
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.scheduler import Request, RequestState, Scheduler
from paddle_tpu.testing import chaos

from test_token_major import with_budget


@pytest.fixture(scope="module")
def model():
    cfg = llama.preset("llama-debug")
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def engine(model, depth0=False, budget=None, **kw):
    cfg, params = model[:2]
    kw = dict(dict(max_running=4, chunk=4, page_size=8, max_model_len=64),
              **kw)
    eng = serving.LLMEngine(cfg, params, **kw)
    if budget is not None:
        eng = with_budget(eng, budget)
    if depth0:
        eng._sync = "test"
    return eng


def prompts_of(*lengths, seed=0, vocab=120):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).tolist() for n in lengths]


def drain(eng):
    calls = 0
    while eng.has_work():
        eng.step()
        calls += 1
        assert calls < 2000
    return calls


def served(model, requests, depth0=False, **kw):
    """Streams of ``requests`` (``(prompt, n_new[, eos])``) and what the
    run added to the stats, on a fresh engine."""
    eng = engine(model, depth0=depth0, **kw)
    before = serving.serving_stats()
    rids = [eng.add_request(r[0], r[1], eos_token_id=(r[2:] or [None])[0])
            for r in requests]
    drain(eng)
    after = serving.serving_stats()
    assert eng.kv.audit()["ok"]
    assert eng.kv.prefix or eng.kv.allocator.num_allocated == 0
    assert eng.scheduler.in_flight == 0 and eng._flight is None
    assert all(eng._requests[r].pending == 0 for r in rids)
    eng.shutdown()
    return [eng.output_of(r) for r in rids], \
        {k: after[k] - before.get(k, 0) for k in after}


MIXED = [(p, 5 + i) for i, p in enumerate(
    prompts_of(5, 13, 3, 9, 17, 6, 11, seed=3))]


# -- the order ----------------------------------------------------------------

def test_mixed_and_deferred_rows_serve_the_streams_of_depth_0(model):
    """Seven requests on four slots, a budget of 8 fed tokens a step:
    prefill chunks beside decode rows, rows the budget defers, slots that
    are taken again."""
    want, sync = served(model, MIXED, depth0=True, budget=8)
    got, stats = served(model, MIXED, budget=8)
    assert got == want and [len(o) for o in got] == [r[1] for r in MIXED]
    assert sync["pipelined_steps"] == 0 and \
        sync["pipeline_drains"] == sync["pipeline_drains.test"] == \
        sync["steps"]
    # every step but the first was dispatched behind the one before it
    assert stats["pipelined_steps"] == stats["steps"] - 1 > 20
    assert stats["pipeline_drains"] == stats["discarded_tokens"] == 0
    assert stats["deferred_rows"] > 0 and sync["deferred_rows"] > 0
    # the same tokens fed, each once
    for key in ("prefill_tokens", "decode_tokens", "requests_finished"):
        assert stats[key] == sync[key]


def test_step_returns_what_finished_at_the_step_it_fetched(model):
    eng = engine(model)
    rid = eng.add_request([5, 6, 7], 2)         # prefill, then one decode
    assert eng.has_work() and eng._flight is None
    assert eng.step() == []                     # dispatches the prefill
    assert eng._flight is not None and eng.has_work()
    assert eng.output_of(rid) == []             # its token is on the device
    assert eng._requests[rid].pending == 1
    assert eng.step() == []                     # decode behind it; token 1
    assert len(eng.output_of(rid)) == 1 and eng._requests[rid].ending
    # the request is fed nothing more: this call only fetches
    assert eng.scheduler.has_work() and eng.step() == [rid]
    assert eng.state_of(rid) is RequestState.FINISHED
    assert not eng.has_work() and eng.step() == []
    eng.shutdown()


def test_a_request_that_ends_by_eos_has_its_next_row_discarded(model):
    (plain,), _ = served(model, [(MIXED[1][0], 8)], depth0=True)
    eos = plain[3]
    cut = plain[:plain.index(eos) + 1]
    requests = [(MIXED[1][0], 8, eos), (MIXED[2][0], 6)]
    want, sync = served(model, requests, depth0=True)
    got, stats = served(model, requests)
    assert got == want and got[0] == cut and len(got[1]) == 6
    # the request was fed once more before its end was known: that row's
    # token is thrown away.  The one that ends by max_new_tokens is not
    assert stats["discarded_tokens"] == 1 and sync["discarded_tokens"] == 0
    assert stats["decode_tokens"] == sync["decode_tokens"]


def test_page_pressure_drains_and_preempts_as_depth_0_does(model):
    """Three pages short of what the rows grow to: the plan that would
    have to preempt waits for the step in flight."""
    requests = [(p, 12) for p in prompts_of(7, 6, 5, seed=4)]
    kw = dict(max_running=3, num_pages=7)
    want, sync = served(model, requests, depth0=True, **kw)
    got, stats = served(model, requests, **kw)
    assert got == want and all(len(o) == 12 for o in got)
    assert stats["requests_preempted"] == sync["requests_preempted"] > 0
    assert stats["pipeline_drains"] == \
        stats["pipeline_drains.page_pressure"] > 0
    assert stats["pipelined_steps"] > 0 and stats["discarded_tokens"] == 0


def test_a_plan_that_only_waits_announces_no_row_again(model):
    """``admitted`` request events: one a request and one a re-admission
    after a preemption, as at depth 0.  The plan that drains names no row,
    and the rows of the plan after it were admitted long before."""
    import paddle_tpu as paddle
    from paddle_tpu.profiler import trace
    requests = [(p, 12) for p in prompts_of(7, 6, 5, seed=4)]
    kw = dict(max_running=3, num_pages=7)
    admitted = {}
    paddle.set_flags({"FLAGS_tpu_trace": True})
    try:
        for depth0 in (True, False):
            trace.clear()
            _, stats = served(model, requests, depth0=depth0, **kw)
            events = [e for e in trace.events()
                      if e.get("name") == "serve/admitted"]
            # (a victim seated again by the plan that preempted it never
            # left two plans running, and is not announced)
            assert 3 < len(events) <= 3 + stats["requests_preempted"]
            assert sum(not e["readmission"] for e in events) == 3
            admitted[depth0] = len(events)
            drains = stats["pipeline_drains.page_pressure"]
        assert drains > 0 and admitted[False] == admitted[True]
    finally:
        paddle.set_flags({"FLAGS_tpu_trace": False})
        trace.clear()


def test_cancel_and_a_deadline_with_the_row_in_flight(model):
    want, _ = served(model, MIXED[:4], depth0=True)
    now = [0.0]
    eng = engine(model, clock=lambda: now[0])
    before = serving.serving_stats()
    rids = [eng.add_request(p, n, deadline_s=(5.0 if i == 1 else None))
            for i, (p, n) in enumerate(MIXED[:4])]
    for _ in range(6):
        eng.step()
    assert all(eng._requests[r].pending for r in rids[:2])
    assert eng.cancel(rids[0])      # takes effect at once, a step in flight
    assert eng.state_of(rids[0]) is RequestState.CANCELLED
    assert eng.kv.audit()["ok"]
    now[0] = 10.0                   # rids[1] expires at the next schedule
    drain(eng)
    assert isinstance(eng.error_of(rids[1]), serving.DeadlineExceeded)
    stats = serving.serving_stats()
    # each had a decode row in the step in flight
    assert stats["discarded_tokens"] - before["discarded_tokens"] == 2
    got = [eng.output_of(r) for r in rids]
    assert got[2:] == want[2:]
    for cut, whole in zip(got[:2], want[:2]):
        assert 0 < len(cut) < len(whole) and cut == whole[:len(cut)]
    assert eng.kv.audit()["ok"] and eng.kv.allocator.num_allocated == 0
    eng.shutdown()


def test_the_benchmarks_ending_cancel_audit_shutdown_with_a_step_in_flight(
        model):
    eng = engine(model)
    rids = [eng.add_request(p, n) for p, n in MIXED[:4]]
    for _ in range(5):
        eng.step()
    assert eng._flight is not None
    for rid in rids:
        eng.cancel(rid)
    assert eng.kv.audit()["ok"] and eng.kv.allocator.num_allocated == 0
    assert eng._lower(1).as_text() and eng._lower(eng.chunk).as_text()
    eng.shutdown()


def test_cancelling_its_last_row_drops_the_step_in_flight(model):
    """What ``Router.drain`` does to a replica: nobody waits for the step in
    flight once every row of it is cancelled, so the engine has no work
    left, with no further ``step()``; the chain of pools goes on."""
    (want,), _ = served(model, [MIXED[4]], depth0=True)
    eng = engine(model)
    rids = [eng.add_request(p, n) for p, n in MIXED[:3]]
    for _ in range(6):
        eng.step()
    rows = len(eng._flight.plan.seqs)
    assert rows == 3 and all(s.produces for s in eng._flight.plan.seqs)
    before = serving.serving_stats()
    assert eng.cancel(rids[0]) and eng.cancel(rids[1])
    assert eng.has_work() and eng._flight is not None   # rids[2] waits
    assert eng.cancel(rids[2])
    assert not eng.has_work() and eng._flight is None
    assert eng.scheduler.in_flight == 0
    stats = serving.serving_stats()
    assert stats["discarded_tokens"] - before["discarded_tokens"] == rows
    assert stats["steps"] - before["steps"] == 1        # it ran, all the same
    assert eng.step() == [] and eng.kv.audit()["ok"]
    # the next request starts behind the dropped step, on the same pools
    rid = eng.add_request(*MIXED[4])
    drain(eng)
    assert eng.output_of(rid) == want
    eng.shutdown()


# -- recovery -------------------------------------------------------------------

@pytest.mark.parametrize("where", ["dispatch", "fetch"])
def test_a_fault_with_a_step_in_flight_replays_the_same_streams(
        model, where, monkeypatch):
    want, _ = served(model, MIXED[:5], depth0=True)
    eng = engine(model)
    rids = [eng.add_request(p, n) for p, n in MIXED[:5]]
    before = serving.serving_stats()
    for _ in range(4):
        eng.step()
    assert eng._flight is not None and any(
        eng._requests[r].pending for r in rids)
    if where == "dispatch":
        with chaos.installed(chaos.Chaos("fail@serve.step:times=1")):
            assert eng.step() == []
    else:
        fetch = engine_mod.LLMEngine._fetch

        def failing(flight):
            monkeypatch.setattr(engine_mod.LLMEngine, "_fetch",
                                staticmethod(fetch))
            raise RuntimeError("device lost at fetch")
        monkeypatch.setattr(engine_mod.LLMEngine, "_fetch",
                            staticmethod(failing))
        assert eng.step() == []
    # everything in flight was dropped with the pools
    assert eng._flight is None and eng.scheduler.in_flight == 0
    assert all(eng._requests[r].pending == 0 and eng._requests[r].fed == 0
               for r in rids)
    drain(eng)
    stats = serving.serving_stats()
    assert stats["recoveries"] == before["recoveries"] + 1
    assert stats["quarantined"] == before["quarantined"]
    assert [eng.output_of(r) for r in rids] == want
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_recovery_does_not_blame_a_row_cancelled_in_flight(
        model, monkeypatch):
    """The step whose fetch fails carries a row of a request cancelled
    since: it has had its terminal event, so it is neither probed nor
    quarantined, however guilty a probe would find it."""
    want, _ = served(model, MIXED[:3], depth0=True)
    eng = engine(model)
    rids = [eng.add_request(p, n) for p, n in MIXED[:3]]
    for _ in range(5):
        eng.step()
    assert rids[0] in [s.request.rid for s in eng._flight.plan.seqs]
    assert eng.cancel(rids[0]) and eng._flight is not None
    before = serving.serving_stats()
    fetch = engine_mod.LLMEngine._fetch

    def failing(flight):
        monkeypatch.setattr(engine_mod.LLMEngine, "_fetch",
                            staticmethod(fetch))
        raise RuntimeError("device lost at fetch")
    monkeypatch.setattr(engine_mod.LLMEngine, "_fetch",
                        staticmethod(failing))
    with chaos.installed(chaos.Chaos(f"fail@serve.step:rid={rids[0]}")):
        assert eng.step() == []
        drain(eng)
    stats = serving.serving_stats()
    assert stats["recoveries"] == before["recoveries"] + 1
    assert stats["quarantined"] == before["quarantined"]
    assert eng.state_of(rids[0]) is RequestState.CANCELLED
    assert eng.error_of(rids[0]) is None
    assert [eng.output_of(r) for r in rids[1:]] == want[1:]
    assert eng.kv.audit()["ok"]
    eng.shutdown()


def test_a_killed_replica_propagates_from_the_dispatch(model):
    eng = engine(model)
    eng.add_request([1, 2, 3], 4)
    eng.step()
    with chaos.installed(chaos.Chaos("kill@serve.step:times=1")):
        with pytest.raises(chaos.ReplicaKilled):
            eng.step()
    eng.shutdown()


# -- the other models ---------------------------------------------------------

def _model_of(module, name):
    cfg = module.preset(name, dtype=jnp.float32)
    return cfg, module.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("module, name", [
    (jamba, "jamba-debug"), (phi4flash, "phi4flash-debug"),
    (deepseek_v2, "deepseek-v2-debug")],
    ids=["recurrent_state", "rings_and_shared_pool", "latent_and_experts"])
def test_models_with_state_rings_or_latents_serve_the_streams_of_depth_0(
        module, name):
    """State a slot that only moves forward, window rings, a latent pool
    whose experts the device counts: five requests on three slots, so that
    slots are reused by first chunks that reset them."""
    other = _model_of(module, name)
    requests = [(p, 5) for p in prompts_of(5, 37, 16, 50, 23, seed=6,
                                           vocab=256)]
    kw = dict(max_running=3, chunk=16, page_size=16, max_model_len=128)
    want, sync = served(other, requests, depth0=True, **kw)
    got, stats = served(other, requests, **kw)
    assert got == want
    assert stats["pipelined_steps"] == stats["steps"] - 1 > 0
    assert stats["pipeline_drains"] == 0
    for key in getattr(module, "device_counts", ()):
        # fetched a step late, every step's all the same
        assert stats[key] > 0 and key in sync


# -- engines that cannot plan on counts -----------------------------------------

def test_a_prefix_cache_engine_fetches_before_it_plans(model):
    shared = prompts_of(20, seed=9)[0]
    requests = [(shared + [7, 8], 4), (shared + [9], 4), (shared, 3)]
    want, _ = served(model, requests, depth0=True)
    got, stats = served(model, requests, prefix_cache=True,
                        max_running=1)
    assert got == want and stats["prefix_hit_tokens"] > 0
    assert stats["pipelined_steps"] == 0
    assert stats["pipeline_drains.prefix_cache"] == stats["steps"] > 0


def test_a_spec_engine_fetches_before_it_plans(model):
    cfg, params = model
    requests = MIXED[:3]
    want, _ = served(model, requests, depth0=True)
    got, stats = served(model, requests,
                        spec=serving.SpecDecodeConfig(cfg, params, k=2))
    assert got == want and stats["spec_accepted"] > 0
    assert stats["pipelined_steps"] == 0
    assert stats["pipeline_drains.spec"] == stats["steps"] > 0


def test_the_constructor_has_no_argument_for_the_order():
    import inspect
    assert list(inspect.signature(serving.LLMEngine.__init__).parameters) == [
        "self", "cfg", "params", "max_running", "chunk", "page_size",
        "num_pages", "max_model_len", "kv_dtype", "donate_pools", "clock",
        "max_queue", "slo", "watchdog", "prefix_cache", "spec"]


# -- the step's cost to the service -------------------------------------------

class Ticks:
    """A clock that moves one second at every reading."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_step_wall_s_at_depth_0_is_uploads_to_fetch_as_it_was(model):
    """The old definition: the clock read before the uploads and after the
    fetch, with no reading between them, so one tick a step."""
    eng = engine(model, depth0=True, clock=Ticks())
    eng.add_request([3, 4, 5, 6, 7], 3)
    drain(eng)
    # a chunk of 4, the prompt's last token, two decode steps
    assert eng._step_wall_s == {4: [1.0], 1: [1.0, 1.0, 1.0]}
    eng.shutdown()


def test_step_wall_s_with_a_step_in_flight_is_its_time_at_the_head(model):
    """From the later of its own dispatch and the fetch before it.  A call
    reads the clock for deadlines, for the new step's start and at the
    fetch.  The first step held the head from its own start (the second
    reading) to the next call's fetch (the fifth): 3.  A step dispatched
    behind another from that one's fetch to its own, a call's three
    readings later; the last is fetched by a call that dispatches nothing,
    two readings later."""
    clock = Ticks()
    eng = engine(model, clock=clock)
    eng.add_request([3, 4, 5, 6, 7], 3)
    drain(eng)
    assert eng._step_wall_s == {4: [3.0], 1: [3.0, 3.0, 2.0]}
    model_of = eng.service_model()
    assert model_of.calibrated and model_of.decode_step_s == 3.0
    eng.shutdown()


# -- the scheduler's two moments ------------------------------------------------

def scheduler(num_pages=16, **kw):
    kv = PagedKVCache(num_pages, 4, 8)
    return Scheduler(kv, **dict(dict(max_running=2, chunk=4), **kw))


def test_dispatch_advances_counts_and_complete_brings_the_values():
    s = scheduler()
    req = Request(prompt=[1, 2, 3], max_new_tokens=3)
    s.add(req)
    first = s.schedule()
    s.dispatch(first)
    assert (req.fed, req.pending, req.output, s.in_flight) == (3, 1, [], 1)
    assert s.kv.num_tokens(req.rid) == 0        # registered at complete
    second = s.schedule()                       # planned on counts alone
    (row,) = second.seqs
    assert (row.q_len, row.seq_len, row.produces) == (1, 4, True)
    s.dispatch(second)
    assert (req.fed, req.pending, s.in_flight) == (4, 2, 2)
    assert s.complete(first, {row.slot: 9}, now_s=1.0) == []
    assert (req.output, req.pending, req.first_token_s) == ([9], 1, 1.0)
    assert s.kv.num_tokens(req.rid) == 3
    # the token in flight is the last: nothing more is fed
    assert not req.ending
    third = s.schedule()
    s.dispatch(third)
    assert req.ending and s.schedule().seqs == []
    s.complete(second, {row.slot: 8})
    assert s.complete(third, {row.slot: 7}) == [req]
    assert req.output == [9, 8, 7] and req.state is RequestState.FINISHED
    assert s.in_flight == 0 and not s.has_work()


def test_the_tools_read_a_steps_cost_and_pass_over_a_call_that_landed_none(
        model, tmp_path):
    """``tools/fleet_sim.py`` and ``tools/trace_report.py`` on a sidecar of
    a drained engine: the samples are ``_step_wall_s``, not the spans' own
    lengths, and the call that only dispatched adds none."""
    import importlib.util
    import os
    import paddle_tpu as paddle
    from paddle_tpu.profiler import trace
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")

    def tool(name):
        spec = importlib.util.spec_from_file_location(
            f"_{name}_under_test", os.path.join(tools, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    paddle.set_flags({"FLAGS_tpu_trace": True})
    trace.clear()
    try:
        eng = engine(model)
        for p, n in MIXED[:3]:
            eng.add_request(p, n)
        drain(eng)
        eng.shutdown()
        trace.write_sidecar(trace.sidecar_path(str(tmp_path)))
        events = trace.events()
    finally:
        paddle.set_flags({"FLAGS_tpu_trace": False})
        trace.clear()
    samples = {b: sorted(ts) for b, ts in eng._step_wall_s.items()}
    n = sum(map(len, samples.values()))
    spans = [e for e in events if e.get("name") == "serve/step"]
    assert len(spans) == n + 1 and spans[0]["landed"] == 0
    fs = tool("fleet_sim")
    _, steps = fs.load_trace(fs.load_paddle(), str(tmp_path))
    assert {b: sorted(ts) for b, ts in steps.items()} == samples
    report = tool("trace_report").step_stats(events)["serve/step"]
    assert report["count"] == n
    assert report["mean_s"] == pytest.approx(
        sum(t for ts in samples.values() for t in ts) / n)


def test_a_plan_that_would_preempt_waits_for_the_step_in_flight():
    s = scheduler(num_pages=4, max_running=2)       # 3 usable pages of 4
    old = Request(prompt=[1, 2, 3, 4], max_new_tokens=8)
    young = Request(prompt=[5, 6, 7], max_new_tokens=8)
    s.add(old)
    s.add(young)
    plan = s.schedule()
    s.dispatch(plan)                # a page each; both sample a token
    ahead = s.schedule()            # old needs a second page: one is free
    s.dispatch(ahead)
    s.complete(plan, {0: 11, 1: 12})
    one_more = s.schedule()         # young crosses its page: none is free
    assert one_more.drain == "page_pressure" and one_more.seqs == [] and \
        one_more.preempted == []
    assert young.state is RequestState.RUNNING and young.pending == 1
    s.complete(ahead, {0: 13, 1: 14})
    again = s.schedule()            # nothing in flight: today's preemption
    assert again.drain is None and again.preempted == [old]
    assert (old.fed, old.pending, old.output) == (0, 0, [11, 13])
    # young has its page, and old, seated again at once, replays from 0
    rows = {q.request.rid: q for q in again.seqs}
    assert rows[young.rid].seq_len == 5
    assert (rows[old.rid].q_len, rows[old.rid].seq_len) == (4, 4)


def test_a_row_whose_request_left_its_slot_is_skipped():
    s = scheduler()
    req = Request(prompt=[1, 2], max_new_tokens=4, eos_token_id=5)
    s.add(req)
    first = s.schedule()
    s.dispatch(first)
    second = s.schedule()
    s.dispatch(second)
    assert s.complete(first, {0: 5}) == [req]       # the end, by value
    assert not s.holds(second.seqs[0])
    assert s.complete(second, {0: 9}) == [] and req.output == [5]
    assert s.kv.audit()["ok"] and s.kv.allocator.num_allocated == 0
