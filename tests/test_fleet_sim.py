"""Fleet observatory (ISSUE 16): workload presets, service-model and
burn-rate arithmetic, the recommend-only autoscaler, and the
trace-driven discrete-event fleet simulator.

The acceptance bar: ``serving.workloads`` streams are deterministic
and preset errors enumerate every preset; the ``AdmissionGate``
hysteresis extracted from the engine behaves identically standalone;
``ServiceModel``/``SLOBurnGauge``/``ArrivalForecast`` math is exact on
an injectable clock; a flash-crowd scale-up fires in the simulator
*before* the SLO is violated; scale-down drains are idempotent under
PR 11 drain semantics; ``tools/fleet_sim.py`` is deterministic,
jax-free, rejects unknown-schema sidecars with exit 2, and agrees with
``pod_report serving --fleet-*`` on the min-replica answer; and a
2-replica simulated fleet matches a live run over the same seeded
workload exactly on admitted/shed counts, with TTFT p95 within the
stated calibration tolerance and the live SLO verdict reproduced.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.serving import AdmissionGate, autoscale, workloads

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLEET_SIM = os.path.join(REPO, "tools", "fleet_sim.py")
POD_REPORT = os.path.join(REPO, "tools", "pod_report.py")


@pytest.fixture(scope="module")
def fs():
    spec = importlib.util.spec_from_file_location(
        "_fleet_sim_under_test", FLEET_SIM)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pt(fs):
    return fs.load_paddle()


def _run_tool(path, *args, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, path, *args],
                          capture_output=True, text=True, env=env,
                          timeout=300)


# ---------------------------------------------------------------------------
# workloads: seeded synthetic arrival processes
# ---------------------------------------------------------------------------


class TestWorkloads:
    def test_deterministic_for_fixed_seed(self):
        a = workloads.generate("flash-crowd", 50, seed=3)
        b = workloads.generate("flash-crowd", 50, seed=3)
        assert a == b
        assert a != workloads.generate("flash-crowd", 50, seed=4)

    def test_unknown_preset_enumerates_every_preset(self):
        with pytest.raises(ValueError) as ei:
            workloads.validate("tsunami")
        for preset in workloads.PRESETS:
            assert preset in str(ei.value)

    @pytest.mark.parametrize("preset", workloads.PRESETS)
    def test_exact_count_sorted_and_bounded(self, preset):
        arr = workloads.generate(preset, 40, seed=1, horizon_s=30.0,
                                 prompt_len=6, max_new_tokens=4,
                                 vocab=50)
        assert len(arr) == 40
        ts = [a.t_s for a in arr]
        assert ts == sorted(ts)
        assert all(0.0 <= t <= 30.0 for t in ts)
        assert all(len(a.prompt) == 6 for a in arr)
        assert all(1 <= tok < 50 for a in arr for tok in a.prompt)

    def test_flash_crowd_spike_density_and_shared_prefix(self):
        arr = workloads.generate("flash-crowd", 400, seed=0,
                                 horizon_s=60.0, prompt_len=12)
        spike = [a for a in arr
                 if workloads.in_flash_window(a.t_s, 60.0)]
        before = [a for a in arr if 18.0 <= a.t_s < 30.0]
        # 6x intensity over the same-width window just before
        assert len(spike) > 2 * len(before)
        # everyone in the spike asks about the same hot content
        assert {a.group for a in spike} == {1}
        assert len({a.prompt[:6] for a in spike}) == 1

    def test_step_schedule_covers_every_arrival(self):
        arr = workloads.generate("bursty", 30, seed=2)
        sched = workloads.step_schedule(arr, 64)
        assert sum(len(v) for v in sched.values()) == 30
        assert all(0 <= k < 64 for k in sched)

    def test_peak_rate_exceeds_mean_for_flash_crowd(self):
        arr = workloads.generate("flash-crowd", 300, seed=0,
                                 horizon_s=60.0)
        mean = workloads.mean_rate(arr, horizon_s=60.0)
        peak = workloads.peak_rate(arr, window_s=5.0)
        assert peak > 2.0 * mean
        uni = workloads.generate("uniform", 300, seed=0,
                                 horizon_s=60.0)
        assert workloads.peak_rate(uni, 5.0) < 2.0 * workloads.mean_rate(
            uni, horizon_s=60.0)


# ---------------------------------------------------------------------------
# AdmissionGate: the engine's shedding hysteresis, standalone
# ---------------------------------------------------------------------------


def test_admission_gate_watermark_hysteresis():
    g = AdmissionGate(8)
    assert g.recover_below == 4
    assert not g.check(0)
    assert not g.check(7)          # below the watermark: open
    assert g.check(8)              # trips at max_queue
    assert g.check(5)              # still shedding above recover mark
    assert not g.check(4)          # recovers at <= max_queue // 2
    assert not g.check(7)          # and stays open until the watermark
    assert g.check(9)


# ---------------------------------------------------------------------------
# ServiceModel: capacity arithmetic + calibration
# ---------------------------------------------------------------------------


def _model(**kw):
    kw.setdefault("max_running", 8)
    kw.setdefault("chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("num_pages", 33)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_queue", 64)
    return autoscale.ServiceModel(**kw)


def test_service_model_capacity_arithmetic():
    m = _model()
    assert m.blocks_per_request == 4
    assert m.concurrency == 8            # (33-1)//4 ties max_running
    pool_bound = dataclasses.replace(m, num_pages=9)
    assert pool_bound.concurrency == 2   # (9-1)//4: pool binds
    assert m.steps_per_request(32, 8) == 2 + 7
    assert m.request_service_s(32, 8) == pytest.approx(
        2 * m.prefill_chunk_s + 7 * m.decode_step_s)
    assert m.capacity_rps(32, 8) > pool_bound.capacity_rps(32, 8)
    # mean step cost sits between the two bucket costs
    assert m.decode_step_s < m.mean_step_s(32, 8) < m.prefill_chunk_s


def test_service_model_calibrates_from_step_medians():
    samples = {1: [0.01, 0.02, 0.03], 16: [0.05, 0.07, 0.50]}
    m = autoscale.ServiceModel.from_step_samples(
        samples, max_running=8, chunk=16, page_size=16, num_pages=33,
        max_model_len=64, max_queue=64)
    assert m.calibrated
    assert m.decode_step_s == pytest.approx(0.02)
    # median, so the one-off compile outlier doesn't poison the model
    assert m.prefill_chunk_s == pytest.approx(0.07)
    m0 = autoscale.ServiceModel.from_step_samples(
        {}, max_running=8, chunk=16, page_size=16, num_pages=33,
        max_model_len=64, max_queue=64)
    assert not m0.calibrated
    assert m0.prefill_chunk_s == autoscale.DEFAULT_PREFILL_CHUNK_S
    assert m0.decode_step_s == autoscale.DEFAULT_DECODE_STEP_S


def test_replicas_for_applies_headroom():
    m = _model()
    cap = m.capacity_rps(32, 8)
    assert autoscale.replicas_for(m, 0.0, prompt_len=32,
                                  new_tokens=8) == 1
    assert autoscale.replicas_for(m, cap * 0.8, prompt_len=32,
                                  new_tokens=8) == 1
    # 1.7x capacity over 0.85 headroom needs exactly 2
    assert autoscale.replicas_for(m, cap * 1.7, prompt_len=32,
                                  new_tokens=8) == 2


def test_recommend_fleet_sizes_to_peak_not_mean():
    m = _model(num_pages=9, max_running=2, prefill_chunk_s=0.05,
               decode_step_s=0.02)
    arr = workloads.generate("flash-crowd", 300, seed=0,
                             horizon_s=60.0, prompt_len=12,
                             max_new_tokens=8)
    rec = autoscale.recommend_fleet(m, arr)
    assert rec["offered_rps_peak"] > rec["offered_rps_mean"]
    by_peak = autoscale.replicas_for(
        m, rec["offered_rps_peak"], prompt_len=rec["prompt_len"],
        new_tokens=rec["new_tokens"])
    assert rec["min_replicas"] == by_peak
    assert rec["min_replicas"] > autoscale.replicas_for(
        m, rec["offered_rps_mean"], prompt_len=rec["prompt_len"],
        new_tokens=rec["new_tokens"])


# ---------------------------------------------------------------------------
# burn gauge + forecast: window math on explicit time
# ---------------------------------------------------------------------------


def test_burn_gauge_multi_window_math():
    g = autoscale.SLOBurnGauge(windows_s=(10.0, 40.0), budget=0.05)
    assert g.burn_rates(0.0) == {10.0: None, 40.0: None}
    for t in range(10):
        g.observe(ok=(t >= 2), t=float(t))   # violations at t=0, 1
    br = g.burn_rates(9.0)
    assert br[10.0] == pytest.approx(0.2 / 0.05)   # 2/10 over budget
    # the fast window forgets the violations, the slow one still sees
    # them — the classic fast/slow confirmation pair
    br = g.burn_rates(15.0)
    assert br[10.0] == 0.0
    assert br[40.0] == pytest.approx(4.0)


def test_arrival_forecast_tracks_and_decays():
    f = autoscale.ArrivalForecast(tau_s=2.0)
    t = 0.0
    for _ in range(50):
        t += 0.1
        f.observe(t)                 # steady 10 req/s
    rate = f.rate(t)
    assert 5.0 <= rate <= 15.0
    # silence decays the estimate — an idle stream must not hold a
    # spike's rate
    assert f.rate(t + 10.0) < 1.0


def test_arrival_forecast_trend_projects_acceleration():
    f = autoscale.ArrivalForecast(tau_s=2.0)
    t, dt = 0.0, 0.5
    for _ in range(60):              # inter-arrival gap shrinking
        dt *= 0.93
        t += dt
        f.observe(t)
    assert f.forecast(t, horizon_s=5.0) > f.rate(t)


# ---------------------------------------------------------------------------
# AutoscalePolicy: injectable clock, both scale-up paths, cooldown
# ---------------------------------------------------------------------------


def _policy(model, **kw):
    kw.setdefault("slo_ttft_s", 0.2)
    kw.setdefault("prompt_len", 32)
    kw.setdefault("new_tokens", 8)
    kw.setdefault("windows_s", (5.0, 20.0))
    kw.setdefault("horizon_s", 10.0)
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("forecast_tau_s", 2.0)
    kw.setdefault("clock", lambda: 0.0)
    return autoscale.AutoscalePolicy(model, **kw)


def test_policy_forecast_scale_up_fires_without_any_violation():
    m = _model(max_running=2, num_pages=9, prefill_chunk_s=0.05,
               decode_step_s=0.02)     # capacity ~5 req/s
    pol = _policy(m)
    t = 0.0
    for _ in range(100):
        t += 0.05
        pol.observe_arrival(t=t)       # 20 req/s offered
    rec = pol.recommend(1, t=t)
    assert rec.action == "scale_up"
    assert rec.target_replicas > 1
    # no TTFT was ever observed: this is the pre-violation forecast
    # path, not the reactive burn backstop
    assert all(b is None for b in rec.burn.values())


def test_policy_reactive_burn_scale_up_and_to_dict():
    m = _model()
    pol = _policy(m)
    t = 0.0
    for _ in range(20):
        t += 1.0
        pol.observe_arrival(t=t)       # 1 req/s — well under capacity
        pol.observe_ttft(10.0, t=t)    # but every TTFT violates
    rec = pol.recommend(2, t=t)
    assert rec.action == "scale_up"
    assert rec.target_replicas == 3    # live + 1, the reactive bump
    assert "burn" in rec.reason
    d = rec.to_dict()
    assert d["burn"]["5s"] >= 2.0 and d["burn"]["20s"] >= 1.0


def test_policy_scale_down_waits_out_the_cooldown():
    m = _model()
    pol = _policy(m, cooldown_s=10.0)
    pol.observe_arrival(t=0.0)
    pol.observe_arrival(t=0.1)         # then silence: demand ~ 0
    rec1 = pol.recommend(4, t=50.0)
    assert rec1.action == "hold"       # below demand, but not yet
    rec2 = pol.recommend(4, t=55.0)
    assert rec2.action == "hold"
    rec3 = pol.recommend(4, t=61.0)    # sustained past cooldown
    assert rec3.action == "scale_down"
    assert rec3.target_replicas < 4
    assert not rec3.applied
    pol.mark_applied(rec3)
    assert rec3.applied


def test_policy_populates_fleet_stats_and_profiler_section():
    autoscale.reset_fleet_stats()
    pol = _policy(_model())
    pol.observe_arrival(t=0.0)
    pol.observe_ttft(10.0, t=0.1)      # one violation
    pol.recommend(1, t=1.0)
    s = autoscale.fleet_stats()
    assert s["policies"] == 1
    assert s["arrivals"] == 1
    assert s["ttft_samples"] == 1 and s["ttft_violations"] == 1
    assert s["recommendations"] == 1
    from paddle_tpu import profiler as prof
    table = prof.Profiler(timer_only=True).summary_table()
    assert "Fleet" in table
    assert "recommendations: 1" in table
    autoscale.reset_fleet_stats()


# ---------------------------------------------------------------------------
# simulator: flash-crowd autoscaling + drain idempotence on the real
# Router (the jax-free grafted slice)
# ---------------------------------------------------------------------------


def _sim_model(pt, **kw):
    kw.setdefault("max_running", 2)
    kw.setdefault("chunk", 8)
    kw.setdefault("page_size", 16)
    kw.setdefault("num_pages", 9)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_queue", 16)
    kw.setdefault("prefill_chunk_s", 0.05)
    kw.setdefault("decode_step_s", 0.02)
    return pt.autoscale.ServiceModel(**kw)


def test_sim_flash_crowd_scale_up_fires_before_slo_violation(fs, pt):
    model = _sim_model(pt)
    arrivals = pt.workloads.generate(
        "flash-crowd", 200, seed=0, horizon_s=60.0, prompt_len=12,
        max_new_tokens=8)
    fixed = fs.simulate(pt, model, arrivals, 1, slo_ttft_s=0.5,
                        burn_window_s=5.0)
    auto = fs.simulate(pt, model, arrivals, 1, slo_ttft_s=0.5,
                       burn_window_s=5.0, autoscale=True,
                       autoscale_apply=True)
    ups = [e for e in auto["scale_events"]
           if e["action"] == "scale_up"]
    assert ups and ups[0]["applied"]
    # the forecaster answers the spike (flash window opens at t=30)
    assert any(29.0 <= e["t_s"] <= 36.0 for e in ups)
    # the scale-up fires BEFORE any SLO violation: either capacity
    # arrived early enough that nothing violates, or the first
    # violation postdates the first provisioned replica
    if auto["first_violation_s"] is not None:
        assert auto["first_scale_up_s"] < auto["first_violation_s"]
    assert auto["ttft_violations"] <= 0.05 * auto["admitted"]
    # and it matters: the fixed single replica violates the SLO the
    # autoscaled fleet meets, then the trough is drained ahead
    assert not fixed["slo_ok"]
    assert auto["slo_ok"]
    assert auto["ttft_p95_s"] < fixed["ttft_p95_s"]
    assert any(e["action"] == "scale_down"
               for e in auto["scale_events"])


def test_sim_deterministic_in_process(fs, pt):
    model = _sim_model(pt)
    arrivals = pt.workloads.generate("bursty", 80, seed=5)
    a = fs.simulate(pt, model, arrivals, 2, slo_ttft_s=0.5)
    b = fs.simulate(pt, model, arrivals, 2, slo_ttft_s=0.5)
    assert a == b


def test_router_scale_down_drain_is_idempotent(fs, pt):
    model = _sim_model(pt)
    clock = fs.SimClock(serial=True)
    engines = [fs.SimEngine(pt, model, clock, name=f"s{i}")
               for i in range(3)]
    policy = pt.autoscale.AutoscalePolicy(
        model, slo_ttft_s=1.0, prompt_len=12, new_tokens=8,
        windows_s=(5.0, 20.0), cooldown_s=0.0, clock=clock.now)
    router = pt.router.Router(
        [(e.name, e) for e in engines], clock=clock.now,
        heartbeat_timeout=1e12, autoscaler=policy,
        autoscale_apply=True)
    policy.observe_arrival(t=0.0)
    policy.observe_arrival(t=0.1)      # then a long trough
    clock.jump_to(60.0)
    router.step()
    assert router.last_recommendation.action == "scale_down"
    assert router.last_recommendation.applied
    states = router.replica_states()
    draining = [n for n, s in states.items() if s == "draining"]
    assert len(draining) == 1
    # PR 11 drain semantics: draining an already-draining replica is
    # a no-op — nothing migrates twice, the state machine holds
    drains_before = pt.stats.STATS["drains"]
    assert router.drain(draining[0]) == 0
    assert pt.stats.STATS["drains"] == drains_before
    assert router.replica_states()[draining[0]] == "draining"


# ---------------------------------------------------------------------------
# the CLI: determinism, exit codes, jax-freedom, sidecar rejection
# ---------------------------------------------------------------------------


def test_cli_deterministic_across_runs():
    args = ("--workload", "bursty", "--requests", "60", "--seed", "7",
            "--replicas", "1-2", "--slo-ttft-s", "0.5")
    a = _run_tool(FLEET_SIM, *args)
    b = _run_tool(FLEET_SIM, *args)
    assert a.returncode == 0, a.stderr
    assert a.stdout == b.stdout


def test_cli_unknown_workload_exit_2_enumerates_presets():
    p = _run_tool(FLEET_SIM, "--workload", "tsunami")
    assert p.returncode == 2
    for preset in workloads.PRESETS:
        assert preset in p.stderr


def test_cli_rejects_unknown_schema_sidecar(tmp_path):
    from paddle_tpu.profiler import trace as real_trace
    side = tmp_path / "trace_rank0.jsonl"
    side.write_text(json.dumps({"schema": "someone.elses.trace.v9"})
                    + "\n")
    p = _run_tool(FLEET_SIM, "--trace-dir", str(tmp_path))
    assert p.returncode == 2
    assert "someone.elses.trace.v9" in p.stderr
    assert real_trace.SCHEMA in p.stderr


@pytest.mark.parametrize("payload", ["", "not json at all\n"])
def test_cli_rejects_corrupt_sidecar(tmp_path, payload):
    (tmp_path / "trace_rank0.jsonl").write_text(payload)
    p = _run_tool(FLEET_SIM, "--trace-dir", str(tmp_path))
    assert p.returncode == 2
    assert "fleet_sim: error:" in p.stderr


def test_cli_replays_a_trace_the_engine_recorded(tmp_path):
    """An engine under FLAGS_tpu_trace leaves the request arrivals and
    the step costs in the ring; written as a sidecar they are a workload
    and a calibration that ``--trace-dir`` replays."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models import llama
    from paddle_tpu.profiler import trace

    cfg = llama.preset("llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    paddle.set_flags({"FLAGS_tpu_trace": True})
    trace.clear()
    try:
        eng = serving.LLMEngine(cfg, params, max_running=4, chunk=4,
                                page_size=16, max_model_len=32)
        for a in workloads.generate("uniform", 6, prompt_len=8,
                                    max_new_tokens=4, vocab=cfg.vocab_size):
            eng.add_request(list(a.prompt), a.max_new_tokens)
        while eng.has_work():
            eng.step()
        eng.shutdown()
        side = trace.write_sidecar(trace.sidecar_path(str(tmp_path)))
    finally:
        paddle.set_flags({"FLAGS_tpu_trace": False})
        trace.clear()
    assert os.path.basename(side) == "trace_rank0.jsonl"
    p = _run_tool(FLEET_SIM, "--trace-dir", str(tmp_path),
                  "--replicas", "1-2", "--slo-ttft-s", "60")
    assert p.returncode == 0, p.stderr
    doc = json.loads(p.stdout)
    assert doc["sweep"]
    assert all(run["admitted"] == 6 for run in doc["sweep"])


def test_cli_runs_without_jax(tmp_path):
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        "raise ImportError('fleet_sim must not import jax')\n")
    p = _run_tool(FLEET_SIM, "--workload", "uniform", "--requests",
                  "20", env_extra={"PYTHONPATH": str(poison)})
    assert p.returncode == 0, p.stderr
    doc = json.loads(p.stdout)
    assert doc["tool"] == "fleet_sim"
    assert doc["sweep"]


def test_cli_exit_1_when_no_config_meets_slo():
    p = _run_tool(FLEET_SIM, "--workload", "uniform", "--requests",
                  "30", "--replicas", "1", "--slo-ttft-s", "0.001",
                  "--prefill-chunk-s", "0.05", "--decode-step-s",
                  "0.02")
    assert p.returncode == 1
    doc = json.loads(p.stdout)
    assert doc["recommended"] is None


# ---------------------------------------------------------------------------
# pod_report serving --fleet-* agrees with fleet_sim's analytic answer
# ---------------------------------------------------------------------------


def test_pod_report_fleet_block_matches_fleet_sim(tmp_path):
    rep = tmp_path / "serving.json"
    p1 = _run_tool(POD_REPORT, "serving", "--preset", "llama-debug",
                   "--mesh", "v5p-8", "--page-size", "16", "--seq",
                   "64", "--out", str(rep))
    assert p1.returncode == 0, p1.stderr
    with open(rep) as f:
        fleet = json.load(f)["serving"]["fleet"]
    assert fleet["workload"] == "diurnal"
    p2 = _run_tool(FLEET_SIM, "--workload", "diurnal", "--requests",
                   "200", "--seed", "0", "--horizon-s", "60",
                   "--prompt-len", "12", "--max-new-tokens", "8",
                   "--max-running", "8", "--chunk", "16",
                   "--max-model-len", "64", "--capacity-json",
                   str(rep), "--replicas", "1")
    assert p2.returncode == 0, p2.stderr
    run = json.loads(p2.stdout)["sweep"][0]
    # same seeded arrivals + same ServiceModel arithmetic -> the two
    # tools must return the SAME min-replica answer, exactly
    assert run["analytic_min_replicas"] == fleet["min_replicas"]
    assert run["offered_rps_peak"] == fleet["offered_rps_peak"]
    assert run["capacity_rps_per_replica"] \
        == fleet["capacity_rps_per_replica"]


# ---------------------------------------------------------------------------
# the new tool stays lint-clean (tier-1 ratchet covers paddle_tpu/;
# tools/ needs its own sweep)
# ---------------------------------------------------------------------------


def test_fleet_sim_tool_is_lint_clean():
    from paddle_tpu.analysis import ast_checks
    findings = list(ast_checks.check_paths([FLEET_SIM]))
    assert findings == [], [f"{f.rule} {f.where}: {f.message}"
                            for f in findings]


# ---------------------------------------------------------------------------
# sim vs live: the same seeded workload through real engines and the
# simulator — admission must match exactly, latency within tolerance
# ---------------------------------------------------------------------------


class TestSimVsLive:
    @pytest.fixture(autouse=True)
    def _interpret_mode(self):
        from paddle_tpu.ops import pallas_ops
        old = pallas_ops._INTERPRET
        pallas_ops._INTERPRET = True
        yield
        pallas_ops._INTERPRET = old

    @pytest.fixture(scope="class")
    def tiny(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models import llama
        cfg = llama.LlamaConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            dtype=jnp.float32, use_remat=False)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def _arrivals(self, n=60):
        return workloads.generate("flash-crowd", n, seed=0,
                                  horizon_s=60.0, prompt_len=8,
                                  max_new_tokens=6, vocab=128)

    def _live_engines(self, tiny, n_replicas, max_queue):
        from paddle_tpu import serving
        cfg, params = tiny
        engines = []
        for i in range(n_replicas):
            eng = serving.LLMEngine(cfg, params, max_running=4,
                                    chunk=4, page_size=8,
                                    max_model_len=32,
                                    max_queue=max_queue)
            # compile both buckets before the measured drive
            eng.add_request([1, 2, 3, 4], 2)
            while eng.has_work():
                eng.step()
            engines.append((f"r{i}", eng))
        return engines

    def _drive_live(self, tiny, n_replicas, sched, last, max_queue):
        from paddle_tpu import serving
        engines = self._live_engines(tiny, n_replicas, max_queue)
        router = serving.Router(engines, heartbeat_timeout=1e9)
        admitted = shed = 0
        step = 0
        while step <= last or router.has_work():
            for a in sched.get(step, ()):
                try:
                    router.submit(list(a.prompt), a.max_new_tokens)
                    admitted += 1
                except serving.AdmissionRejected:
                    shed += 1
            router.step()
            step += 1
            assert step < 5000, "live drive did not converge"
        ttfts = sorted(rr.first_token_s - rr.arrival_s
                       for rr in router._requests.values()
                       if rr.first_token_s is not None)
        return admitted, shed, ttfts, engines[0][1]

    def _drive_sim(self, fs, pt, model, n_replicas, sched, last):
        clock = fs.SimClock(serial=True)
        engines = [fs.SimEngine(pt, model, clock, name=f"r{i}")
                   for i in range(n_replicas)]
        router = pt.router.Router(
            [(e.name, e) for e in engines], clock=clock.now,
            heartbeat_timeout=1e12)
        admitted = shed = 0
        step = 0
        while step <= last or router.has_work():
            for a in sched.get(step, ()):
                try:
                    router.submit(list(a.prompt), a.max_new_tokens)
                    admitted += 1
                except pt.errors.AdmissionRejected:
                    shed += 1
            clock.begin_iteration()
            router.step()
            clock.commit_iteration()
            step += 1
            assert step < 5000, "sim drive did not converge"
        ttfts = sorted(rr.first_token_s - rr.arrival_s
                       for rr in router._requests.values()
                       if rr.first_token_s is not None)
        return admitted, shed, ttfts

    @staticmethod
    def _p95(xs):
        import numpy as np
        return float(np.percentile(np.asarray(xs, dtype=float), 95))

    def test_admitted_and_shed_match_exactly(self, fs, pt, tiny):
        """The sim runs the real Scheduler/AdmissionGate/Router, so on
        the same step-indexed submissions its admission decisions are
        the live run's decisions — not approximately, exactly."""
        arr = self._arrivals()
        sched = workloads.step_schedule(arr, 60)
        last = max(sched)
        admitted_l, shed_l, ttfts_l, eng = self._drive_live(
            tiny, 2, sched, last, max_queue=3)
        assert shed_l > 0, "workload must overload the gate"
        sm = eng.service_model()
        model = pt.autoscale.ServiceModel(
            max_running=sm.max_running, chunk=sm.chunk,
            page_size=sm.page_size, num_pages=sm.num_pages,
            max_model_len=sm.max_model_len, max_queue=sm.max_queue,
            prefill_chunk_s=sm.prefill_chunk_s,
            decode_step_s=sm.decode_step_s, calibrated=sm.calibrated)
        assert model.calibrated
        admitted_s, shed_s, ttfts_s = self._drive_sim(
            fs, pt, model, 2, sched, last)
        assert (admitted_s, shed_s) == (admitted_l, shed_l)
        assert len(ttfts_s) == len(ttfts_l)
        # latency is as good as the calibration: p95 within 3x (the
        # stated tolerance — step-time variance on a loaded CPU host
        # is the error source, admission above is exact)
        p_live, p_sim = self._p95(ttfts_l), self._p95(ttfts_s)
        assert p_live / 3.0 <= p_sim <= p_live * 3.0, \
            f"sim p95 {p_sim:.4f}s vs live {p_live:.4f}s"

    def test_min_replica_recommendation_validated_live(self, fs, pt,
                                                       tiny):
        """Pick the SLO between the live 1- and 2-replica p95s: live,
        2 replicas meet it and 1 violates it.  The simulator, anchored
        on the observed 2-replica fleet (the capacity-planning use:
        you can measure the fleet you have, the sim predicts the one
        you don't), must reproduce that verdict — shrinking to 1
        replica violates the SLO."""
        arr = self._arrivals()
        sched = workloads.step_schedule(arr, 60)
        last = max(sched)
        _, _, ttfts_1, eng = self._drive_live(tiny, 1, sched, last,
                                              max_queue=64)
        _, _, ttfts_2, _ = self._drive_live(tiny, 2, sched, last,
                                            max_queue=64)
        p1, p2 = self._p95(ttfts_1), self._p95(ttfts_2)
        assert p1 > p2, "one replica must queue worse than two"
        slo = (p1 * p2) ** 0.5        # geometric midpoint
        assert p2 <= slo < p1         # live: 2 meets, 1 violates
        sm = eng.service_model()
        model = pt.autoscale.ServiceModel(
            max_running=sm.max_running, chunk=sm.chunk,
            page_size=sm.page_size, num_pages=sm.num_pages,
            max_model_len=sm.max_model_len, max_queue=sm.max_queue,
            prefill_chunk_s=sm.prefill_chunk_s,
            decode_step_s=sm.decode_step_s, calibrated=sm.calibrated)
        _, _, sim_1 = self._drive_sim(fs, pt, model, 1, sched, last)
        _, _, sim_2 = self._drive_sim(fs, pt, model, 2, sched, last)
        s1, s2 = self._p95(sim_1), self._p95(sim_2)
        # the queueing *structure* must match: relative degradation
        # from losing a replica agrees with live within 35%
        assert abs(s1 / s2 - p1 / p2) < 0.35 * (p1 / p2), \
            f"sim degradation {s1 / s2:.2f}x vs live {p1 / p2:.2f}x"
        # one-point anchor on the fleet we actually ran (median step
        # calibration understates live tails by a host-dependent
        # constant; anchoring the deployed config removes it)
        scale = p2 / s2
        assert 1.0 / 3.0 <= scale <= 3.0, \
            "calibration drifted outside stated tolerance"
        assert scale * s1 > slo, \
            "sim must predict that shrinking to 1 replica violates"
