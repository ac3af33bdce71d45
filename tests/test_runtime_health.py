"""Runtime health layer: phase watchdogs, heartbeats, hang-aware chaos.

Reference analog: the elastic stack's heartbeat/watchdog loop
(fleet/elastic/manager.py) and the distributed runtime's op timeouts.
Everything here runs without real hangs: the Watchdog and HealthMonitor
take injected clocks, chaos sleeps are injectable, and exit-101
conversion goes through a recorded ``exit_fn`` instead of ``os._exit``.
The real cross-process hang → detect → relaunch proof lives in
tests/test_hang_recovery.py (slow tier).
"""
import pickle
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import runtime
from paddle_tpu.profiler import metrics
from paddle_tpu.runtime import health as hl
from paddle_tpu.runtime import watchdog as wd
from paddle_tpu.runtime.health import CollectiveTimeout, HealthMonitor
from paddle_tpu.runtime.watchdog import (PhaseTimeout, Watchdog,
                                         run_with_deadline)
from paddle_tpu.testing import chaos


@pytest.fixture(autouse=True)
def _clean_runtime_state():
    wd.clear_incidents()
    yield
    wd.clear_incidents()
    hl.uninstall()
    chaos.uninstall()


@pytest.fixture
def metrics_on():
    metrics.reset()
    paddle.set_flags({"FLAGS_tpu_metrics": True})
    yield
    paddle.set_flags({"FLAGS_tpu_metrics": False})
    metrics.reset()


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class _FakeStore:
    """Single-process stand-in for the TCPStore surface the monitor
    uses (set/get of bytes)."""

    def __init__(self):
        self.kv = {}

    def set(self, key, value):
        self.kv[key] = value

    def get(self, key):
        return self.kv.get(key)


# ---------------------------------------------------------------------------
# Watchdog: phase deadlines with an injected clock (no real sleeps)
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_expiry_raises_once_with_fields(self):
        clk = _FakeClock()
        w = Watchdog(clock=clk, deadlines={"compile": 5.0}, dump=False)
        w.begin("compile")
        assert w.poll() == []  # not yet due
        clk.advance(6.0)
        with pytest.raises(PhaseTimeout) as ei:
            w.poll()
        assert ei.value.phase == "compile"
        assert ei.value.deadline_s == 5.0
        assert ei.value.elapsed_s == pytest.approx(6.0)
        # a hung phase expires exactly once (the ticker would otherwise
        # dump stacks every second for the duration of the hang)
        assert w.poll() == []
        assert len(w.expired) == 1
        assert w.end("compile") == pytest.approx(6.0)

    def test_expiry_records_incident_and_callback(self):
        clk = _FakeClock()
        seen = []
        w = Watchdog(clock=clk, deadlines={"ckpt.commit": 1.0},
                     on_expire=seen.append, dump=False)
        w.begin("ckpt.commit")
        clk.advance(2.0)
        newly = w.poll(raise_on_expire=False)
        assert [e.phase for e in newly] == ["ckpt.commit"]
        assert [e.phase for e in seen] == ["ckpt.commit"]
        rec = wd.last_incident()
        assert rec["kind"] == "watchdog_expired"
        assert rec["phase"] == "ckpt.commit"
        assert rec["deadline_s"] == 1.0

    def test_phase_cm_scopes_and_disabled_deadline(self):
        clk = _FakeClock()
        w = Watchdog(clock=clk, deadlines={"first_step": 0.0}, dump=False)
        with w.phase("first_step"):
            assert w.active_phases() == ["first_step"]
            clk.advance(1e6)
            assert w.poll() == []  # deadline <= 0 disables the phase
        assert w.active_phases() == []

    def test_deadline_for_prefers_explicit_then_flag(self):
        old = paddle.get_flags(["FLAGS_tpu_watchdog_compile"])
        paddle.set_flags({"FLAGS_tpu_watchdog_compile": 12.5})
        try:
            assert Watchdog().deadline_for("compile") == 12.5
            assert Watchdog(
                deadlines={"compile": 3.0}).deadline_for("compile") == 3.0
            paddle.set_flags({"FLAGS_tpu_watchdog_compile": 0.0})
            assert Watchdog().deadline_for("compile") is None
            # phases without a flag are unwatched, not an error
            assert Watchdog().deadline_for("no-such-phase") is None
        finally:
            paddle.set_flags(old)

    def test_module_phase_hook_noop_when_flag_off(self):
        assert not paddle.get_flags(["FLAGS_tpu_watchdog"])[
            "FLAGS_tpu_watchdog"]
        with wd.phase("compile"):
            pass  # must not arm anything or require a global watchdog


class TestRunWithDeadline:
    def test_returns_value_and_reraises(self):
        assert run_with_deadline(lambda: 41 + 1, 5.0) == 42
        with pytest.raises(ValueError, match="boom"):
            run_with_deadline(
                lambda: (_ for _ in ()).throw(ValueError("boom")), 5.0)

    def test_timeout_raises_phase_timeout(self, metrics_on):
        with pytest.raises(PhaseTimeout) as ei:
            run_with_deadline(lambda: time.sleep(30), 0.05,
                              phase="measure", dump=False)
        assert ei.value.phase == "measure"
        rec = wd.last_incident()
        assert rec["kind"] == "watchdog_expired"
        assert rec["phase"] == "measure"
        assert rec["detail"] == "run_with_deadline"
        snap = metrics.snapshot()
        assert snap['watchdog_expired_total{phase="measure"}'] == 1


# ---------------------------------------------------------------------------
# chaos: hang/stall actions, gang-aware rank/restart gating
# ---------------------------------------------------------------------------

class TestHangChaos:
    def test_parse_hang_stall_options(self):
        r = chaos.Rule.parse("hang@collective.all_reduce:step=3,restart=0")
        assert (r.action, r.point, r.step, r.restart, r.secs) == (
            "hang", "collective.all_reduce", 3, 0, None)
        assert chaos.Rule.parse("stall@store.get:secs=0.5").secs == 0.5
        # sleep_s kept as a spelling alias for secs
        assert chaos.Rule.parse("hang@p:sleep_s=2").secs == 2.0
        assert chaos.Rule.parse("hang@p:rank=1").rank == 1
        with pytest.raises(ValueError, match="unknown chaos option"):
            chaos.Rule.parse("hang@p:bogus=1")

    def test_infinite_hang_sleeps_in_chunks(self, monkeypatch):
        naps = []

        def fake_sleep(s):
            naps.append(s)
            if len(naps) >= 3:
                raise KeyboardInterrupt  # test-only escape from "forever"

        monkeypatch.setattr(chaos, "_SLEEP", fake_sleep)
        with chaos.installed("hang@p"):
            with pytest.raises(KeyboardInterrupt):
                chaos.chaos_point("p")
        assert naps == [chaos._HANG_CHUNK_S] * 3

    def test_bounded_hang_and_stall_return(self, monkeypatch):
        naps = []
        monkeypatch.setattr(chaos, "_SLEEP", naps.append)
        with chaos.installed("hang@p:secs=2;stall@q;stall@r:secs=0.25") as c:
            chaos.chaos_point("p")
            chaos.chaos_point("q")
            chaos.chaos_point("r")
        assert naps == [2.0, 1.0, 0.25]
        assert [a for _, _, a in c.log] == ["hang", "stall", "stall"]

    def test_rank_and_restart_gating(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        monkeypatch.setenv("PADDLE_RESTART_COUNT", "0")
        with chaos.installed("raise@p:rank=0"):
            chaos.chaos_point("p")  # other rank: no fire
        with chaos.installed("raise@p:restart=1"):
            chaos.chaos_point("p")  # other generation: no fire
        with chaos.installed("raise@p:rank=1,restart=0"):
            with pytest.raises(chaos.ChaosError):
                chaos.chaos_point("p")


# ---------------------------------------------------------------------------
# store.wait timeout (TCPStore(timeout=...) honored on the py fallback)
# ---------------------------------------------------------------------------

class TestStoreWaitTimeout:
    def test_pystore_wait_honors_store_timeout(self):
        from paddle_tpu.distributed.store import _PyStore
        s = _PyStore("127.0.0.1", 0, True, 0.1)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match=r"timed out after 0.1s"):
            s.wait("test-runtime-health-missing-key")
        assert time.monotonic() - t0 < 5.0
        # per-call override beats the store default
        with pytest.raises(TimeoutError, match=r"timed out after 0.0s"):
            s.wait("test-runtime-health-missing-key", timeout=0.01)
        s.set("test-runtime-health-k", b"v")
        assert s.wait("test-runtime-health-k") == b"v"


# ---------------------------------------------------------------------------
# HealthMonitor: detection logic with fake store/clock/exit
# ---------------------------------------------------------------------------

def _mon(store, rank, world, clk, exits, **kw):
    kw.setdefault("collective_deadline", 3.0)
    kw.setdefault("heartbeat_timeout", 5.0)
    return HealthMonitor(store, rank, world, job_id="t", restart=0,
                         clock=clk, exit_fn=exits.append, dump=False,
                         **kw)


class TestHealthMonitor:
    def test_beat_publishes_payload_and_beacon(self):
        store, clk, exits = _FakeStore(), _FakeClock(), []
        m = _mon(store, 0, 2, clk, exits)
        m.set_step(7)
        m.beat()
        payload = pickle.loads(store.get("health/t/0/hb/0"))
        assert payload["n"] == 1 and payload["step"] == 7
        assert payload["coll"] is None
        with m.collective("all_reduce"):
            payload = pickle.loads(store.get("health/t/0/hb/0"))
            assert payload["coll"]["op"] == "all_reduce"
        payload = pickle.loads(store.get("health/t/0/hb/0"))
        assert payload["coll"] is None and not exits

    def test_self_collective_timeout_converts_to_exit_101(self):
        store, clk, exits = _FakeStore(), _FakeClock(), []
        saved = []
        m = _mon(store, 1, 2, clk, exits)
        m.register_final_save(lambda: saved.append(True))
        cm = m.collective("all_reduce")
        cm.__enter__()  # main thread "hangs" inside the op
        clk.advance(4.0)  # past the 3s deadline
        found = m.check()
        assert exits == [hl.RELAUNCH_EXIT_CODE]
        assert saved == [True]
        assert found[0]["kind"] == "collective_timeout"
        assert found[0]["op"] == "all_reduce"
        assert "all_reduce" in m.failed
        # first detector propagates the gang-wide fail flag
        why = pickle.loads(store.get("health/t/0/fail"))
        assert why["rank"] == 1
        # conversion is idempotent: a second detection cannot exit twice
        m.check()
        assert exits == [hl.RELAUNCH_EXIT_CODE]
        cm.__exit__(None, None, None)

    def test_peer_follows_gang_fail_flag(self):
        store, clk, exits = _FakeStore(), _FakeClock(), []
        store.set("health/t/0/fail", pickle.dumps(
            {"reason": "rank 1 hung", "rank": 1, "t": 0.0}))
        m = _mon(store, 0, 2, clk, exits)
        m.check()
        assert exits == [hl.RELAUNCH_EXIT_CODE]
        assert "rank 1" in m.failed

    def test_dead_rank_detected_by_silent_heartbeat(self):
        store, clk, exits0 = _FakeStore(), _FakeClock(), []
        m0 = _mon(store, 0, 2, clk, exits0)
        m1 = _mon(store, 1, 2, clk, [])
        m1.beat()
        m0.check()  # registers peer counter at t=0
        clk.advance(6.0)  # > 5s heartbeat_timeout, no new beat
        found = m0.check()
        assert exits0 == [hl.RELAUNCH_EXIT_CODE]
        assert found[0]["kind"] == "rank_dead" and found[0]["peer"] == 1
        assert m0.dead == {1}

    def test_live_peer_is_not_declared_dead(self):
        store, clk, exits0 = _FakeStore(), _FakeClock(), []
        m0 = _mon(store, 0, 2, clk, exits0)
        m1 = _mon(store, 1, 2, clk, [])
        for _ in range(4):
            m1.beat()
            m0.check()
            clk.advance(4.0)  # under the 5s timeout between beats
        assert exits0 == [] and m0.dead == set()

    def test_peer_beacon_aging_detected(self):
        store, clk, exits0 = _FakeStore(), _FakeClock(), []
        m0 = _mon(store, 0, 2, clk, exits0)
        # peer advertised entering a collective 10 wall-seconds ago and
        # never exited (beacon age uses wall time: "since" crosses hosts)
        store.set("health/t/0/hb/1", pickle.dumps(
            {"n": 1, "step": 3, "phase": None, "t": time.time(),
             "coll": {"op": "all_gather", "seq": 1,
                      "since": time.time() - 10.0}}))
        found = m0.check()
        assert exits0 == [hl.RELAUNCH_EXIT_CODE]
        assert found[0]["kind"] == "collective_timeout"
        assert found[0]["op"] == "all_gather" and found[0]["peer"] == 1

    def test_straggler_soft_flag_no_exit(self):
        store, clk, exits0 = _FakeStore(), _FakeClock(), []
        m0 = _mon(store, 0, 2, clk, exits0, straggler_skew=2)
        m0.set_step(10)
        store.set("health/t/0/hb/1", pickle.dumps(
            {"n": 1, "step": 1, "phase": None, "t": time.time(),
             "coll": None}))
        found = m0.check()
        assert exits0 == []  # skew is a precursor, not a failure
        assert m0.stragglers == {1}
        assert found[0]["kind"] == "straggler" and found[0]["skew"] == 9
        # the peer catches up: flag clears
        store.set("health/t/0/hb/1", pickle.dumps(
            {"n": 2, "step": 10, "phase": None, "t": time.time(),
             "coll": None}))
        m0.check()
        assert m0.stragglers == set()

    def test_collective_beacon_hook_is_noop_without_monitor(self):
        assert not hl.monitored()
        with hl.collective_beacon("all_reduce"):
            pass
        assert hl.current_step() is None

    def test_collective_wires_beacon_and_step(self):
        store, clk, exits = _FakeStore(), _FakeClock(), []
        m = hl.install(_mon(store, 0, 1, clk, exits))
        try:
            hl.set_step(5)
            assert hl.current_step() == 5
            t = paddle.to_tensor(np.float32(1.0))
            from paddle_tpu.distributed import all_reduce
            all_reduce(t)  # eager 1-rank path, through the beacon
            payload = pickle.loads(store.get("health/t/0/hb/0"))
            assert payload["coll"] is None  # exited cleanly
            assert payload["n"] >= 2  # entry + exit beats
        finally:
            hl.uninstall()


# ---------------------------------------------------------------------------
# no degradation: a kernel that fails is an error, never the jnp reference
# ---------------------------------------------------------------------------

def test_failed_kernel_raises_instead_of_serving_reference(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops as po

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(po, "_INTERPRET", True)
    monkeypatch.setattr(po, "_int8_matmul_call", refuse)
    monkeypatch.setattr(po, "_fused_mlp_call", refuse)
    wq, ws = po.quantize_int8(jnp.ones((128, 128), jnp.float32))
    with pytest.raises(RuntimeError, match="Mosaic"):
        po.int8_matmul(jnp.ones((8, 128), jnp.float32), wq, ws)
    w = jnp.ones((256, 256), jnp.float32)
    with pytest.raises(RuntimeError, match="Mosaic"):
        po.fused_mlp_block(jnp.ones((1, 256, 256), jnp.float32),
                           jnp.ones((256,), jnp.float32), w, w, w)
    assert wd.last_incident() is None


# ---------------------------------------------------------------------------
# reporting: Profiler "Health" section, incidents summary
# ---------------------------------------------------------------------------

class TestHealthReporting:
    def test_summary_without_monitor(self):
        lines = runtime.summary_lines()
        assert lines[0] == "Health"
        assert "monitor: not installed" in lines[1]
        assert "incidents: none" in lines[-1]

    def test_summary_with_monitor_and_incidents(self):
        store, clk = _FakeStore(), _FakeClock()
        hl.install(_mon(store, 0, 4, clk, []))
        wd.record_incident("collective_timeout", op="all_reduce", peer=2)
        lines = runtime.summary_lines()
        assert any("rank 0/4" in ln for ln in lines)
        assert any("collective_timeout" in ln and "op=all_reduce" in ln
                   for ln in lines)

    def test_profiler_summary_table_has_health_section(self):
        from paddle_tpu import profiler as prof
        p = prof.Profiler(timer_only=True)
        p.start()
        p.stop()
        table = p.summary_table()
        assert "Health" in table
        assert "monitor: not installed" in table
