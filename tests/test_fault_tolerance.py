"""Fault tolerance: crash-consistent commits, preemption, chaos harness.

Reference analog: fleet/elastic/manager.py's relaunch contract assumes
the state a worker resumes from is durable; these tests prove it by
killing saves at every window of the commit protocol (in-process via the
``raise`` chaos action — same filesystem state as ``os._exit`` — plus
one real ``os._exit`` subprocess kill) and asserting ``latest_step``
never lands on a torn checkpoint and that a resumed run matches an
uninterrupted one.
"""
import os
import signal
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import checkpoint as dckpt
from paddle_tpu.distributed import fault_tolerance as ft
from paddle_tpu.distributed.fault_tolerance import (
    CheckpointManager, PreemptionHandler, backoff_delays,
    retry_with_backoff)
from paddle_tpu.profiler import metrics
from paddle_tpu.testing import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def metrics_on():
    metrics.reset()
    ft.reset_stats()
    paddle.set_flags({"FLAGS_tpu_metrics": True})
    yield
    paddle.set_flags({"FLAGS_tpu_metrics": False})
    metrics.reset()
    ft.reset_stats()


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    yield
    chaos.uninstall()


def _write_payload(d, name="w.bin", data=b"x" * 64):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, name), "wb") as f:
        f.write(data)


# ---------------------------------------------------------------------------
# commit protocol primitives
# ---------------------------------------------------------------------------

class TestCommitProtocol:
    def test_manifest_roundtrip_and_verify(self, tmp_path):
        d = str(tmp_path / "ck")
        _write_payload(d)
        man = ft.write_manifest(d, extra={"step": 7})
        assert man["step"] == 7 and man["bytes_total"] == 64
        assert ft.read_manifest(d) == man
        assert ft.is_committed(d)
        assert ft.verify_dir(d)["files"][0]["path"] == "w.bin"

    def test_verify_catches_truncation_and_bitrot(self, tmp_path):
        d = str(tmp_path / "ck")
        _write_payload(d)
        ft.write_manifest(d)
        chaos.truncate_file(os.path.join(d, "w.bin"), 0.5)
        with pytest.raises(ft.CheckpointCorruptionError,
                           match="truncated write"):
            ft.verify_dir(d)
        _write_payload(d)  # restore size, then flip bytes
        ft.write_manifest(d)
        chaos.corrupt_file(os.path.join(d, "w.bin"))
        with pytest.raises(ft.CheckpointCorruptionError, match="CRC32"):
            ft.verify_dir(d)
        # size-only mode misses bit rot by design
        assert ft.verify_dir(d, checksums=False)

    def test_uncommitted_dir_is_invisible(self, tmp_path):
        d = str(tmp_path / "step_00000003")
        _write_payload(d)  # no manifest: the save never committed
        assert not ft.is_committed(d)
        assert ft.committed_steps(str(tmp_path)) == []
        with pytest.raises(ft.CheckpointCorruptionError):
            ft.verify_dir(d)

    def test_commit_dir_publishes_atomically(self, tmp_path):
        final = str(tmp_path / "ck")
        tmp = final + ft.TMP_SUFFIX
        _write_payload(tmp, data=b"new" * 10)
        ft.commit_dir(tmp, final, extra={"step": 1})
        assert ft.is_committed(final) and not os.path.exists(tmp)
        # overwrite: old copy is kept until the rename, dropped after
        tmp2 = final + ft.TMP_SUFFIX
        _write_payload(tmp2, data=b"newer" * 10)
        ft.commit_dir(tmp2, final, extra={"step": 2})
        assert ft.read_manifest(final)["step"] == 2
        assert not os.path.exists(final + ft.OLD_SUFFIX)

    def test_commit_dir_overwrite_false_refuses(self, tmp_path):
        final = str(tmp_path / "ck")
        _write_payload(final)
        ft.write_manifest(final)
        tmp = final + ft.TMP_SUFFIX
        _write_payload(tmp)
        with pytest.raises(FileExistsError):
            ft.commit_dir(tmp, final, overwrite=False)


class TestRecoverDir:
    """Each crash window inside commit_dir maps to one committed state."""

    def test_committed_final_wins_and_drops_strays(self, tmp_path):
        final = str(tmp_path / "ck")
        _write_payload(final)
        ft.write_manifest(final, extra={"gen": "final"})
        _write_payload(final + ft.TMP_SUFFIX)
        _write_payload(final + ft.OLD_SUFFIX)
        assert ft.recover_dir(final) == final
        assert ft.read_manifest(final)["gen"] == "final"
        assert not os.path.exists(final + ft.TMP_SUFFIX)
        assert not os.path.exists(final + ft.OLD_SUFFIX)

    def test_crash_between_aside_and_publish_rolls_forward(self, tmp_path):
        # window: old moved aside, tmp (already durable+manifested) not
        # yet renamed — the new checkpoint wins
        final = str(tmp_path / "ck")
        _write_payload(final + ft.TMP_SUFFIX)
        ft.write_manifest(final + ft.TMP_SUFFIX, extra={"gen": "new"})
        _write_payload(final + ft.OLD_SUFFIX)
        ft.write_manifest(final + ft.OLD_SUFFIX, extra={"gen": "old"})
        assert ft.recover_dir(final) == final
        assert ft.read_manifest(final)["gen"] == "new"
        assert not os.path.exists(final + ft.OLD_SUFFIX)

    def test_crash_before_manifest_rolls_back(self, tmp_path):
        final = str(tmp_path / "ck")
        _write_payload(final + ft.TMP_SUFFIX)  # never manifested
        _write_payload(final + ft.OLD_SUFFIX)
        ft.write_manifest(final + ft.OLD_SUFFIX, extra={"gen": "old"})
        assert ft.recover_dir(final) == final
        assert ft.read_manifest(final)["gen"] == "old"

    def test_husk_with_no_recovery_raises(self, tmp_path):
        final = str(tmp_path / "ck")
        _write_payload(final)  # uncommitted, nothing adjacent
        with pytest.raises(ft.CheckpointCorruptionError):
            ft.recover_dir(final)
        with pytest.raises(FileNotFoundError):
            ft.recover_dir(str(tmp_path / "absent"))


class TestPruning:
    def _commit_step(self, root, step):
        final = os.path.join(root, ft.step_dir_name(step))
        tmp = final + ft.TMP_SUFFIX
        _write_payload(tmp)
        ft.commit_dir(tmp, final, extra={"step": step})

    def test_keeps_newest_k_and_zero_keeps_all(self, tmp_path):
        root = str(tmp_path)
        for s in (1, 2, 3, 4, 5):
            self._commit_step(root, s)
        assert ft.prune_steps(root, keep=0) == []
        assert ft.prune_steps(root, keep=2) == [1, 2, 3]
        assert ft.committed_steps(root) == [4, 5]

    def test_never_removes_last_committed_or_inflight(self, tmp_path):
        root = str(tmp_path)
        for s in (1, 2, 3):
            self._commit_step(root, s)
        removed = ft.prune_steps(root, keep=1, inflight={2})
        assert removed == [1]  # 2 in flight, 3 is the newest
        assert ft.committed_steps(root) == [2, 3]

    def test_sweeps_stale_tmp_dirs_but_not_inflight(self, tmp_path):
        root = str(tmp_path)
        self._commit_step(root, 1)
        stale = os.path.join(root, ft.step_dir_name(9) + ft.TMP_SUFFIX)
        live = os.path.join(root, ft.step_dir_name(8) + ft.TMP_SUFFIX)
        _write_payload(stale)
        _write_payload(live)
        ft.prune_steps(root, keep=3, inflight={8})
        assert not os.path.exists(stale)  # crash leftover: swept
        assert os.path.exists(live)       # async save in progress: kept


# ---------------------------------------------------------------------------
# framework.io atomic save + corrupt-load naming
# ---------------------------------------------------------------------------

class TestFrameworkIO:
    def test_crash_mid_save_leaves_previous_file(self, tmp_path):
        from paddle_tpu.framework.io import load, save
        p = str(tmp_path / "m.pdparams")
        save({"w": paddle.to_tensor([1.0])}, p)
        with chaos.installed(
                chaos.Chaos().rule("raise", "io.save.pre_commit")):
            with pytest.raises(chaos.ChaosError):
                save({"w": paddle.to_tensor([2.0])}, p)
        # the original survives the crashed overwrite; no tmp litter
        assert float(load(p)["w"].numpy()[0]) == 1.0
        assert [f for f in os.listdir(tmp_path) if ".ptq-tmp" in f] == []

    def test_corrupt_load_names_the_file(self, tmp_path):
        from paddle_tpu.framework.io import load, save
        p = str(tmp_path / "m.pdparams")
        save({"w": paddle.to_tensor([1.0])}, p)
        chaos.truncate_file(p, 0.3)
        with pytest.raises(RuntimeError) as ei:
            load(p)
        assert "m.pdparams" in str(ei.value)
        assert "killed mid-save" in str(ei.value)


# ---------------------------------------------------------------------------
# distributed.checkpoint (orbax backend) under chaos
# ---------------------------------------------------------------------------

class TestCheckpointCrashConsistency:
    def test_crash_at_commit_keeps_previous_step(self, tmp_path):
        root = str(tmp_path)
        dckpt.save_step(root, {"w": jnp.arange(4.0)}, 1)
        with chaos.installed(
                chaos.Chaos().rule("raise", "ckpt.commit.pre", step=2)):
            with pytest.raises(chaos.ChaosError):
                dckpt.save_step(root, {"w": jnp.arange(4.0) * 2}, 2)
        assert dckpt.latest_step(root) == 1
        state, step = dckpt.load_step(root)
        assert step == 1
        np.testing.assert_allclose(np.asarray(state["w"]), np.arange(4.0))
        # the torn step 2 tmp dir is swept by the next successful save
        dckpt.save_step(root, {"w": jnp.arange(4.0) * 3}, 3)
        assert not any(ft.TMP_SUFFIX in d for d in os.listdir(root))
        assert dckpt.latest_step(root) == 3

    def test_crash_before_save_leaves_no_trace(self, tmp_path):
        root = str(tmp_path)
        with chaos.installed(
                chaos.Chaos().rule("raise", "ckpt.save.pre")):
            with pytest.raises(chaos.ChaosError):
                dckpt.save_step(root, {"w": jnp.arange(4.0)}, 1)
        assert dckpt.latest_step(root) is None
        with pytest.raises(FileNotFoundError, match="no committed"):
            dckpt.load_step(root)

    def test_restore_falls_back_past_corrupt_step(self, tmp_path,
                                                  metrics_on, capsys):
        root = str(tmp_path)
        dckpt.save_step(root, {"w": jnp.arange(4.0)}, 1)
        dckpt.save_step(root, {"w": jnp.arange(4.0) * 2}, 2)
        d2 = os.path.join(root, ft.step_dir_name(2))
        victim = next(p for _, p in ft._payload_files(d2)
                      if os.path.getsize(p) > 8)
        chaos.truncate_file(victim, 0.5)
        state, step = dckpt.load_step(root)
        assert step == 1
        assert "falling back" in capsys.readouterr().err
        snap = metrics.snapshot()
        assert snap["ckpt_restore_fallback_total"] == 1
        assert snap["ckpt_restores_total"] == 1

    def test_explicit_step_load_raises_on_corruption(self, tmp_path):
        root = str(tmp_path)
        dckpt.save_step(root, {"w": jnp.arange(4.0)}, 1)
        d1 = os.path.join(root, ft.step_dir_name(1))
        victim = next(p for _, p in ft._payload_files(d1)
                      if os.path.getsize(p) > 8)
        chaos.truncate_file(victim, 0.5)
        with pytest.raises(ft.CheckpointCorruptionError):
            dckpt.load_step(root, step=1)

    def test_async_save_commits_via_wait(self, tmp_path):
        root = str(tmp_path)
        dckpt.save_step(root, {"w": jnp.arange(8.0)}, 1, sync=False)
        dckpt.wait_until_finished()
        assert dckpt.latest_step(root) == 1
        assert ft.verify_dir(os.path.join(root, ft.step_dir_name(1)))

    def test_save_metrics_recorded(self, tmp_path, metrics_on):
        dckpt.save_step(str(tmp_path), {"w": jnp.arange(4.0)}, 5)
        snap = metrics.snapshot()
        assert snap["ckpt_saves_total"] == 1
        assert snap["ckpt_bytes_total"] > 0
        assert snap["ckpt_last_committed_step"] == 5
        assert snap["ckpt_save_seconds"]["count"] == 1

    def test_checkpoints_section_in_profiler_summary(self, tmp_path):
        from paddle_tpu import profiler as prof
        dckpt.save_step(str(tmp_path), {"w": jnp.arange(4.0)}, 1)
        p = prof.Profiler(timer_only=True)
        p.start()
        p.stop()
        table = p.summary_table()
        assert "Checkpoints" in table
        assert "saves committed" in table


# ---------------------------------------------------------------------------
# CheckpointManager
# ---------------------------------------------------------------------------

class TestCheckpointManager:
    def test_pickle_backend_interval_keep_resume(self, tmp_path):
        root = str(tmp_path / "mgr")
        with CheckpointManager(root, save_interval_steps=2, keep=2,
                               backend="pickle") as mgr:
            state, start = mgr.restore()
            assert state is None and start == 0
            for step in range(1, 8):
                mgr.step_end(step, {"w": paddle.to_tensor([float(step)])})
            assert mgr.all_steps() == [4, 6]  # every 2, keep 2
        state, step = CheckpointManager(root, backend="pickle").restore()
        assert step == 6
        assert float(state["w"].numpy()[0]) == 6.0

    def test_orbax_backend_resume(self, tmp_path):
        root = str(tmp_path / "mgr")
        mgr = CheckpointManager(root, save_interval_steps=3, keep=1,
                                sync=True)
        for step in range(1, 7):
            mgr.step_end(step, {"w": jnp.full((2,), float(step))})
        mgr.close()
        assert mgr.all_steps() == [6]
        state, step = CheckpointManager(root).restore()
        assert step == 6
        np.testing.assert_allclose(np.asarray(state["w"]), [6.0, 6.0])

    def test_pickle_restore_falls_back_past_corruption(self, tmp_path):
        root = str(tmp_path / "mgr")
        mgr = CheckpointManager(root, save_interval_steps=1, keep=3,
                                backend="pickle")
        for step in (1, 2):
            mgr.save(step, {"w": paddle.to_tensor([float(step)])})
        chaos.truncate_file(
            os.path.join(root, ft.step_dir_name(2), mgr.state_file), 0.3)
        state, step = mgr.restore()
        assert step == 1
        with pytest.raises((ft.CheckpointCorruptionError, RuntimeError)):
            mgr.restore(step=2)

    def test_bad_args_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="backend"):
            CheckpointManager(str(tmp_path), backend="npz")
        with pytest.raises(ValueError, match="save_interval_steps"):
            CheckpointManager(str(tmp_path), save_interval_steps=0)


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------

class TestPreemption:
    def test_relaunch_code_matches_elastic_contract(self):
        from paddle_tpu.distributed.fleet import elastic
        assert ft.RELAUNCH_EXIT_CODE == elastic.RELAUNCH_EXIT_CODE == 101

    def test_sigterm_latches_and_exits_101_after_final_save(self, tmp_path):
        root = str(tmp_path / "mgr")
        with CheckpointManager(root, save_interval_steps=100, keep=3,
                               backend="pickle", preemption=True) as mgr:
            mgr.step_end(1, {"w": paddle.to_tensor([1.0])})
            assert mgr.all_steps() == []  # interval 100: no save yet
            os.kill(os.getpid(), signal.SIGTERM)
            assert mgr.preempted()
            with pytest.raises(SystemExit) as ei:
                mgr.step_end(2, {"w": paddle.to_tensor([2.0])})
            assert ei.value.code == ft.RELAUNCH_EXIT_CODE
            # the final checkpoint committed before the exit
            assert mgr.all_steps() == [2]
        state, step = CheckpointManager(root, backend="pickle").restore()
        assert step == 2 and float(state["w"].numpy()[0]) == 2.0

    def test_handler_restores_previous_signal_disposition(self):
        seen = []
        prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
        try:
            with PreemptionHandler(signals=(signal.SIGTERM,)) as h:
                os.kill(os.getpid(), signal.SIGTERM)
                assert h.requested() and not seen
                h.clear()
                assert not h.requested()
            os.kill(os.getpid(), signal.SIGTERM)
            assert seen == [signal.SIGTERM]  # old handler is back
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_sigterm_chaos_action_triggers_handler(self):
        with PreemptionHandler(signals=(signal.SIGTERM,)) as h:
            with chaos.installed(
                    chaos.Chaos().rule("sigterm", "train.step", step=3)):
                for step in (1, 2, 3):
                    chaos.chaos_point("train.step", step=step)
            assert h.requested()

    def test_model_fit_handle_preemption_exits_101(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu.hapi import Model
        from paddle_tpu.hapi.callbacks import Callback
        from paddle_tpu.io import TensorDataset

        x = paddle.to_tensor(np.random.rand(16, 4).astype("float32"))
        y = paddle.to_tensor(np.random.randint(0, 2, (16, 1)))
        ds = TensorDataset([x, y])
        model = Model(nn.Linear(4, 2))
        model.prepare(paddle.optimizer.SGD(
            learning_rate=0.1, parameters=model.network.parameters()),
            nn.CrossEntropyLoss())

        class _Sig(Callback):
            def on_train_batch_end(self, step, logs=None):
                os.kill(os.getpid(), signal.SIGTERM)

        prev_disposition = signal.getsignal(signal.SIGTERM)
        with pytest.raises(SystemExit) as ei:
            model.fit(ds, epochs=2, batch_size=8, verbose=0,
                      save_dir=str(tmp_path / "sv"), callbacks=[_Sig()],
                      handle_preemption=True)
        assert ei.value.code == ft.RELAUNCH_EXIT_CODE
        # the preemption checkpoint was cut before exiting
        saved = os.listdir(tmp_path / "sv")
        assert any(f.startswith("preempted") for f in saved)
        # the handler was uninstalled on the way out
        assert signal.getsignal(signal.SIGTERM) == prev_disposition


# ---------------------------------------------------------------------------
# retries with backoff
# ---------------------------------------------------------------------------

class TestRetryWithBackoff:
    def test_schedule_is_exponential_with_seeded_jitter(self):
        import random
        delays = list(backoff_delays(4, base=0.1, factor=2.0,
                                     max_delay=10.0, jitter=0.25,
                                     rng=random.Random(7)))
        assert len(delays) == 3
        base = [0.1, 0.2, 0.4]
        for d, b in zip(delays, base):
            assert b <= d < b * 1.25
        # same seed, same schedule
        again = list(backoff_delays(4, base=0.1, factor=2.0,
                                    max_delay=10.0, jitter=0.25,
                                    rng=random.Random(7)))
        assert delays == again

    def test_max_delay_caps_growth(self):
        delays = list(backoff_delays(5, base=1.0, factor=10.0,
                                     max_delay=2.0, jitter=0.0))
        assert delays == [1.0, 2.0, 2.0, 2.0]

    def test_retries_then_succeeds(self):
        calls, slept = [], []
        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionResetError("boom")
            return "ok"
        assert retry_with_backoff(
            flaky, attempts=4, jitter=0.0, sleep=slept.append) == "ok"
        assert len(calls) == 3
        assert slept == [0.05, 0.1]

    def test_exhausted_attempts_reraise(self):
        slept = []
        def always():
            raise ConnectionResetError("down")
        with pytest.raises(ConnectionResetError):
            retry_with_backoff(always, attempts=3, jitter=0.0,
                               sleep=slept.append)
        assert len(slept) == 2

    def test_give_up_raises_immediately(self):
        # TimeoutError IS an OSError: give_up must win the classification
        calls = []
        def timeout():
            calls.append(1)
            raise TimeoutError("budget spent")
        with pytest.raises(TimeoutError):
            retry_with_backoff(timeout, retryable=(OSError,),
                               give_up=(TimeoutError,), attempts=5,
                               sleep=lambda s: None)
        assert len(calls) == 1

    def test_non_retryable_raises_immediately(self):
        def bug():
            raise ValueError("programming error")
        with pytest.raises(ValueError):
            retry_with_backoff(bug, sleep=lambda s: None)


class TestStoreRetries:
    def test_transient_disconnects_are_retried(self, tmp_path):
        from paddle_tpu.distributed.store import TCPStore
        store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
        slept = []
        store._sleep = slept.append
        with chaos.installed(chaos.Chaos(
                "disconnect@store.get:times=2")) as c:
            store.set("k", b"v")
            assert store.get("k") == b"v"  # 2 injected failures absorbed
        assert [a for *_x, a in c.log] == ["disconnect", "disconnect"]
        assert len(slept) == 2
        store.close()

    def test_exhausted_retries_surface_the_error(self):
        from paddle_tpu.distributed.store import TCPStore
        store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
        store._sleep = lambda s: None
        store.retries = 2
        with chaos.installed(chaos.Chaos("disconnect@store.add")):
            with pytest.raises(ConnectionResetError):
                store.add("ctr", 1)
        store.close()


class TestDownloadRetries:
    def test_transient_http_then_success(self, tmp_path, monkeypatch):
        import io
        import urllib.request
        from paddle_tpu.utils import download
        calls = []

        class _Resp(io.BytesIO):
            def __enter__(self):
                return self
            def __exit__(self, *a):
                return False

        def fake_urlopen(url, timeout=None):
            calls.append(url)
            if len(calls) < 3:
                raise ConnectionResetError("flaky edge")
            return _Resp(b"payload")

        monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
        dst = str(tmp_path / "artifact.bin")
        download._fetch("http://example.invalid/artifact.bin", dst,
                        sleep=lambda s: None)
        assert len(calls) == 3
        with open(dst, "rb") as f:
            assert f.read() == b"payload"

    def test_md5_mismatch_caches_nothing(self, tmp_path):
        from paddle_tpu.utils import download
        src = tmp_path / "src.bin"
        src.write_bytes(b"corrupted in flight")
        dst = str(tmp_path / "cache" / "src.bin")
        os.makedirs(os.path.dirname(dst))
        with pytest.raises(RuntimeError, match="md5 mismatch"):
            download._fetch(str(src), dst, md5sum="0" * 32)
        assert os.listdir(os.path.dirname(dst)) == []

    def test_non_transient_fails_fast(self, tmp_path, monkeypatch):
        import urllib.error
        import urllib.request
        from paddle_tpu.utils import download
        calls = []

        def fake_urlopen(url, timeout=None):
            calls.append(url)
            raise urllib.error.HTTPError(url, 404, "nope", {}, None)

        monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
        with pytest.raises(RuntimeError, match="failed after 1 attempt"):
            download._fetch("http://example.invalid/gone",
                            str(tmp_path / "gone"), sleep=lambda s: None)
        assert len(calls) == 1


# ---------------------------------------------------------------------------
# chaos harness itself
# ---------------------------------------------------------------------------

class TestChaosHarness:
    def test_spec_parsing(self):
        c = chaos.Chaos("raise@ckpt.commit.pre:step=3,times=1;"
                        "disconnect@store.*:after=2")
        assert len(c.rules) == 2
        r = c.rules[0]
        assert (r.action, r.point, r.step, r.times) == \
            ("raise", "ckpt.commit.pre", 3, 1)
        assert c.rules[1].after == 2

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError, match="expected"):
            chaos.Rule.parse("raise-no-at-sign")
        with pytest.raises(ValueError, match="unknown chaos action"):
            chaos.Rule.parse("explode@p")
        with pytest.raises(ValueError, match="unknown chaos option"):
            chaos.Rule.parse("raise@p:bogus=1")

    def test_step_filter_times_and_after(self):
        c = chaos.Chaos().rule("raise", "p", step=2, times=1)
        c.rule("disconnect", "q", after=1)
        chaos.install(c)
        try:
            chaos.chaos_point("p", step=1)  # wrong step: no fire
            with pytest.raises(chaos.ChaosError):
                chaos.chaos_point("p", step=2)
            chaos.chaos_point("p", step=2)  # times=1 exhausted
            chaos.chaos_point("q")          # after=1 skips the first hit
            with pytest.raises(ConnectionResetError):
                chaos.chaos_point("q")
        finally:
            chaos.uninstall()
        assert [a for *_x, a in c.log] == ["raise", "disconnect"]

    def test_probabilistic_rules_are_seed_deterministic(self):
        def run(seed):
            c = chaos.Chaos("raise@p:prob=0.5", seed=seed)
            fired = []
            with chaos.installed(c):
                for i in range(20):
                    try:
                        chaos.chaos_point("p", step=i)
                        fired.append(0)
                    except chaos.ChaosError:
                        fired.append(1)
            return fired
        assert run(3) == run(3)
        assert 0 < sum(run(3)) < 20

    def test_env_install(self, monkeypatch):
        monkeypatch.setenv("PTQ_CHAOS", "raise@env.point")
        try:
            c = chaos.install_from_env()
            assert chaos.active() is c
            with pytest.raises(chaos.ChaosError):
                chaos.chaos_point("env.point")
        finally:
            chaos.uninstall()

    def test_inactive_harness_is_free(self):
        assert chaos.active() is None
        chaos.chaos_point("anything", step=1)  # no-op, no error


# ---------------------------------------------------------------------------
# acceptance: a real kill (os._exit) mid-save never corrupts the run
# ---------------------------------------------------------------------------

_KILL_WORKER = """
import os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
from paddle_tpu.distributed.fault_tolerance import CheckpointManager

root, steps = sys.argv[1], int(sys.argv[2])
mgr = CheckpointManager(root, save_interval_steps=1, keep=0,
                        backend="pickle")
state, start = mgr.restore()
w = state["w"].numpy() if state is not None else np.zeros(4, np.float32)
if start:
    print(f"resumed from step {start}", flush=True)
for step in range(start + 1, steps + 1):
    w = w + np.float32(step)        # deterministic trajectory
    mgr.step_end(step, {"w": paddle.to_tensor(w)})
print("FINAL", " ".join(f"{v:.1f}" for v in w), flush=True)
sys.stdout.flush()
os._exit(0)
"""


@pytest.mark.parametrize("crash_point", ["ckpt.save.pre",
                                         "ckpt.commit.pre",
                                         "ft.commit.swap"])
def test_kill_midsave_then_resume_matches_uninterrupted(tmp_path,
                                                        crash_point):
    """The acceptance criterion: os._exit at any window of the save path
    leaves latest_step on a committed checkpoint, and resuming completes
    the identical trajectory an uninterrupted run produces."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(_KILL_WORKER))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def run(root, extra_env, steps=5):
        e = dict(env)
        e.update(extra_env)
        return subprocess.run(
            [sys.executable, str(script), str(root), str(steps)],
            cwd=REPO, env=e, capture_output=True, text=True, timeout=300)

    # uninterrupted reference
    ref_root = tmp_path / "ref"
    ref = run(ref_root, {})
    assert ref.returncode == 0, (ref.stdout, ref.stderr)
    ref_final = [l for l in ref.stdout.splitlines()
                 if l.startswith("FINAL")][0]

    # killed run: os._exit(42) fires inside the step-3 save
    root = tmp_path / "ckpt"
    killed = run(root, {"PTQ_CHAOS": f"crash@{crash_point}:step=3"})
    assert killed.returncode == 42, (killed.stdout, killed.stderr)
    # whatever the kill window, latest_step is a COMMITTED step < 3
    latest = ft.latest_committed_step(str(root))
    assert latest == 2, sorted(os.listdir(root))
    ft.verify_dir(os.path.join(str(root), ft.step_dir_name(latest)))

    # resume finishes and lands exactly on the reference trajectory
    resumed = run(root, {})
    assert resumed.returncode == 0, (resumed.stdout, resumed.stderr)
    assert "resumed from step 2" in resumed.stdout
    final = [l for l in resumed.stdout.splitlines()
             if l.startswith("FINAL")][0]
    assert final == ref_final
