"""Executable-level memory & cost observability (profiler/xmem).

Covers the capture layer at each compile surface (to_static jit cache,
static Executor, inference Predictor), the "Memory" section of
Profiler.summary_table(), the metrics-registry export, the
device.memory_stats() merge of live allocator counters with
analysis-derived static peaks, the pod-fit reporter
(tools/pod_report.py, hardware-free on a virtual v5p-64 mesh).
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import device as pdev
from paddle_tpu import profiler as prof
from paddle_tpu import static
from paddle_tpu.profiler import metrics, xmem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def xmem_on():
    """Enable FLAGS_tpu_xmem on a clean store; restore after."""
    xmem.reset()
    paddle.set_flags({"FLAGS_tpu_xmem": True})
    yield
    paddle.set_flags({"FLAGS_tpu_xmem": False})
    xmem.reset()


@pytest.fixture
def metrics_on():
    """Metrics registry on (implies xmem capture), both reset after."""
    metrics.reset()
    xmem.reset()
    paddle.set_flags({"FLAGS_tpu_metrics": True})
    yield
    paddle.set_flags({"FLAGS_tpu_metrics": False})
    metrics.reset()
    xmem.reset()


# ---------------------------------------------------------------------------
# capture surfaces
# ---------------------------------------------------------------------------

class TestCaptureSurfaces:
    def test_to_static_captures_and_stays_correct(self, xmem_on):
        @paddle.jit.to_static
        def f(x):
            return x * 2.0 + 1.0

        x = paddle.to_tensor(np.arange(8, dtype=np.float32))
        out = f(x)
        np.testing.assert_allclose(
            out.numpy(), np.arange(8, dtype=np.float32) * 2.0 + 1.0)
        profs = [p for p in xmem.profiles() if p["source"] == "to_static"]
        assert profs, "to_static compile was not captured"
        p = profs[0]
        assert p["peak_bytes"] > 0
        assert p["argument_bytes"] >= 8 * 4
        # a repeat call with the same signature reuses the AOT executable
        n = len(xmem.profiles())
        out2 = f(x)
        np.testing.assert_allclose(out2.numpy(), out.numpy())
        assert len(xmem.profiles()) == n

    def test_capture_off_by_default(self):
        xmem.reset()
        assert not xmem.enabled()

        @paddle.jit.to_static
        def g(x):
            return x - 1.0

        g(paddle.to_tensor(np.ones((4,), np.float32)))
        assert xmem.profiles() == []

    def test_executor_capture(self, xmem_on):
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [None, 8])
            y = static.nn.fc(x, 4)
        exe = static.Executor()
        xs = np.random.default_rng(0).standard_normal((2, 8)).astype(
            "float32")
        exe.run(main, feed={"x": xs}, fetch_list=[y])
        assert any(p["source"] == "executor" for p in xmem.profiles())

    def test_predictor_capture(self, xmem_on, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference
        from paddle_tpu.jit import InputSpec

        paddle.seed(3)
        net = nn.Sequential(nn.Linear(16, 8), nn.ReLU(), nn.Linear(8, 2))
        prefix = str(tmp_path / "m")
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([4, 16], "float32")])
        x = np.random.default_rng(1).standard_normal((4, 16)).astype(
            np.float32)
        ref = net(paddle.to_tensor(x)).numpy()

        pred = inference.create_predictor(
            inference.Config(prefix + ".pdmodel"))
        got = pred.run([x])[0]
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
        profs = [p for p in xmem.profiles() if p["source"] == "predictor"]
        assert profs and profs[0]["peak_bytes"] > 0
        # second run reuses the captured executable, numerics intact
        np.testing.assert_allclose(pred.run([x])[0], ref,
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# surfacing: summary table, metrics registry, device memory APIs
# ---------------------------------------------------------------------------

class TestSurfacing:
    def test_summary_table_memory_section(self, xmem_on):
        @paddle.jit.to_static
        def f(x):
            return x @ x

        f(paddle.to_tensor(np.eye(16, dtype=np.float32)))
        p = prof.Profiler(timer_only=True)
        p.start()
        p.stop()
        table = p.summary_table()
        assert "Memory" in table
        assert "PeakHBM" in table
        assert "to_static" in table

    def test_summary_table_hint_when_nothing_captured(self):
        xmem.reset()
        p = prof.Profiler(timer_only=True)
        p.start()
        p.stop()
        table = p.summary_table()
        assert "Memory" in table
        assert "no executables captured" in table

    def test_metrics_registry_exports_same_numbers(self, metrics_on):
        @paddle.jit.to_static
        def f(x):
            return x + 2.0

        f(paddle.to_tensor(np.ones((32,), np.float32)))
        profs = [p for p in xmem.profiles() if p["source"] == "to_static"]
        assert profs
        snap = metrics.snapshot()
        peaks = {k: v for k, v in snap.items()
                 if k.startswith("xmem_peak_bytes")}
        assert peaks, "xmem_peak_bytes gauge missing from registry"
        assert profs[0]["peak_bytes"] in peaks.values()
        assert "xmem_peak_bytes" in metrics.to_prometheus()
        assert snap.get("xmem_captures_total", 0) >= 1

    def test_device_memory_stats_merge(self, xmem_on):
        @paddle.jit.to_static
        def f(x):
            return x @ x

        f(paddle.to_tensor(np.ones((64, 64), np.float32)))
        peak = xmem.max_static_peak()
        assert peak > 0
        stats = pdev.memory_stats()
        assert stats["xmem_static_peak_bytes"] == peak
        assert stats["peak_bytes_in_use"] >= peak
        assert pdev.max_memory_allocated() >= peak
        # cuda namespace routes through the same merged view
        assert pdev.cuda.max_memory_allocated() >= peak
        assert pdev.memory_allocated() >= 0
        # device selection resolves (int ordinal and string forms)
        assert pdev.memory_stats(0)["xmem_static_peak_bytes"] == peak
        assert pdev.memory_stats("cpu")["xmem_static_peak_bytes"] == peak


# ---------------------------------------------------------------------------
# pod-fit reporter
# ---------------------------------------------------------------------------

class TestPodReport:
    def test_llama7b_fits_v5p_64(self, tmp_path):
        """Acceptance: the 7B preset compiles hardware-free on a virtual
        v5p-64 mesh and the report says it fits in 95 GiB/chip."""
        out = str(tmp_path / "report.json")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)          # let the tool set 64 devices
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "pod_report.py"),
             "--preset", "llama7b", "--mesh", "v5p-64", "--out", out],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
        assert r.returncode == 0, r.stderr[-3000:]
        with open(out) as f:
            report = json.load(f)
        t = report["topology"]
        assert t["dp"] * t["pp"] * t["sharding"] * t["mp"] == 64
        assert report["model"]["n_params"] > 6.5e9
        mem = report["memory"]
        assert mem["per_device_peak_bytes"] > 0
        assert mem["per_device_peak_gib"] == pytest.approx(
            mem["per_device_peak_bytes"] / 2**30, abs=1e-3)
        fits = report["fits"]
        assert fits["fits"] is True
        assert fits["headroom_bytes"] > 0
        assert mem["per_device_peak_bytes"] <= fits["hbm_bytes_per_chip"]
        assert report["collectives"], "no collectives in the SPMD HLO"
        pred = report["predicted"]
        assert 0 < pred["mfu"] < 1
        assert pred["step_time_ms"] > 0
        assert report["planner"]["candidates_considered"] > 1

    def test_mesh_spec_parsing(self):
        spec = importlib.util.spec_from_file_location(
            "pod_report", os.path.join(REPO, "tools", "pod_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.parse_mesh("v5p-64") == ("v5p", 64)
        assert mod.parse_mesh("v5e-8") == ("v5e", 8)
        with pytest.raises(SystemExit):
            mod.parse_mesh("h100-8")
        with pytest.raises(SystemExit):
            mod.parse_mesh("v5p")


# ---------------------------------------------------------------------------
# satellite fixes riding along with this PR
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_exponential_decay_honors_decay_steps(self):
        sched = static.exponential_decay(
            learning_rate=0.1, decay_steps=100, decay_rate=0.5)
        for _ in range(100):
            sched.step()
        assert sched() == pytest.approx(0.05, rel=1e-6)

    def test_exponential_decay_staircase(self):
        sched = static.exponential_decay(
            learning_rate=0.1, decay_steps=10, decay_rate=0.5,
            staircase=True)
        for _ in range(9):
            sched.step()
        assert sched() == pytest.approx(0.1)   # floor(9/10) == 0
        sched.step()
        assert sched() == pytest.approx(0.05)  # floor(10/10) == 1
        with pytest.raises(ValueError):
            static.exponential_decay(0.1, decay_steps=0, decay_rate=0.5)

    def test_create_parameter_uses_framework_rng(self):
        paddle.seed(123)
        a = static.create_parameter([4, 4], "float32")
        b = static.create_parameter([4, 4], "float32")
        assert not np.allclose(a.numpy(), b.numpy()), \
            "two created parameters must not be identical"
        paddle.seed(123)
        a2 = static.create_parameter([4, 4], "float32")
        np.testing.assert_allclose(a.numpy(), a2.numpy())  # seed-driven
        bias = static.create_parameter([4], "float32", is_bias=True)
        np.testing.assert_allclose(bias.numpy(), np.zeros(4))

    def test_sequence_pad_rejects_overlong_sequence(self):
        vals = np.arange(5, dtype=np.float32)
        lens = np.asarray([3, 2])
        with pytest.raises(ValueError, match="exceeds"):
            static.nn.sequence_pad((vals, lens), 0.0, maxlen=2)
        # maxlen >= longest still pads fine
        out, ln = static.nn.sequence_pad((vals, lens), 0.0, maxlen=4)
        assert out.shape == [2, 4] or tuple(out.shape) == (2, 4)

    def test_legacy_shells_warn_once(self):
        static.compat._WARNED_KNOBS.clear()
        with pytest.warns(UserWarning, match="no effect"):
            bs = static.BuildStrategy()
            bs.fuse_elewise_add_act_ops = True
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            bs.fuse_bn_act_ops = True  # second knob: silent
        with pytest.warns(UserWarning, match="no-op"):
            main = static.Program()
            static.CompiledProgram(main).with_data_parallel()

    def test_vendor_places_unified(self):
        from paddle_tpu.core.place import NPUPlace as CoreNPU
        from paddle_tpu.compat import NPUPlace as CompatNPU
        with pytest.warns(UserWarning):
            p1 = CoreNPU(1)
        with pytest.warns(UserWarning):
            p2 = CompatNPU(1)
        assert type(p1) is type(p2)
        assert getattr(p1, "device_id", 0) == getattr(p2, "device_id", 0)
