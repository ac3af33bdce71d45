"""Numerics observability: NaN/Inf watchdog, first-bad-op localization,
and tensor-stats telemetry.

Covers what the reference stack gets from FLAGS_check_nan_inf +
nan_inf_utils and paddle.amp.debugging: watchdog check sites gated by
FLAGS_tpu_check_nan_inf (amp/debugging.py), jaxpr re-interpretation
that names the first primitive producing non-finites with file:line
attribution (profiler/numerics.py), the grad-norm / update-ratio
telemetry instrumented in optimizer/clip/scaler/hapi, and the
tools/nan_hunt.py offline CLI.
"""
import json
import os
import pickle
import subprocess
import sys
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.amp import GradScaler, debugging
from paddle_tpu.profiler import metrics, numerics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def checker_on():
    """Enable the watchdog with a clean slate; restore after."""
    numerics.reset()
    cfg = debugging.enable_tensor_checker(
        debugging.TensorCheckerConfig(debug_mode="raise"))
    yield cfg
    debugging.disable_tensor_checker()
    numerics.reset()


@pytest.fixture
def metrics_on():
    metrics.reset()
    numerics.reset()
    paddle.set_flags({"FLAGS_tpu_metrics": True})
    yield
    paddle.set_flags({"FLAGS_tpu_metrics": False})
    metrics.reset()
    numerics.reset()


def _nan_tensor():
    return paddle.to_tensor(np.array([1.0, np.nan, np.inf], np.float32))


# ---------------------------------------------------------------------------
# watchdog gating + actions
# ---------------------------------------------------------------------------

class TestWatchdogGating:
    def test_disabled_by_default_is_noop(self):
        numerics.reset()
        assert not numerics.enabled()
        x = _nan_tensor()
        # passthrough identity, nothing recorded, no exception
        assert debugging.check_numerics(x, "off_site") is x
        assert not numerics.check_array(np.array([np.nan]), "off_site")
        assert numerics.sites() == {}

    def test_enable_disable_tensor_checker(self):
        cfg = debugging.enable_tensor_checker(
            debugging.TensorCheckerConfig(debug_mode="warn"))
        try:
            assert numerics.enabled()
            assert debugging.checker_config() is cfg
            assert paddle.get_flags(
                ["FLAGS_tpu_check_nan_inf"])["FLAGS_tpu_check_nan_inf"]
        finally:
            debugging.disable_tensor_checker()
        assert not numerics.enabled()
        assert debugging.checker_config() is None

    def test_invalid_debug_mode_rejected(self):
        with pytest.raises(ValueError):
            debugging.TensorCheckerConfig(debug_mode="explode")

    def test_invalid_action_rejected(self, checker_on):
        with pytest.raises(ValueError):
            debugging.check_numerics(_nan_tensor(), "t", action="explode")


class TestCheckActions:
    def test_raise_action(self, checker_on):
        with pytest.raises(numerics.NonFiniteError, match="badsite"):
            debugging.check_numerics(_nan_tensor(), "badsite")

    def test_warn_action(self, checker_on):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            debugging.check_numerics(_nan_tensor(), "wsite", action="warn")
        assert len(w) == 1 and issubclass(w[0].category, RuntimeWarning)
        assert "1 NaN, 1 Inf" in str(w[0].message)

    def test_collect_action(self, checker_on):
        debugging.clear_results()
        debugging.check_numerics(_nan_tensor(), "csite", action="collect")
        res = debugging.collect_results()
        assert len(res) == 1
        assert res[0]["name"] == "csite"
        assert res[0]["nan"] == 1 and res[0]["inf"] == 1
        debugging.clear_results()
        assert debugging.collect_results() == []

    def test_hit_counters(self, checker_on):
        ok = paddle.to_tensor([1.0, 2.0])
        debugging.check_numerics(ok, "site_a")
        debugging.check_numerics(ok, "site_a")
        with pytest.raises(numerics.NonFiniteError):
            debugging.check_numerics(_nan_tensor(), "site_a")
        s = numerics.sites()["site_a"]
        assert s["hits"] == 3 and s["nonfinite"] == 1
        assert s["last"]["nan"] == 1

    def test_finite_passthrough(self, checker_on):
        x = paddle.to_tensor([3.0])
        assert debugging.check_numerics(x, "fine") is x
        assert numerics.sites()["fine"]["nonfinite"] == 0

    def test_check_tree_names_leaves(self, checker_on):
        tree = {"a": paddle.to_tensor([1.0]),
                "b": paddle.to_tensor([np.nan])}
        with warnings.catch_warnings(record=True):
            warnings.simplefilter("ignore")
            found = numerics.check_tree(tree, "tree", action="warn")
        assert found
        assert any(k.startswith("tree[") and v["nonfinite"]
                   for k, v in numerics.sites().items())

    def test_step_window_skips_outside(self):
        numerics.reset()
        cfg = debugging.enable_tensor_checker(debugging.TensorCheckerConfig(
            debug_mode="raise", start_step=2))
        try:
            # step 0: before the window — no raise
            debugging.check_numerics(_nan_tensor(), "win")
            debugging.advance_step()
            debugging.advance_step()
            assert cfg.in_window()
            with pytest.raises(numerics.NonFiniteError):
                debugging.check_numerics(_nan_tensor(), "win")
        finally:
            debugging.disable_tensor_checker()
            numerics.reset()


# ---------------------------------------------------------------------------
# in-jit checks (jax.debug.callback)
# ---------------------------------------------------------------------------

class TestInJit:
    def test_collect_inside_jit(self):
        numerics.reset()
        debugging.enable_tensor_checker(
            debugging.TensorCheckerConfig(debug_mode="collect"))
        try:
            @paddle.jit.to_static
            def f(x):
                y = debugging.check_numerics(x * 2.0, "jit_mid",
                                             action="collect")
                return y / (x - x)  # -> inf

            f(paddle.to_tensor(np.ones((3,), np.float32)))
            # mid check was finite; nothing collected for it
            assert all(r["name"] != "jit_mid"
                       for r in debugging.collect_results())
            assert numerics.sites()["jit_mid"]["nonfinite"] == 0
        finally:
            debugging.disable_tensor_checker()
            numerics.reset()

    def test_raise_inside_jit_surfaces(self, checker_on):
        @jax.jit
        def f(a):
            b = jnp.log(a)  # log(0) = -inf
            debugging.check_numerics(b, "jit_log", action="raise")
            return b

        # the callback's NonFiniteError surfaces through XLA as a
        # runtime error carrying the message, not the original type
        with pytest.raises(Exception):
            np.asarray(f(jnp.zeros((2,))))

    def test_flag_off_silences_compiled_checks(self, checker_on):
        debugging.clear_results()

        @jax.jit
        def f(a):
            debugging.check_numerics(a, "toggle_site", action="collect")
            return a + 1


        np.asarray(f(jnp.array([np.nan])))
        assert len(debugging.collect_results()) == 1
        # switch off: the already-compiled callback re-checks the flag
        debugging.disable_tensor_checker()
        np.asarray(f(jnp.array([np.nan])))
        assert len(debugging.collect_results()) == 1


# ---------------------------------------------------------------------------
# first-bad-op localization
# ---------------------------------------------------------------------------

class TestLocalize:
    def test_finds_injected_log_zero(self):
        def model(a):
            b = a * 2.0
            c = jnp.log(b - b)  # <- the injected bad op (this line)
            return jnp.sum(c + 1.0)

        bad_line = model.__code__.co_firstlineno + 2
        report = numerics.localize(model, np.ones((4,), np.float32))
        assert report is not None
        assert report["primitive"] == "log"
        assert report["file"].endswith("test_numerics.py")
        assert report["line"] == bad_line
        assert report["inf"] == 4 and report["nan"] == 0
        assert "test_numerics" in report["where"]

    def test_blames_introducer_not_propagator(self):
        def model(a):
            c = a / (a - a)        # inf introduced HERE (div)
            return jnp.sqrt(c) + 1.0  # propagates, must not be blamed

        report = numerics.localize(model, np.ones((2,), np.float32))
        assert report["primitive"] == "div"

    def test_finite_returns_none(self):
        assert numerics.localize(
            lambda a: jnp.sum(a * 3.0), np.ones((4,), np.float32)) is None

    def test_recurses_into_nested_jit(self):
        @jax.jit
        def inner(a):
            return jnp.log(a - a)

        def outer(a):
            return inner(a * 2.0) + 1.0

        report = numerics.localize(outer, np.ones((2,), np.float32))
        assert report["primitive"] == "log"
        assert "jit/" in report["path"]   # the nested-jit primitive

    def test_nonfinite_input_reported_as_input(self):
        report = numerics.localize(lambda a: a + 1.0,
                                   np.array([np.nan], np.float32))
        assert report["primitive"] == "<input>"

    def test_accepts_tensors(self):
        def model(t):
            return paddle.log(t - t)

        report = numerics.localize(model, paddle.to_tensor([1.0, 2.0]))
        assert report is not None and report["primitive"] == "log"

    def test_watch_decorator(self, checker_on):
        @numerics.watch
        def risky(a):
            return jnp.log(a - a)

        with pytest.raises(numerics.NonFiniteError) as ei:
            risky(jnp.ones((2,)))
        assert ei.value.report is not None
        assert ei.value.report["primitive"] == "log"
        # site is named by qualname, which nests under the test here
        bad = [s for nm, s in numerics.sites().items() if "risky" in nm]
        assert bad and bad[0]["nonfinite"] == 1

    def test_to_static_watchdog_localizes(self):
        numerics.reset()
        debugging.enable_tensor_checker(
            debugging.TensorCheckerConfig(debug_mode="collect"))
        try:
            @paddle.jit.to_static
            def step(x):
                return x / (x - x)

            step(paddle.to_tensor(np.ones((3,), np.float32)))
            res = [r for r in debugging.collect_results()
                   if r["name"].startswith("to_static:")]
            assert len(res) == 1
            assert res[0]["report"]["primitive"] == "div"
            assert "step" in res[0]["name"]
            assert numerics.sites()[res[0]["name"]]["nonfinite"] == 1
        finally:
            debugging.disable_tensor_checker()
            numerics.reset()


# ---------------------------------------------------------------------------
# tensor-stats telemetry
# ---------------------------------------------------------------------------

class TestTensorStats:
    def _one_step(self, clip=None):
        net = nn.Linear(4, 3)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters(),
                                   grad_clip=clip)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = paddle.sum(net(x))
        loss.backward()
        grads = [np.asarray(p.grad._array, np.float32)
                 for p in net.parameters()]
        expected = float(np.sqrt(sum(float((g ** 2).sum())
                                     for g in grads)))
        opt.step()
        opt.clear_grad()
        return expected

    def test_grad_global_norm_gauge(self, metrics_on):
        expected = self._one_step()
        snap = metrics.snapshot()
        assert snap["grad_global_norm"] == pytest.approx(expected,
                                                         rel=1e-5)
        assert numerics.last_stats()["grad_global_norm"] == \
            pytest.approx(expected, rel=1e-5)

    def test_per_param_stats(self, metrics_on):
        self._one_step()
        snap = metrics.snapshot()
        rms = {k: v for k, v in snap.items()
               if k.startswith("grad_rms{")}
        zf = {k: v for k, v in snap.items()
              if k.startswith("grad_zero_fraction{")}
        assert len(rms) == 2 and len(zf) == 2  # weight + bias
        assert all(v > 0 for v in rms.values())
        assert all(0.0 <= v <= 1.0 for v in zf.values())

    def test_weight_update_ratio(self, metrics_on):
        self._one_step()
        snap = metrics.snapshot()
        assert 0 < snap["weight_update_ratio"] < 10
        assert snap["param_global_norm"] > 0

    def test_clip_records_pre_post_norms(self, metrics_on):
        pre = self._one_step(clip=nn.ClipGradByGlobalNorm(0.01))
        snap = metrics.snapshot()
        assert snap["grad_global_norm_preclip"] == pytest.approx(
            pre, rel=1e-5)
        assert snap["grad_global_norm_postclip"] == pytest.approx(0.01)
        assert snap["grad_clip_activations_total"] == 1
        # post-clip global norm is what the optimizer step sees
        assert snap["grad_global_norm"] == pytest.approx(0.01, rel=1e-4)

    def test_train_batch_loss_telemetry(self, metrics_on):
        from paddle_tpu.hapi import Model
        m = Model(nn.Linear(4, 2))
        m.prepare(optimizer=paddle.optimizer.SGD(
            learning_rate=0.1, parameters=m.network.parameters()),
            loss=nn.MSELoss())
        m.train_batch(paddle.to_tensor(np.ones((2, 4), np.float32)),
                      paddle.to_tensor(np.zeros((2, 2), np.float32)))
        snap = metrics.snapshot()
        assert snap["train_batches_total"] == 1
        assert snap["train_loss"] > 0
        assert "train_loss" in numerics.last_stats()

    def test_profiler_summary_has_numerics_section(self, metrics_on):
        from paddle_tpu import profiler as prof
        p = prof.Profiler()
        p.start()
        self._one_step()
        p.stop()
        table = p.summary_table()
        assert "Numerics" in table
        assert "grad_global_norm" in table

    def test_disabled_path_records_nothing(self):
        metrics.reset()
        numerics.reset()
        self._one_step()
        assert "grad_global_norm" not in metrics.snapshot()
        assert numerics.last_stats() == {}


# ---------------------------------------------------------------------------
# GradScaler
# ---------------------------------------------------------------------------

class TestGradScaler:
    def _setup(self, scale=1024.0):
        net = nn.Linear(3, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        scaler = GradScaler(init_loss_scaling=scale)
        x = paddle.to_tensor(np.ones((2, 3), np.float32))
        loss = scaler.scale(paddle.sum(net(x)))
        loss.backward()
        return net, opt, scaler

    def test_canonical_unscale_clip_step_divides_once(self):
        # the double-unscale regression: step() after an explicit
        # unscale_() must NOT divide by the scale again
        net, opt, scaler = self._setup()
        grads_after_unscale = None
        scaler.unscale_(opt)
        grads_after_unscale = [np.asarray(p.grad._array)
                               for p in net.parameters()]
        scaler.step(opt)
        scaler.update()
        # true (unscaled) grad of sum(Wx+b) over batch of ones: rows of
        # x summed -> 2.0 for weights, 2.0 for bias
        for g in grads_after_unscale:
            np.testing.assert_allclose(g, np.full_like(g, 2.0),
                                       rtol=1e-5)

    def test_step_without_unscale_still_unscales_once(self):
        net1, opt1, scaler1 = self._setup()
        scaler1.step(opt1)
        net2, opt2, scaler2 = self._setup()
        scaler2.unscale_(opt2)
        scaler2.step(opt2)
        w1 = np.asarray(net1.parameters()[0]._array)
        w2 = np.asarray(net2.parameters()[0]._array)
        # both paths applied exactly one division by the scale; the two
        # nets start from different random weights, so compare updates
        # via the grads left on the parameters
        g1 = np.asarray(net1.parameters()[0].grad._array)
        g2 = np.asarray(net2.parameters()[0].grad._array)
        np.testing.assert_allclose(g1, g2, rtol=1e-5)
        assert np.isfinite(w1).all() and np.isfinite(w2).all()

    def test_double_unscale_raises(self):
        _, opt, scaler = self._setup()
        scaler.unscale_(opt)
        with pytest.raises(RuntimeError, match="already been called"):
            scaler.unscale_(opt)

    def test_unscale_after_step_raises(self):
        _, opt, scaler = self._setup()
        scaler.step(opt)
        with pytest.raises(RuntimeError, match="after step"):
            scaler.unscale_(opt)

    def test_update_resets_per_optimizer_state(self):
        _, opt, scaler = self._setup()
        scaler.unscale_(opt)
        scaler.step(opt)
        scaler.update()
        # after update() the optimizer is READY again
        loss = scaler.scale(paddle.to_tensor(5.0))
        scaler.unscale_(opt)

    def test_found_inf_skips_step_and_decreases_scale(self, metrics_on):
        net, opt, scaler = self._setup(scale=4.0)
        w_before = np.asarray(net.parameters()[0]._array).copy()
        net.parameters()[0].grad._set_array(
            jnp.full_like(net.parameters()[0].grad._array, np.inf))
        scaler.step(opt)
        scaler.update()
        np.testing.assert_array_equal(
            np.asarray(net.parameters()[0]._array), w_before)
        assert scaler.get_init_loss_scaling() == pytest.approx(2.0)
        snap = metrics.snapshot()
        assert snap["amp_found_inf_total"] == 1
        assert snap["amp_skipped_steps_total"] == 1
        assert snap["amp_loss_scale"] == pytest.approx(2.0)
        assert numerics.last_stats()["loss_scale"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# nan_hunt CLI
# ---------------------------------------------------------------------------

def _run_nan_hunt(tmp_path, payload, extra=()):
    repro = tmp_path / "repro.pkl"
    with open(repro, "wb") as f:
        pickle.dump(payload, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "nan_hunt.py"),
         "--repro", str(repro), *extra],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO)


class TestNanHunt:
    SRC = ("import jax.numpy as jnp\n"
           "def step(a):\n"
           "    return jnp.log(a - a)\n")

    def test_reports_bad_op_and_exits_2(self, tmp_path):
        out = tmp_path / "report.json"
        proc = _run_nan_hunt(tmp_path, {
            "src": self.SRC, "entry": "step",
            "args": [np.ones((3,), np.float32)]},
            extra=("--out", str(out)))
        assert proc.returncode == 2, proc.stderr
        doc = json.loads(out.read_text())
        assert doc["finite"] is False
        assert doc["report"]["primitive"] == "log"
        assert "FIRST BAD OP: log" in proc.stderr

    def test_finite_exits_0(self, tmp_path):
        proc = _run_nan_hunt(tmp_path, {
            "src": "def step(a):\n    return a + 1\n", "entry": "step",
            "args": [np.ones((3,), np.float32)]})
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["finite"] is True and doc["report"] is None


# ---------------------------------------------------------------------------
# ScalarLogger
# ---------------------------------------------------------------------------

class TestScalarLogger:
    def test_jsonl_records(self, tmp_path, metrics_on):
        from paddle_tpu.hapi.callbacks import ScalarLogger
        lg = ScalarLogger(str(tmp_path / "run"))
        metrics.gauge("some_gauge", "").set(7.0)
        lg.log(1, loss=0.5, lr=0.1, skipme="not-a-number")
        lg.log(2, loss=0.25)
        lg.close()
        lines = [json.loads(l) for l in
                 open(lg.path).read().splitlines()]
        assert [r["step"] for r in lines] == [1, 2]
        assert lines[0]["scalars"] == {"loss": 0.5, "lr": 0.1}
        assert lines[0]["metrics"]["some_gauge"] == 7.0

    def test_callback_log_freq(self, tmp_path):
        from paddle_tpu.hapi.callbacks import ScalarLogger
        lg = ScalarLogger(str(tmp_path / "run"), log_freq=2,
                          with_metrics=False)
        for i in range(4):
            lg.on_train_batch_end(i, {"loss": float(i)})
        lg.on_train_end()
        lines = [json.loads(l) for l in
                 open(lg.path).read().splitlines()]
        assert [r["step"] for r in lines] == [2, 4]
        assert "metrics" not in lines[0]
