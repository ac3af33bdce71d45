"""tpu_lint static-analysis suite: jaxpr rules, AST rules, pragmas,
baseline ratchet, to_static/flag wiring, and the self-hosted CLI run.

Every rule has a firing and a non-firing case; attribution tests pin the
exact source line findings point at.
"""
import inspect
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.experimental
import jax.numpy as jnp
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import ast_checks
from paddle_tpu.analysis import core as lint_core
from paddle_tpu.analysis import jaxpr_checks
from paddle_tpu.analysis import kernel_checks
from paddle_tpu.analysis import spmd_checks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "tpu_lint_baseline.json")


@pytest.fixture(autouse=True)
def _clean_lint_state():
    analysis.reset()
    yield
    analysis.reset()
    paddle.set_flags({"FLAGS_tpu_lint": False})


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# jaxpr rules
# ---------------------------------------------------------------------------

def _marker_line(fn, marker):
    src, start = inspect.getsourcelines(fn)
    for i, line in enumerate(src):
        if marker in line:
            return start + i
    raise AssertionError(f"marker {marker!r} not found")


def test_host_callback_in_loop_fires_with_attribution():
    def scan_fn(xs):
        def body(c, x):
            jax.debug.callback(lambda v: None, x)  # LINT-MARK-CB
            return c + x, x
        c, _ = lax.scan(body, jnp.float32(0), xs)
        return c

    found = jaxpr_checks.lint_callable(scan_fn, np.ones(3, np.float32))
    hits = [f for f in found if f.rule == "host-callback-in-loop"]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == "error"
    assert f.source == "jaxpr"
    assert f.file and f.file.endswith("test_analysis.py")
    assert f.line == _marker_line(scan_fn, "LINT-MARK-CB")
    assert "scan" in f.extra["path"]


def test_host_callback_outside_loop_clean():
    def top(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1
    found = jaxpr_checks.lint_callable(top, np.float32(1))
    assert "host-callback-in-loop" not in _rules_of(found)


def test_host_callback_in_while_fires():
    def loop(x):
        def cond(v):
            return v < 10.0

        def body(v):
            jax.debug.callback(lambda q: None, v)
            return v + 1.0
        return lax.while_loop(cond, body, x)
    found = jaxpr_checks.lint_callable(loop, np.float32(0))
    assert "host-callback-in-loop" in _rules_of(found)


def test_f64_promotion_fires():
    with jax.enable_x64(True):
        found = jaxpr_checks.lint_callable(
            lambda x: x + np.float64(1.0), np.ones(2, np.float32))
    hits = [f for f in found if f.rule == "f64-promotion"]
    assert hits and hits[0].severity == "warning"
    assert "float64" in hits[0].message


def test_f64_promotion_clean_for_f32():
    found = jaxpr_checks.lint_callable(
        lambda x: x * 2.0 + 1.0, np.ones(2, np.float32))
    assert "f64-promotion" not in _rules_of(found)


def test_int32_overflow_reduction_fires():
    found = jaxpr_checks.lint_callable(
        lambda x: jnp.sum(x), jax.ShapeDtypeStruct((1 << 21,), jnp.int32))
    hits = [f for f in found if f.rule == "int32-overflow-reduction"]
    assert hits and hits[0].extra["elements"] == 1 << 21


def test_int32_reduction_small_or_float_clean():
    found = jaxpr_checks.lint_callable(
        lambda x: jnp.sum(x), jax.ShapeDtypeStruct((64,), jnp.int32))
    assert "int32-overflow-reduction" not in _rules_of(found)
    found = jaxpr_checks.lint_callable(
        lambda x: jnp.sum(x),
        jax.ShapeDtypeStruct((1 << 21,), jnp.float32))
    assert "int32-overflow-reduction" not in _rules_of(found)


def test_oversized_constant_fires():
    big = np.zeros((600, 600), np.float32)  # 1.4 MiB > 1 MiB default

    def fn(x):
        return x + jnp.asarray(big)
    found = jaxpr_checks.lint_callable(fn, np.ones((600, 600), np.float32))
    hits = [f for f in found if f.rule == "oversized-constant"]
    assert hits and hits[0].extra["nbytes"] == big.nbytes


def test_oversized_constant_threshold_and_arg_clean():
    big = np.zeros((600, 600), np.float32)
    found = jaxpr_checks.lint_callable(
        lambda x: x + jnp.asarray(big), np.ones((600, 600), np.float32),
        config={"max_const_bytes": 8 << 20})
    assert "oversized-constant" not in _rules_of(found)
    # passed as an argument: no constant is baked
    found = jaxpr_checks.lint_callable(
        lambda x, w: x + w, np.ones((600, 600), np.float32), big)
    assert "oversized-constant" not in _rules_of(found)


def test_unusable_donation_fires():
    jf = jax.jit(lambda a, b: (a.sum() > 0).astype(jnp.int32),
                 donate_argnums=(0,))
    found = jaxpr_checks.lint_callable(jf, np.ones(4, np.float32),
                                       np.ones(4, np.float32))
    hits = [f for f in found if f.rule == "unusable-donation"]
    assert hits and hits[0].extra["arg_index"] == 0


def test_usable_donation_clean():
    jf = jax.jit(lambda a, b: a * 2 + b, donate_argnums=(0,))
    found = jaxpr_checks.lint_callable(jf, np.ones(4, np.float32),
                                       np.ones(4, np.float32))
    assert "unusable-donation" not in _rules_of(found)


def test_collective_divergence_fires():
    def fn(p, x):
        return lax.cond(p, lambda v: lax.psum(v, "i"),
                        lambda v: v + 0.0, x)
    closed = jax.make_jaxpr(fn, axis_env=[("i", 2)])(np.array(True),
                                                     np.float32(1))
    found = jaxpr_checks.check_jaxpr(closed, name="fn")
    hits = [f for f in found if f.rule == "collective-divergence"]
    assert hits and hits[0].severity == "error"
    assert "psum" in hits[0].extra["branches"]


def test_collective_symmetric_branches_clean():
    def fn(p, x):
        return lax.cond(p, lambda v: lax.psum(v, "i"),
                        lambda v: lax.psum(v * 2, "i"), x)
    closed = jax.make_jaxpr(fn, axis_env=[("i", 2)])(np.array(True),
                                                     np.float32(1))
    found = jaxpr_checks.check_jaxpr(closed, name="fn")
    assert "collective-divergence" not in _rules_of(found)


# ---------------------------------------------------------------------------
# AST rules
# ---------------------------------------------------------------------------

def _check(src):
    return ast_checks.check_source(textwrap.dedent(src), path="t.py")


def test_ast_host_sync_in_loop_fires_with_line():
    found = _check("""\
    import jax.numpy as jnp
    def f(xs, g):
        total = 0.0
        for x in xs:
            total += float(jnp.dot(x, g))
        return total
    """)
    hits = [f for f in found if f.rule == "host-sync-in-loop"]
    assert len(hits) == 1
    assert hits[0].line == 5
    assert hits[0].severity == "error"


def test_ast_host_sync_item_numpy_in_loop():
    found = _check("""\
    def f(xs):
        out = []
        while xs:
            out.append(xs.pop().item())
            v = xs[0].numpy()
        return out
    """)
    lines = sorted(f.line for f in found if f.rule == "host-sync-in-loop")
    assert lines == [4, 5]


def test_ast_host_sync_outside_loop_clean():
    found = _check("""\
    import jax.numpy as jnp
    def f(x, g):
        return float(jnp.dot(x, g))
    """)
    assert "host-sync-in-loop" not in _rules_of(found)


def test_ast_host_sync_explicit_device_get_clean():
    found = _check("""\
    import jax, jax.numpy as jnp
    def f(xs):
        for x in xs:
            done = bool(jax.device_get(jnp.all(x)))
        return done
    """)
    assert "host-sync-in-loop" not in _rules_of(found)


def test_ast_host_sync_in_to_static_body_fires():
    found = _check("""\
    import jax.numpy as jnp
    import paddle
    @paddle.jit.to_static
    def step(x):
        return float(jnp.sum(x))
    """)
    hits = [f for f in found if f.rule == "host-sync-in-loop"]
    assert hits and hits[0].line == 5
    assert "to_static" in hits[0].message


def test_ast_except_pass_fires_and_narrow_clean():
    found = _check("""\
    def f():
        try:
            risky()
        except Exception:
            pass
        try:
            risky()
        except ValueError:
            pass
        try:
            risky()
        except Exception as e:
            log(e)
    """)
    hits = [f for f in found if f.rule == "except-pass"]
    assert len(hits) == 1 and hits[0].line == 4


def test_ast_bare_except_fires():
    found = _check("""\
    def f():
        try:
            risky()
        except:
            pass
    """)
    assert "except-pass" in _rules_of(found)


def test_ast_mutable_default_fires_and_none_clean():
    found = _check("""\
    def f(a=[], b={}, c=set(), d=None, e=()):
        return a, b, c, d, e
    """)
    hits = [f for f in found if f.rule == "mutable-default-arg"]
    assert len(hits) == 3


def test_ast_flag_lookup_in_loop_fires_and_hoisted_clean():
    found = _check("""\
    import os
    def f(steps):
        for _ in range(steps):
            if os.environ.get("FLAGS_x"):
                pass
            v = get_flags("FLAGS_y")
        hoisted = get_flags("FLAGS_y")
        return hoisted
    """)
    lines = sorted(f.line for f in found
                   if f.rule == "flag-lookup-in-loop")
    assert lines == [4, 6]


def test_ast_nested_def_resets_loop_context():
    # a def inside a loop is a new host frame: its body is not
    # per-iteration code
    found = _check("""\
    import jax.numpy as jnp
    def f(xs, g):
        for x in xs:
            def helper(y):
                return float(jnp.dot(y, g))
        return helper
    """)
    assert "host-sync-in-loop" not in _rules_of(found)


def test_ast_syntax_error_is_a_finding():
    found = ast_checks.check_source("def f(:\n", path="bad.py")
    assert [f.rule for f in found] == ["syntax-error"]


def test_ast_mosaic_block_shape_fires_on_illegal_literal():
    # the exact BENCH_r02 failure: a (1, 256) LSE block — second-to-last
    # dim 1 is neither divisible by 8 nor (statically knowably) equal to
    # the array dim
    found = _check("""\
    from jax.experimental import pallas as pl
    def make_specs(S):
        a = pl.BlockSpec((1, 256), lambda i: (i, 0))
        b = pl.BlockSpec(block_shape=(8, 100), index_map=lambda i: (i, 0))
        c = pl.BlockSpec((64,), lambda i: (i,))
        return a, b, c
    """)
    hits = {f.line: f for f in found if f.rule == "mosaic-block-shape"}
    assert sorted(hits) == [3, 4, 5]
    assert hits[3].severity == "warning"
    assert "% 8" in hits[3].message           # (1, 256): sublane dim
    assert "% 128" in hits[4].message         # (8, 100): lane dim
    assert "% 128" in hits[5].message         # rank-1 64


def test_ast_mosaic_block_shape_clean_cases():
    # legal literals, variable shapes (autotuned -> not judgeable), other
    # BlockSpec-named calls without a shape, and pragma suppression
    found = _check("""\
    from jax.experimental import pallas as pl
    def make_specs(bq, S):
        ok = pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0))
        var = pl.BlockSpec((1, bq, 256), lambda i: (i, 0, 0))
        none = pl.BlockSpec(memory_space=None)
        sup = pl.BlockSpec((1, 256), lambda i: (i, 0))  # tpu-lint: disable=mosaic-block-shape
        return ok, var, none, sup
    """)
    assert "mosaic-block-shape" not in _rules_of(found)


# ---------------------------------------------------------------------------
# pragma suppression
# ---------------------------------------------------------------------------

def test_pragma_same_line_suppresses():
    found = _check("""\
    def f():
        try:
            risky()
        except Exception:  # tpu-lint: disable=except-pass
            pass
    """)
    assert "except-pass" not in _rules_of(found)


def test_pragma_line_above_suppresses():
    found = _check("""\
    import jax.numpy as jnp
    def f(xs, g):
        for x in xs:
            # tpu-lint: disable=host-sync-in-loop
            v = float(jnp.dot(x, g))
        return v
    """)
    assert "host-sync-in-loop" not in _rules_of(found)


def test_pragma_wrong_rule_does_not_suppress():
    found = _check("""\
    def f():
        try:
            risky()
        except Exception:  # tpu-lint: disable=host-sync-in-loop
            pass
    """)
    assert "except-pass" in _rules_of(found)


def test_pragma_all_and_file_filter(tmp_path):
    p = tmp_path / "mod.py"
    p.write_text("x = 1  # tpu-lint: disable=all\n")
    f = lint_core.Finding(rule="anything", severity="warning",
                          message="m", file=str(p), line=1)
    assert lint_core.filter_file_pragmas([f]) == []
    f2 = lint_core.Finding(rule="anything", severity="warning",
                           message="m", file=str(p), line=0)
    assert lint_core.filter_file_pragmas([f2]) == [f2]


# ---------------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------------

def _mk(rule, path, line, severity="warning"):
    return lint_core.Finding(rule=rule, severity=severity, message="m",
                             file=path, line=line)


def test_baseline_roundtrip_and_diff(tmp_path):
    root = str(tmp_path)
    findings = [_mk("except-pass", os.path.join(root, "a.py"), 10),
                _mk("except-pass", os.path.join(root, "a.py"), 20)]
    bl_path = str(tmp_path / "baseline.json")
    lint_core.write_baseline(bl_path, findings, root)
    baseline = lint_core.load_baseline(bl_path)
    assert [e["path"] for e in baseline["entries"]] == ["a.py", "a.py"]

    # unchanged -> clean
    new, fixed = lint_core.diff_baseline(findings, baseline, root)
    assert new == [] and fixed == []

    # one more finding in the same bucket -> exactly it is new
    extra = _mk("except-pass", os.path.join(root, "a.py"), 30)
    new, _ = lint_core.diff_baseline(findings + [extra], baseline, root)
    assert new == [extra]

    # lines shifted but same count -> still clean (count ratchet)
    shifted = [_mk("except-pass", os.path.join(root, "a.py"), 11),
               _mk("except-pass", os.path.join(root, "a.py"), 21)]
    new, fixed = lint_core.diff_baseline(shifted, baseline, root)
    assert new == [] and fixed == []

    # one fixed -> reported so the baseline gets regenerated
    new, fixed = lint_core.diff_baseline(findings[:1], baseline, root)
    assert new == [] and fixed == [{"rule": "except-pass", "path": "a.py",
                                    "removed": 1}]


def test_baseline_update_is_deterministic(tmp_path):
    root = str(tmp_path)
    findings = [_mk("b-rule", os.path.join(root, "z.py"), 2),
                _mk("a-rule", os.path.join(root, "a.py"), 9),
                _mk("a-rule", os.path.join(root, "a.py"), 3)]
    p1, p2 = str(tmp_path / "b1.json"), str(tmp_path / "b2.json")
    lint_core.write_baseline(p1, findings, root)
    lint_core.write_baseline(p2, list(reversed(findings)), root)
    assert open(p1).read() == open(p2).read()


# ---------------------------------------------------------------------------
# to_static / flag / metrics / profiler wiring
# ---------------------------------------------------------------------------

def _scan_callback_fn():
    @paddle.jit.to_static(lint=True)
    def step(xs):
        def body(c, x):
            jax.debug.callback(lambda v: None, x)
            return c + x, x
        c, _ = lax.scan(body, jnp.float32(0), xs._array)
        return paddle.to_tensor(c)
    return step


def test_to_static_lint_true_records_findings():
    step = _scan_callback_fn()
    step(paddle.to_tensor(np.ones(4, np.float32)))
    found = analysis.findings()
    assert any(f.rule == "host-callback-in-loop"
               and f.function.endswith("step") for f in found)


def test_to_static_lints_once_per_signature():
    step = _scan_callback_fn()
    x = paddle.to_tensor(np.ones(4, np.float32))
    step(x)
    n = len(analysis.findings())
    step(x)  # same signature: no re-lint, registry dedupes anyway
    assert len(analysis.findings()) == n


def test_lint_disabled_path_records_nothing():
    assert analysis.enabled() is False

    @paddle.jit.to_static
    def step(xs):
        def body(c, x):
            jax.debug.callback(lambda v: None, x)
            return c + x, x
        c, _ = lax.scan(body, jnp.float32(0), xs._array)
        return paddle.to_tensor(c)
    step(paddle.to_tensor(np.ones(4, np.float32)))
    assert analysis.findings() == []


def test_flags_tpu_lint_enables_globally():
    paddle.set_flags({"FLAGS_tpu_lint": True})
    try:
        @paddle.jit.to_static
        def step(xs):
            def body(c, x):
                jax.debug.callback(lambda v: None, x)
                return c + x, x
            c, _ = lax.scan(body, jnp.float32(0), xs._array)
            return paddle.to_tensor(c)
        step(paddle.to_tensor(np.ones(4, np.float32)))
        assert "host-callback-in-loop" in _rules_of(analysis.findings())
    finally:
        paddle.set_flags({"FLAGS_tpu_lint": False})


def test_lint_findings_metric_counter():
    from paddle_tpu.profiler import metrics
    paddle.set_flags({"FLAGS_tpu_metrics": True})
    try:
        step = _scan_callback_fn()
        step(paddle.to_tensor(np.ones(4, np.float32)))
        snap = metrics.snapshot()
        key = 'lint_findings_total{rule="host-callback-in-loop"}'
        assert snap.get(key, 0) >= 1
    finally:
        paddle.set_flags({"FLAGS_tpu_metrics": False})


def test_profiler_summary_has_lint_section():
    step = _scan_callback_fn()
    step(paddle.to_tensor(np.ones(4, np.float32)))
    prof = paddle.profiler.Profiler(timer_only=True)
    prof.start()
    prof.stop()
    table = prof.summary_table()
    assert "Lint" in table
    assert "host-callback-in-loop" in table


def test_lint_never_breaks_the_traced_call():
    # an unhashable static leaf keeps key=None; lint still must not
    # interfere with the call result
    @paddle.jit.to_static(lint=True)
    def mul(x, k):
        return x * k
    out = mul(paddle.to_tensor(np.ones(2, np.float32)), 3.0)
    np.testing.assert_allclose(out.numpy(), [3.0, 3.0])


# ---------------------------------------------------------------------------
# self-hosted lint (tier-1 gate) + CLI acceptance
# ---------------------------------------------------------------------------

def test_self_hosted_lint_clean_against_baseline():
    """The framework itself must stay clean vs the checked-in baseline —
    this is the tier-1 ratchet: new violations fail here. Runs the full
    self-hosted sweep: Level 2 (AST over the package) + Level 3 (the
    registered Pallas kernel library through the verifier)."""
    findings = list(ast_checks.check_paths(
        [os.path.join(REPO, "paddle_tpu")]))
    findings += kernel_checks.verify_registered()
    baseline = lint_core.load_baseline(BASELINE)
    new, _fixed = lint_core.diff_baseline(findings, baseline, REPO)
    assert new == [], "new lint findings vs tools/tpu_lint_baseline.json:" \
        + "".join(f"\n  {f.severity} {f.rule} {f.where}: {f.message}"
                  for f in new)


def test_baseline_is_fully_burned_down():
    """PR satellite: the five Level-1/2 backlog entries (vision NMS
    .tolist, engine per-metric .numpy, two except-pass, dataloader env
    lookup) are FIXED — the checked-in baseline is empty."""
    baseline = lint_core.load_baseline(BASELINE)
    assert baseline["entries"] == []


def test_baseline_backlog_shrunk_lbfgs_and_decode():
    # the satellite fixes must be FIXED, not baselined
    baseline = lint_core.load_baseline(BASELINE)
    paths = {e["path"] for e in baseline["entries"]}
    assert not any("optimizer/lbfgs.py" in p for p in paths)
    assert not any("nn/decode.py" in p for p in paths)
    assert not any("quantization/qat.py" in p for p in paths)


def test_cli_self_hosted_acceptance():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         os.path.join(REPO, "paddle_tpu")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert doc["new"] == []
    assert doc["total_findings"] == 0  # backlog fully burned down


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        import jax.numpy as jnp
        def f(xs, g):
            for x in xs:
                v = float(jnp.dot(x, g))
            return v
    """))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         str(bad), "--no-baseline"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2  # error-severity finding
    doc = json.loads(proc.stdout)
    (finding,) = doc["new"]
    assert finding["rule"] == "host-sync-in-loop"
    assert finding["severity"] == "error"
    assert finding["line"] == 4

    warn_only = tmp_path / "warn.py"
    warn_only.write_text("def f(a=[]):\n    return a\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         str(warn_only), "--no-baseline"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1  # warnings only

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         str(warn_only), "--no-baseline", "--rules", "except-pass"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0  # rule filter


def test_cli_baseline_update_mode(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(a=[]):\n    return a\n")
    bl = tmp_path / "bl.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         str(bad), "--baseline", str(bl), "--baseline-update",
         "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = lint_core.load_baseline(str(bl))
    assert doc["entries"][0]["rule"] == "mutable-default-arg"
    assert doc["entries"][0]["path"] == "bad.py"  # path-relative

    # now the same file lints clean against its baseline
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         str(bad), "--baseline", str(bl), "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the fixed hot paths stay fixed (regression guards for the satellites)
# ---------------------------------------------------------------------------

def test_lbfgs_file_has_no_host_sync_findings():
    found = ast_checks.check_file(
        os.path.join(REPO, "paddle_tpu", "optimizer", "lbfgs.py"))
    assert found == [], [f.to_dict() for f in found]


def test_decode_file_has_no_findings():
    found = ast_checks.check_file(
        os.path.join(REPO, "paddle_tpu", "nn", "decode.py"))
    assert found == [], [f.to_dict() for f in found]


def test_lbfgs_still_converges():
    # quadratic: LBFGS with the fused-transfer rewrite must still land
    # at the lstsq solution
    rng = np.random.default_rng(0)
    A = rng.normal(size=(8, 4)).astype(np.float32)
    b = rng.normal(size=(8,)).astype(np.float32)
    x = paddle.to_tensor(np.zeros(4, np.float32), stop_gradient=False)

    def closure():
        r = paddle.matmul(paddle.to_tensor(A), x) - paddle.to_tensor(b)
        loss = paddle.sum(r * r)
        loss.backward()
        return loss

    opt = paddle.optimizer.LBFGS(learning_rate=1.0, max_iter=40,
                                 line_search_fn="strong_wolfe",
                                 parameters=[x])
    opt.step(closure)
    expect, *_ = np.linalg.lstsq(A, b, rcond=None)
    np.testing.assert_allclose(x.numpy(), expect, atol=1e-3)


# ---------------------------------------------------------------------------
# Level 3: kernel verifier — seeded-defect fixtures, each pinned to file:line
# ---------------------------------------------------------------------------

from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

_F32_16x128 = jax.ShapeDtypeStruct((16, 128), jnp.float32)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _k_rules(findings, rule):
    return [f for f in findings if f.rule == rule]


def _seed_oob(x):
    return pl.pallas_call(  # LINT-MARK-K-OOB
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i + 1, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)))(x)


def test_kernel_index_oob_fires_with_exact_line():
    found = kernel_checks.verify_kernel(_seed_oob, _F32_16x128)
    hits = _k_rules(found, "kernel-index-oob")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error" and f.source == "kernel"
    assert f.file and f.file.endswith("test_analysis.py")
    assert f.line == _marker_line(_seed_oob, "LINT-MARK-K-OOB")
    assert "off-by-one" in f.message


def _seed_coverage_gap(x):
    return pl.pallas_call(  # LINT-MARK-K-GAP
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)))(x)


def test_kernel_output_coverage_gap_fires_with_exact_line():
    found = kernel_checks.verify_kernel(
        _seed_coverage_gap, jax.ShapeDtypeStruct((32, 128), jnp.float32))
    hits = _k_rules(found, "kernel-output-coverage")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error"
    assert f.line == _marker_line(_seed_coverage_gap, "LINT-MARK-K-GAP")
    assert f.extra["missing"] == 3 and f.extra["required"] == 4


def _seed_indivisible(x):
    return pl.pallas_call(  # LINT-MARK-K-DIV
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((20, 128), jnp.float32),
        grid=(3,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)))(x)


def test_kernel_grid_divisibility_fires_with_exact_line():
    found = kernel_checks.verify_kernel(
        _seed_indivisible, jax.ShapeDtypeStruct((20, 128), jnp.float32))
    hits = _k_rules(found, "kernel-grid-divisibility")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error"
    assert f.line == _marker_line(_seed_indivisible, "LINT-MARK-K-DIV")
    assert "20 % 8" in f.message


def _seed_mosaic_bf16(x):
    return pl.pallas_call(  # LINT-MARK-K-MOSAIC
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((256,), jnp.bfloat16),
        grid=(2,),
        in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
        out_specs=pl.BlockSpec((128,), lambda i: (i,)))(x)


def _seed_mosaic_f32(x):
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((256,), jnp.float32),
        grid=(2,),
        in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
        out_specs=pl.BlockSpec((128,), lambda i: (i,)))(x)


def test_kernel_mosaic_block_is_dtype_aware():
    # rank-1 (128,) blocks: legal for f32 (% 128), ILLEGAL for bf16
    # (% 256) — the dtype-aware case a shape-only AST rule cannot judge
    found = kernel_checks.verify_kernel(
        _seed_mosaic_bf16, jax.ShapeDtypeStruct((256,), jnp.bfloat16))
    hits = _k_rules(found, "kernel-mosaic-block")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error"
    assert f.line == _marker_line(_seed_mosaic_bf16, "LINT-MARK-K-MOSAIC")
    assert "16-bit" in f.message

    clean = kernel_checks.verify_kernel(
        _seed_mosaic_f32, jax.ShapeDtypeStruct((256,), jnp.float32))
    assert _k_rules(clean, "kernel-mosaic-block") == []


def _seed_vmem_blowout(x):
    return pl.pallas_call(  # LINT-MARK-K-VMEM
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((8192, 512), jnp.float32),
        grid=(1,),
        in_specs=[pl.BlockSpec((8192, 512), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8192, 512), lambda i: (0, 0)))(x)


def test_kernel_vmem_budget_fires_with_exact_line():
    # 16 MiB in + 16 MiB out resident blocks vs the 12 MiB default budget
    found = kernel_checks.verify_kernel(
        _seed_vmem_blowout, jax.ShapeDtypeStruct((8192, 512), jnp.float32))
    hits = _k_rules(found, "kernel-vmem-budget")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "warning"
    assert f.line == _marker_line(_seed_vmem_blowout, "LINT-MARK-K-VMEM")
    assert f.extra["vmem_bytes"] == 2 * 8192 * 512 * 4


def test_kernel_vmem_budget_knob_override():
    # the config knob moves the verdict without touching the kernel
    found = kernel_checks.verify_kernel(
        _seed_vmem_blowout, jax.ShapeDtypeStruct((8192, 512), jnp.float32),
        config={"vmem_budget_bytes": 64 << 20})
    assert _k_rules(found, "kernel-vmem-budget") == []


def test_kernel_vmem_estimate_lands_in_xmem():
    from paddle_tpu.profiler import xmem
    xmem.reset()
    kernel_checks.verify_kernel(
        _seed_vmem_blowout, jax.ShapeDtypeStruct((8192, 512), jnp.float32))
    ests = xmem.kernel_estimates()
    assert any(e["kernel"] == "_copy_kernel"
               and e["vmem_bytes"] == 2 * 8192 * 512 * 4 for e in ests)
    assert any("Pallas kernels" in ln for ln in xmem.summary_lines())


def _leaky_kernel(x_ref, o_ref, acc_ref, spare_ref):
    acc_ref[...] = x_ref[...]
    o_ref[...] = acc_ref[...].astype(jnp.float32)


def _seed_body_hazards(x):
    return pl.pallas_call(  # LINT-MARK-K-BODY
        _leaky_kernel,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        grid=(1,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16),
                        pltpu.VMEM((8, 128), jnp.float32)])(x)


def test_kernel_unused_ref_and_narrow_accumulator_fire():
    found = kernel_checks.verify_kernel(
        _seed_body_hazards, jax.ShapeDtypeStruct((8, 128), jnp.bfloat16))
    unused = _k_rules(found, "kernel-unused-ref")
    assert unused, [f.to_dict() for f in found]
    assert unused[0].extra["ref"] == "spare_ref"
    assert unused[0].severity == "warning"
    # unused-ref is attributed to the kernel DEF, not the call site
    assert unused[0].line == _leaky_kernel.__code__.co_firstlineno
    narrow = _k_rules(found, "kernel-narrow-accumulator")
    assert narrow and narrow[0].extra["scratch_dtype"] == "bfloat16"


def test_kernel_clean_case_is_clean():
    def run(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)))(x)
    assert kernel_checks.verify_kernel(run, _F32_16x128) == []


def test_kernel_pragma_suppresses():
    def run(x):
        return pl.pallas_call(  # tpu-lint: disable=kernel-grid-divisibility
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct((20, 128), jnp.float32),
            grid=(3,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)))(x)
    found = kernel_checks.verify_kernel(
        run, jax.ShapeDtypeStruct((20, 128), jnp.float32))
    assert _k_rules(found, "kernel-grid-divisibility") == []


def _sp_gather_kernel(tbl_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...]


# a serving-style block table drives the index maps through scalar
# prefetch; concrete entries make the maps provable, so a bad entry is
# a verifier error rather than silent garbage reads on hardware
_SP_TBL_OOB = np.asarray([0, 1, 9], np.int32)   # page 9 of a 4-page pool
_SP_TBL_OK = np.asarray([2, 1, 0], np.int32)


def _seed_sp_table_oob(x):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(3,),
        in_specs=[pl.BlockSpec((8, 128), lambda i, tbl: (tbl[i], 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i, tbl: (i, 0)))
    return pl.pallas_call(  # LINT-MARK-K-SP-OOB
        _sp_gather_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((24, 128), jnp.float32))(
        _SP_TBL_OOB, x)


def test_kernel_scalar_prefetch_table_oob_fires():
    found = kernel_checks.verify_kernel(
        _seed_sp_table_oob, jax.ShapeDtypeStruct((32, 128), jnp.float32))
    hits = _k_rules(found, "kernel-index-oob")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error" and f.source == "kernel"
    assert f.line == _marker_line(_seed_sp_table_oob, "LINT-MARK-K-SP-OOB")


def _seed_sp_output_gap(x):
    # the table is in range, but the OUTPUT map pins every grid step to
    # the same block — blocks 0 and 1 of the output are never written
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(3,),
        in_specs=[pl.BlockSpec((8, 128), lambda i, tbl: (tbl[i], 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i, tbl: (tbl[0], 0)))
    return pl.pallas_call(  # LINT-MARK-K-SP-GAP
        _sp_gather_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((24, 128), jnp.float32))(
        _SP_TBL_OK, x)


def test_kernel_scalar_prefetch_output_gap_fires():
    found = kernel_checks.verify_kernel(
        _seed_sp_output_gap, jax.ShapeDtypeStruct((32, 128), jnp.float32))
    hits = _k_rules(found, "kernel-output-coverage")
    assert hits, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error"
    assert f.line == _marker_line(_seed_sp_output_gap, "LINT-MARK-K-SP-GAP")
    # and the table OOB rule stays quiet: the defect is coverage only
    assert _k_rules(found, "kernel-index-oob") == []


def _dma_gather_kernel(tbl_ref, x_hbm, o_ref, buf, sem):
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(x_hbm.at[tbl_ref[i]], buf, sem)
    copy.start()
    copy.wait()
    o_ref[...] = buf[...]


def _dma_gather(table):
    """A kernel that reads an HBM operand by its own DMA at rows it takes
    from a scalar-prefetch table, and says so in its metadata: no index
    map carries the table, so only the declaration lets it be bounded."""
    def run(x):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(3,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((8, 128), lambda i, tbl: (i, 0)),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA(())])
        return pl.pallas_call(  # LINT-MARK-K-DMA-OOB
            _dma_gather_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((24, 128), jnp.float32),
            metadata={"dma_indexes": "[[0, 0, 0]]"})(table, x)
    return run


@pytest.mark.parametrize("table,oob", [(_SP_TBL_OK, False),
                                       (_SP_TBL_OOB, True),
                                       (np.asarray([0, -1, 2], np.int32),
                                        True)],
                         ids=["in-range", "past-the-end", "negative"])
def test_kernel_dma_index_table_is_bounded_by_its_declaration(table, oob):
    # a 4-row pool in HBM; 1 GiB of it must not count as VMEM either
    pool = jax.ShapeDtypeStruct((4, 8, 128), jnp.float32)
    run = _dma_gather(table)
    found = kernel_checks.verify_kernel(run, pool)
    hits = _k_rules(found, "kernel-index-oob")
    assert bool(hits) == oob, [f.to_dict() for f in found]
    if oob:
        assert hits[0].severity == "error"
        assert hits[0].extra["extent"] == 4
        assert hits[0].line == _marker_line(run, "LINT-MARK-K-DMA-OOB")
    assert [f for f in found if f not in hits] == []


def test_kernel_hbm_operand_is_not_counted_as_vmem():
    big = jax.ShapeDtypeStruct((1 << 16, 8, 128), jnp.float32)   # 256 MiB
    found = kernel_checks.verify_kernel(_dma_gather(_SP_TBL_OK), big)
    assert _k_rules(found, "kernel-vmem-budget") == []


def test_shipped_pallas_kernels_verify_clean():
    """ISSUE acceptance: every kernel in ops/pallas_ops.py verifies
    clean on CPU — flash fwd/bwd (streamed + resident, f32 + bf16), the
    fused decoder-block kernels (fwd + vjp-captured bwd), and the
    ragged-paged-attention serving kernel (mixed + decode buckets)."""
    cases = kernel_checks.registered_cases()
    names = {c[0] for c in cases}
    assert {"flash_fwd_streamed", "flash_bwd_streamed",
            "flash_fwd_resident", "flash_bwd_resident",
            "fused_attention_block", "fused_mlp_block",
            "ragged_paged_attention",
            "ragged_paged_attention_decode"} <= names
    found = kernel_checks.verify_registered()
    assert found == [], [f.to_dict() for f in found]


def test_autotune_rejects_verifier_refuted_candidates():
    from paddle_tpu.ops import autotune
    timed = []

    def time_candidate(cand):
        timed.append(cand)
        return 1.0

    def verify(cand):
        return ["refuted"] if cand == (4, 256) else []

    best = autotune.tune("t_verify_gate", ["k1"],
                         [(4, 256), (8, 128)], time_candidate,
                         verify_candidate=verify)
    assert best == (8, 128)
    assert (4, 256) not in timed  # refuted BEFORE any compile/measure


def test_to_static_lint_true_verifies_kernels():
    # the Level-3 shim rides the same trace the lint hook already does;
    # the seeded defect (an output ref the kernel never writes) is
    # harmless at run time, so the call itself still works
    def two_out_kernel(x_ref, o_ref, dead_ref):
        o_ref[...] = x_ref[...] * 2.0

    @paddle.jit.to_static(lint=True)
    def step(x):
        y, _ = pl.pallas_call(
            two_out_kernel,
            out_shape=[jax.ShapeDtypeStruct((8, 128), jnp.float32),
                       jax.ShapeDtypeStruct((8, 128), jnp.float32)],
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0)),
                       pl.BlockSpec((8, 128), lambda i: (0, 0))],
            interpret=True)(x._array)
        return paddle.to_tensor(y)

    out = step(paddle.to_tensor(np.ones((8, 128), np.float32)))
    np.testing.assert_allclose(out.numpy(), 2.0 * np.ones((8, 128)))
    found = analysis.findings()
    hits = [f for f in found if f.rule == "kernel-unused-ref"]
    assert hits, [f.to_dict() for f in found]
    assert hits[0].extra["ref"] == "dead_ref"


# ---------------------------------------------------------------------------
# Level 3: SPMD collective-consistency checker
# ---------------------------------------------------------------------------

def test_spmd_divergent_collectives_rank_dependent_cond():
    def step(x):
        i = lax.axis_index("i")
        return lax.cond(i == 0,  # LINT-MARK-SPMD-COND
                        lambda v: lax.psum(v, "i"),
                        lambda v: v * 2.0, x)

    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step")
    hits = [f for f in found if f.rule == "spmd-divergent-collectives"]
    assert len(hits) == 1, [f.to_dict() for f in found]
    f = hits[0]
    assert f.severity == "error" and f.source == "spmd"
    assert f.extra["rank_dependent"] is True
    assert "WILL take different branches" in f.message
    assert f.file and f.file.endswith("test_analysis.py")
    assert f.line == _marker_line(step, "LINT-MARK-SPMD-COND")


def test_spmd_divergent_collective_order():
    # same collectives, different ORDER across branches — still a
    # deadlock precursor (rank A waits in psum while rank B waits in
    # pmax)
    def step(p, x):
        return lax.cond(
            p,
            lambda v: lax.pmax(lax.psum(v, "i"), "i"),
            lambda v: lax.psum(lax.pmax(v, "i"), "i"), x)

    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(
        np.array(True), jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step")
    hits = [f for f in found if f.rule == "spmd-divergent-collectives"]
    assert hits, [f.to_dict() for f in found]
    # uniform predicate: divergence is proven, rank-dependence is not
    assert hits[0].extra["rank_dependent"] is False


def test_spmd_symmetric_cond_is_clean():
    def step(p, x):
        return lax.cond(p,
                        lambda v: lax.psum(v, "i") * 2.0,
                        lambda v: lax.psum(v * 2.0, "i"), x)
    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(
        np.array(True), jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step")
    assert "spmd-divergent-collectives" not in _rules_of(found)


def test_spmd_divergence_found_inside_jit():
    # the walker recurses through the pjit wrapper and recomputes taint
    # with the inner jaxpr's invars seeded from the outer scope
    def step(x):
        i = lax.axis_index("i")

        @jax.jit
        def inner(v, j):
            return lax.cond(j == 0, lambda u: lax.psum(u, "i"),
                            lambda u: u * 2.0, v)
        return inner(x, i)

    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step")
    hits = [f for f in found if f.rule == "spmd-divergent-collectives"]
    assert hits and hits[0].extra["rank_dependent"] is True


def test_spmd_rank_dependent_loop_fires():
    def step(x):
        i = lax.axis_index("i")

        def cond(c):
            return c[0] < i  # trip count differs per rank

        def body(c):
            return (c[0] + 1, lax.psum(c[1], "i"))

        return lax.while_loop(cond, body, (jnp.int32(0), x))

    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step")
    hits = [f for f in found if f.rule == "spmd-rank-dependent-loop"]
    assert hits, [f.to_dict() for f in found]
    assert hits[0].severity == "error"


def test_spmd_uniform_loop_with_collective_is_clean():
    def step(x):
        def cond(c):
            return c[0] < 3

        def body(c):
            return (c[0] + 1, lax.psum(c[1], "i"))

        return lax.while_loop(cond, body, (jnp.int32(0), x))

    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step")
    assert "spmd-rank-dependent-loop" not in _rules_of(found)


def test_spmd_axis_misuse_fires_for_unknown_axis():
    def step(x):
        return lax.psum(x, "model")
    closed = jax.make_jaxpr(step, axis_env=[("model", 2)])(jnp.ones((4,)))
    found = spmd_checks.check_spmd(closed, name="step",
                                   axis_names=("data",))
    hits = [f for f in found if f.rule == "spmd-axis-misuse"]
    assert hits, [f.to_dict() for f in found]
    clean = spmd_checks.check_spmd(closed, name="step",
                                   axis_names=("data", "model"))
    assert "spmd-axis-misuse" not in _rules_of(clean)


def test_check_jaxpr_merges_spmd_rules():
    # the Level-1 entry point now carries the Level-3 SPMD rules too
    def step(x):
        i = lax.axis_index("i")
        return lax.cond(i == 0, lambda v: lax.psum(v, "i"),
                        lambda v: v * 2.0, x)
    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(jnp.ones((4,)))
    rules = _rules_of(jaxpr_checks.check_jaxpr(closed, name="step"))
    assert "spmd-divergent-collectives" in rules
    assert "collective-divergence" in rules  # L1 rule still present


def test_collective_events_signature():
    def step(x):
        y = lax.psum(x, "i")
        return lax.pmax(y, "i")
    closed = jax.make_jaxpr(step, axis_env=[("i", 2)])(jnp.ones((4,)))
    events = spmd_checks.collective_events(closed.jaxpr)
    assert [e[0] for e in events] == ["psum", "pmax"]
    assert all(e[1] == ("i",) for e in events)


# ---------------------------------------------------------------------------
# Level 3: CLI --kernels mode + --format=github
# ---------------------------------------------------------------------------

_CLI = os.path.join(REPO, "tools", "tpu_lint.py")


def test_cli_kernels_mode_self_hosted_acceptance():
    """ISSUE acceptance: the full self-hosted run INCLUDING the kernel
    registry sweep exits 0 — all shipped kernels verify clean."""
    proc = subprocess.run(
        [sys.executable, _CLI, os.path.join(REPO, "paddle_tpu"),
         "--kernels"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] is True
    assert doc["kernel_cases"] >= 6


def test_cli_kernels_mode_exit_code_on_defect(tmp_path):
    bad = tmp_path / "bad_kernels.py"
    bad.write_text(textwrap.dedent("""\
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _k(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def _run(x):
            return pl.pallas_call(
                _k,
                out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
                grid=(2,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i + 1, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)))(x)

        def kernel_verify_cases():
            return [("bad_copy", _run,
                     (jax.ShapeDtypeStruct((16, 128), jnp.float32),))]
    """))
    proc = subprocess.run(
        [sys.executable, _CLI, str(bad), "--kernels", "--no-baseline"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    oob = [f for f in doc["new"] if f["rule"] == "kernel-index-oob"]
    assert oob and oob[0]["severity"] == "error"
    assert oob[0]["file"].endswith("bad_kernels.py")
    assert oob[0]["line"] == 9  # the pl.pallas_call( line


def test_cli_github_format_annotations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""\
        import jax.numpy as jnp
        def f(xs, g):
            for x in xs:
                v = float(jnp.dot(x, g))
            return v
    """))
    proc = subprocess.run(
        [sys.executable, _CLI, str(bad), "--no-baseline",
         "--format=github"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2
    lines = proc.stdout.splitlines()
    err = [ln for ln in lines if ln.startswith("::error ")]
    assert err and "line=4" in err[0] and "[host-sync-in-loop]" in err[0]
    assert any(ln.startswith("::notice::") for ln in lines)
    # github mode replaces the JSON document entirely
    assert not any(ln.lstrip().startswith("{") for ln in lines)


def test_cli_list_rules_covers_all_levels():
    proc = subprocess.run(
        [sys.executable, _CLI, "x", "--list-rules"],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    catalogue = json.loads(proc.stdout)
    levels = {v["level"] for v in catalogue.values()}
    assert levels == {"ast", "jaxpr", "spmd", "kernel"}
    assert catalogue["kernel-index-oob"]["severity"] == "error"
    assert catalogue["spmd-divergent-collectives"]["severity"] == "error"
