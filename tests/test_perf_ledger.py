"""Perf ledger (ISSUE 17): schema round-trip, direction-aware regression
gate, staleness verdict, normalizers, and the stdlib-only CLI.

Everything runs on rows built here or under ``tmp_path``: the repo tracks
no ledger of its own (``PERF_LEDGER.jsonl`` at the root is the driver's
file, which the repo's tools never write), so no test reads a record
file. A seeded tokens/s regression and a stale-measurement ledger both
exit 1; schema garbage exits 2; ``append``/``report``/``check`` run with
no jax import.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "perf_ledger.py")

# a bench_serve.py result line, as the script prints it on a CPU smoke
_SERVE_LINE = {
    "metric": "serve_tokens_per_sec_chip", "value": 263.35,
    "unit": "tokens/s/chip", "ttft_p95_ms": 10.0, "latency_p95_ms": 80.0,
    "requests": 6, "workload": "uniform", "tokens": 96, "steps": 40,
    "reuse": {"prefix_hit_rate": 0.0}, "kv": {"dtype": "bf16"},
    "preset": "llama-debug", "platform": "cpu", "device": "cpu", "chips": 1}


@pytest.fixture(scope="module")
def L():
    """ledger.py loaded standalone — the tools/perf_ledger.py path."""
    spec = importlib.util.spec_from_file_location(
        "_ledger_under_test",
        os.path.join(REPO, "paddle_tpu", "profiler", "ledger.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _run_cli(*args, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, env=env,
                          timeout=120)


def _measured(L, value, *, round, metric="tokens_per_sec_per_chip",
              source="bench.py", real=True):
    return L.new_record(source, {metric: value}, kind="measured",
                        round=round,
                        provenance={"device": "TPU v5e" if real else "cpu",
                                    "real_device": real})


# ---------------------------------------------------------------------------
# schema round-trip + validation
# ---------------------------------------------------------------------------


def test_record_roundtrip(L, tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rec = L.new_record("bench.py", {"mfu_percent": 62.41,
                                    "tokens_per_sec_per_chip": 20082.8},
                       round=5, ts=1234.5,
                       provenance=L.collect_provenance(device="TPU v5e"),
                       detail={"note": "roundtrip"})
    L.append(path, rec)
    (back,) = L.load(path)
    assert back == json.loads(L.dumps(rec))
    assert back["schema"] == L.SCHEMA
    assert back["provenance"]["real_device"] is True


def test_unknown_metric_rejected(L):
    with pytest.raises(L.LedgerSchemaError, match="unknown metric"):
        L.new_record("bench.py", {"tokens_per_sec": 1.0})


def test_measured_metric_cannot_ride_proxy_row(L):
    with pytest.raises(L.LedgerSchemaError, match="measured-only"):
        L.new_record("pod_report", {"mfu_percent": 62.0}, kind="proxy")


def test_load_rejects_garbage_with_line_number(L, tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"schema": "paddle_tpu.perf_ledger.v1", "round": 1, '
                    '"source": "bench.py", "kind": "measured", '
                    '"metrics": {"mfu_percent": 1.0}}\nnot json\n')
    with pytest.raises(L.LedgerSchemaError, match=":2:"):
        L.load(str(path))


def test_every_metric_declares_direction(L):
    for name, spec in L.METRICS.items():
        assert spec.direction in ("higher", "lower"), name
        assert isinstance(spec.proxy, bool), name


# ---------------------------------------------------------------------------
# direction-aware gate
# ---------------------------------------------------------------------------


def test_gate_fires_on_higher_better_regression(L):
    recs = [_measured(L, 20000.0, round=1), _measured(L, 17000.0, round=2)]
    verdict = L.check(recs, tol=0.05)
    assert not verdict["ok"]
    (r,) = verdict["regressions"]
    assert r["metric"] == "tokens_per_sec_per_chip"
    assert r["latest"] == 17000.0


def test_gate_passes_on_improvement_and_in_band_noise(L):
    # improvement: must NOT fire, this is the whole point of direction
    assert L.check([_measured(L, 20000.0, round=1),
                    _measured(L, 25000.0, round=2)], tol=0.05)["ok"]
    # 3% dip is inside the 5% tolerance band
    assert L.check([_measured(L, 20000.0, round=1),
                    _measured(L, 19400.0, round=2)], tol=0.05)["ok"]


def test_gate_fires_on_lower_better_regression(L):
    recs = [_measured(L, 140.0, round=1, metric="multichip_step_ms",
                      source="bench.py --multichip"),
            _measured(L, 180.0, round=2, metric="multichip_step_ms",
                      source="bench.py --multichip")]
    assert not L.check(recs, tol=0.05)["ok"]
    # and the mirror-image improvement passes
    recs = [_measured(L, 180.0, round=1, metric="multichip_step_ms",
                      source="bench.py --multichip"),
            _measured(L, 140.0, round=2, metric="multichip_step_ms",
                      source="bench.py --multichip")]
    assert L.check(recs, tol=0.05)["ok"]


def test_gate_separates_series_by_label(L):
    # int8 and bf16 serve lines are different series: one regressing
    # while the other improves must flag exactly the regressing one
    def serve(value, label, rnd):
        return L.new_record("bench_serve.py",
                            {"serve_tokens_per_sec_chip": value},
                            label=label, round=rnd,
                            provenance={"real_device": True})
    recs = [serve(250.0, "kv=bf16", 1), serve(100.0, "kv=int8", 1),
            serve(260.0, "kv=bf16", 2), serve(80.0, "kv=int8", 2)]
    verdict = L.check(recs, tol=0.05)
    assert [r["label"] for r in verdict["regressions"]] == ["kv=int8"]


def test_staleness_verdict(L):
    recs = [_measured(L, 20000.0, round=3),
            L.new_record("bench.py", {}, kind="error", round=6)]
    verdict = L.check(recs, stale_after=3)
    assert not verdict["ok"]
    assert verdict["stale"]["age_rounds"] == 3
    assert verdict["stale"]["newest_measured_round"] == 3
    # a fresh real-device measurement clears it
    recs.append(_measured(L, 20100.0, round=6))
    assert L.check(recs, stale_after=3)["ok"]


def test_cpu_smoke_does_not_refresh_staleness_clock(L):
    # the r04/r05 failure mode: CPU rows must not masquerade as fresh
    # silicon measurements
    recs = [_measured(L, 20000.0, round=1),
            _measured(L, 150.0, round=6, metric="multichip_step_ms",
                      source="bench.py --multichip", real=False)]
    verdict = L.check(recs, stale_after=3)
    assert verdict["stale"]["newest_measured_round"] == 1


def test_proxies_only_gates_proxies_and_skips_staleness(L):
    stale_measured = [_measured(L, 20000.0, round=1),
                      L.new_record("bench.py", {}, kind="error", round=9)]
    proxies = [L.new_record("pod_report", {"plan_capacity": 32.0},
                            kind="proxy", round=8),
               L.new_record("pod_report", {"plan_capacity": 16.0},
                            kind="proxy", round=9)]
    # full check: stale; proxies-only: staleness waived but the halved
    # plan_capacity still fires
    assert not L.check(stale_measured, stale_after=3)["ok"]
    assert L.check(stale_measured, stale_after=3,
                   proxies_only=True)["ok"]
    verdict = L.check(stale_measured + proxies, proxies_only=True)
    assert [r["metric"] for r in verdict["regressions"]] == \
        ["plan_capacity"]


# ---------------------------------------------------------------------------
# normalizers + artifact ingestion
# ---------------------------------------------------------------------------


def test_from_bench_serve_result_labels_series(L):
    row = L.from_bench_serve_result(_SERVE_LINE, round=None)
    assert row["label"] == "llama-debug:uniform:kv=bf16"
    assert row["metrics"]["serve_tokens_per_sec_chip"] == 263.35
    assert row["metrics"]["serve_ttft_p95_ms"] == 10.0
    assert row["provenance"]["real_device"] is False


def test_from_pod_report_serving_shape(L):
    report = {"mode": "serving", "preset": "llama7b", "mesh": "v5p-16",
              "serving": {"max_concurrent_requests": 64,
                          "capacity_ratio_vs_bf16": 1.0,
                          "fleet": {"min_replicas": 2}}}
    row = L.from_pod_report(report, round=7)
    assert row["kind"] == "proxy"
    assert row["metrics"] == {"plan_capacity": 64.0,
                              "kv_capacity_ratio_vs_bf16": 1.0,
                              "fleet_min_replicas": 2.0}


# ---------------------------------------------------------------------------
# CLI: exit-code matrix, no-jax guard, tier-1 proxy ratchet
# ---------------------------------------------------------------------------


def test_cli_exit_1_on_seeded_regression(L, tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    L.append(path, _measured(L, 20000.0, round=1))
    L.append(path, _measured(L, 15000.0, round=2))
    p = _run_cli("--ledger", path, "check")
    assert p.returncode == 1, p.stdout + p.stderr
    verdict = json.loads(p.stdout)
    assert verdict["regressions"]


def test_cli_exit_1_on_stale_ledger(L, tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    L.append(path, _measured(L, 20000.0, round=2))
    L.append(path, L.new_record("bench.py", {}, kind="error", round=9))
    p = _run_cli("--ledger", path, "check")
    assert p.returncode == 1, p.stdout + p.stderr
    assert json.loads(p.stdout)["stale"]


def test_cli_exit_2_on_schema_garbage(tmp_path):
    path = tmp_path / "ledger.jsonl"
    path.write_text('{"schema": "v0-prehistoric", "metrics": {}}\n')
    p = _run_cli("--ledger", str(path), "check")
    assert p.returncode == 2
    assert "schema error" in p.stderr
    # missing ledger file is also a usage error, not a crash
    p = _run_cli("--ledger", str(tmp_path / "nope.jsonl"), "check")
    assert p.returncode == 2


def test_cli_append_report_check_run_without_jax(tmp_path):
    poison = tmp_path / "poison"
    poison.mkdir()
    (poison / "jax.py").write_text(
        "raise ImportError('perf_ledger must not import jax')\n")
    env = {"PYTHONPATH": str(poison)}
    path = str(tmp_path / "ledger.jsonl")
    serve = tmp_path / "serve_line.json"
    serve.write_text(json.dumps(_SERVE_LINE))
    for artifact in (str(serve), os.path.join(REPO, "FLEET_r01.json")):
        p = _run_cli("--ledger", path, "append", artifact, env_extra=env)
        assert p.returncode == 0, p.stderr
    p = _run_cli("--ledger", path, "report", env_extra=env)
    assert p.returncode == 0, p.stderr
    assert "263.35" in p.stdout
    p = _run_cli("--ledger", path, "report", "--format", "json",
                 env_extra=env)
    assert json.loads(p.stdout)["rows"] == 2
    p = _run_cli("--ledger", path, "check", "--proxies-only",
                 env_extra=env)
    assert p.returncode == 0, p.stdout + p.stderr
    verdict = json.loads(p.stdout)
    assert verdict["proxies_only"] and verdict["ok"]


def test_default_ledger_is_not_the_drivers_file():
    """Nothing in the repo writes <repo>/PERF_LEDGER.jsonl: the tool and
    both benches default to runs/perf_ledger.jsonl."""
    p = _run_cli("--help")
    assert "runs/perf_ledger.jsonl" in p.stdout
    for script in ("bench.py", "bench_serve.py",
                   os.path.join("tools", "perf_ledger.py"),
                   os.path.join("tools", "pod_report.py")):
        with open(os.path.join(REPO, script)) as f:
            src = f.read()
        assert '"runs", "perf_ledger.jsonl")' in src
        assert '"PERF_LEDGER.jsonl"' not in src


def test_bench_ledger_out_appends_error_row(tmp_path):
    """bench.py --ledger-out writes a ledger row even when the bench
    fails (no chip here) — error rounds are history too — and the exit
    code says it failed."""
    path = str(tmp_path / "ledger.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--ledger-out", path],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 1
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert line, p.stdout + p.stderr
    assert json.loads(line[-1])["error"]
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    assert len(rows) == 1
    assert rows[0]["kind"] == "error"
    assert rows[0]["provenance"]["cmd"].startswith("python bench.py")
