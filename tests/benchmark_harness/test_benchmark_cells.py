"""Both kinds of cell end to end, in-process, at llama-debug width on the
CPU, from a benchmark root in a temporary directory: a cell, a
configuration, two traffic files and a per-layer metric that are new files
beside one BENCHMARK.json — no file under benchmark/ is edited to add them.
And the command itself, which prints no result without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import bench_testlib
from benchmark import contract, harness, trace_reduce

TRAIN, SERVE = "debug.tiny-train", "debug.tiny-closed"
SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.fixture(autouse=True)
def _cpu_reports_no_memory(monkeypatch):
    # XLA:CPU has no memory_stats(); every other part of the line is real
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 123456)


def fake_tpu_reduction(n_ops=6):
    """What reduce_dir gives for a TPU trace, built by the real reduction
    from plain events (the CPU's own trace has no TPU plane)."""
    ops = [("fusion.%d" % i, 100 * i, 60) for i in range(n_ops)]
    return trace_reduce.reduce_events({
        "/host:CPU": {"python": [(trace_reduce.SLICE_NAME, 0, 100 * n_ops)]},
        "/device:TPU:0": {"XLA Ops": ops}})


@pytest.mark.parametrize("workload,rate", [
    (TRAIN, "train_tokens_per_s"), (SERVE, "serve_tokens_per_s")])
def test_cell_runs_end_to_end_untraced(root, workload, rate, capsys):
    spec = harness.load_spec(root)
    result = harness.run_cell(root, spec, workload, SEED, 1.2, False,
                              time.perf_counter())
    contract.check_result(result, spec, workload, False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"       # names what it ran on
    assert set(result["metrics"]) == set(
        contract.cell_metrics(spec, workload, "end_to_end"))
    assert result["metrics"][rate]["value"] > 0
    assert harness.print_result(result, spec, workload, False) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == result


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_cell_runs_end_to_end_traced(root, workload, monkeypatch):
    monkeypatch.setattr(trace_reduce, "reduce_dir",
                        lambda d, host_window_s: fake_tpu_reduction())
    spec = harness.load_spec(root)
    result = harness.run_cell(root, spec, workload, SEED, 1.2, True,
                              time.perf_counter())
    contract.check_result(result, spec, workload, True)
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert dev["busy_s"] == pytest.approx(6 * 60e-9)
    e2e = set(contract.cell_metrics(spec, workload, "end_to_end"))
    assert e2e <= set(result["metrics"])
    if workload == SERVE:
        # the per-layer metric that only the temporary directory defines,
        # found by its name in BENCHMARK.json
        assert result["metrics"]["steps_counted"]["value"] > 10
        assert result["metrics"]["steps_counted"]["unit"] == "steps"
        assert result["metrics"]["host_gap_ms_per_step"]["value"] > 0
        assert result["metrics"]["engine_step_ms"]["value"] > 0
    else:
        assert result["metrics"]["train_step_ms"]["value"] > 0
        # no Mosaic kernel on the CPU: its reader finds nothing to read
        # and the harness leaves the metric out of the line
        assert "pallas_share_pct.train" not in result["metrics"]
    assert len(result["breakdown"]["device_ops"]) == 6


def test_mfu_reader_uses_the_peak_of_the_device_it_ran_on():
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs",
                           "deepseek-coder-1.3b.json")) as f:
        config = json.load(f)
    read = harness.load_module(os.path.join(
        bench_testlib.REPO, "benchmark", "layers", "train_mfu_pct.py")).read
    run = {"end_to_end": {"train_tokens_per_s": 7000.0}, "config": config,
           "counters": {"seq": 2048}, "device_kind": "TPU v5 lite"}
    assert read(run) == pytest.approx(100 * 8.286e9 * 7000 / 197e12, rel=1e-3)
    with pytest.raises(KeyError, match="no peaks for device_kind 'cpu'"):
        read(dict(run, device_kind="cpu"))     # never a default peak


LAYERS = os.path.join(bench_testlib.REPO, "benchmark", "layers")
READ = {
    "ttft_p95_ms": ({"samples": {"ttft_s": [0.1 * i for i in range(1, 21)]}},
                    1905.0),
    "queue_wait_p95_ms": ({"samples": {"queue_s": [0.001] * 19 + [0.5]}},
                          25.95),
    "engine_step_ms": ({"samples": {"engine_step_s": {
        16: [0.1, 0.1, 0.3], 1: [0.02, 0.02]}}}, 100.0),
    "host_gap_ms_per_step": ({"trace": {"window_s": 2.0, "busy_s": 1.9},
                              "counters": {"trace_steps": 20}}, 5.0),
    "pallas_share_pct": ({"trace": {"mosaic_s": 0.5, "busy_s": 2.0},
                          "kernels": ["_rpa_kernel"]}, 25.0),
    "train_step_ms": ({"samples": {"step_s": [1.1, 1.2, 2.5]}}, 1200.0),
}


@pytest.mark.parametrize("name", sorted(READ))
def test_a_reader_takes_its_number_from_the_run_or_finds_nothing(name):
    read = harness.load_module(os.path.join(LAYERS, name + ".py")).read
    run, want = READ[name]
    assert read(run) == pytest.approx(want)
    empty = {"samples": {}, "trace": None, "kernels": [],
             "counters": {}}
    assert read(empty) is None


def test_a_trace_with_no_tpu_plane_fails_the_run(root):
    """The CPU's own profile, through the real reduction: no device plane,
    so the traced run raises instead of printing a busy time of 0."""
    spec = harness.load_spec(root)
    with pytest.raises(trace_reduce.TraceError, match="no TPU plane"):
        harness.run_cell(root, spec, TRAIN, SEED, 1.0, True,
                         time.perf_counter())


def test_a_result_that_breaks_the_contract_is_not_printed(root, capsys):
    spec = harness.load_spec(root)
    bad = {"correct": True, "attempted": 3, "failed": 0,
           "metrics": {"setup_s": {"value": 1.0, "unit": "s"}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1, "window_s": 1.0, "busy_s": 0.0}}
    assert harness.print_result(bad, spec, TRAIN, True) == 1
    out = capsys.readouterr()
    assert out.out == "" and "breaks the contract" in out.err


def test_an_unknown_workload_or_kind_is_an_error(root, tmp_path):
    spec = harness.load_spec(root)
    with pytest.raises(KeyError, match="no workload"):
        harness.run_cell(root, spec, "debug.nothing", 1, 1.0, False, 0.0)
    with pytest.raises(FileNotFoundError, match="kinds/serve-open.py"):
        harness.find_file(root, spec, "kinds/serve-open.py")


def run_command(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def has_result_line(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_the_command_prints_no_result_without_a_tpu():
    proc = run_command(bench_testlib.REPO, "deepseek-coder-1.3b.train-2k")
    assert proc.returncode != 0
    assert not has_result_line(proc.stdout)
    assert '"platform": "cpu"' in proc.stdout      # names the device it found
    assert "nothing is measured without" in proc.stderr


def test_the_command_fails_where_only_the_benchmark_s_files_are(tmp_path):
    with open(os.path.join(bench_testlib.REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    shutil.copy(os.path.join(bench_testlib.REPO, "BENCHMARK.json"), tmp_path)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(bench_testlib.REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_command(str(tmp_path), spec["workloads"][0]["name"])
    assert proc.returncode != 0 and not has_result_line(proc.stdout)
    proc = run_command(bench_testlib.REPO, "no.such-cell")
    assert proc.returncode != 0 and not has_result_line(proc.stdout)
