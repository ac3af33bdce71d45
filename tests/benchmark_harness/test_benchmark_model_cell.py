"""The kind ``serve-closed-model`` end to end, in-process, at a debug width
on the CPU: a third cell beside ``bench_testlib.make_root``'s two, made of new
files only (a Jamba configuration with one attention layer, its traffic, the
three state-space readers), traced and untraced, contract checked. And the
arithmetic of ``kernel_costs_ssm`` by hand."""
import json
import os
import time

import pytest

import bench_testlib
from benchmark import contract, harness, kernel_costs_ssm, spans, trace_reduce

CELL = "jamba-debug.tiny-reason"
SEED = 2**31 + 2027

JAMBA_DEBUG = {    # six layers, the third one attention: a CPU test size
    "source": "tests only", "model": "jamba", "vocab_size": 256,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 6,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "attn_layer_period": 6, "attn_layer_offset": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "dtype": "bfloat16", "reduced": []}

TINY_REASON = dict(
    bench_testlib.TINY_CLOSED, kind="serve-closed-model",
    check=dict(bench_testlib.TINY_CLOSED["check"], state_rel_tol=0.05,
               state_slow_rel_tol=0.05))

SSM_METRICS = ("ssm_ms_per_step", "ssm_scan_ms_per_step",
               "ssm_scan_roofline_pct")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s benchmark with the third cell added to it."""
    root = bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))
    for rel, body in (("configs/jamba-debug.json", JAMBA_DEBUG),
                      ("traffic/tiny-reason.json", TINY_REASON)):
        with open(os.path.join(root, "extra", rel), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    serve = "debug.tiny-closed"
    spec["configs"].append({
        "name": "jamba-debug", "source": "tests only",
        "file": "extra/configs/jamba-debug.json", "reduced": [],
        "why": "CPU test size"})
    spec["workloads"].append({
        "name": CELL, "config": "jamba-debug", "traffic": "tiny-reason",
        "chips": 1, "why": "serve-closed-model kind end to end"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if serve in m.get("workloads", []):
            m["workloads"].append(CELL)
    spec["per_layer"] += [
        bench_testlib.metric(name, "%" if name.endswith("pct") else "ms",
                             layer="kernels", moves="serve_gap_p95_ms",
                             workloads=[CELL]) for name in SSM_METRICS]
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(autouse=True)
def _cpu_reports_no_memory(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 123456)


def test_the_model_cell_runs_end_to_end_untraced(root, capsys):
    spec = harness.load_spec(root)
    result = harness.run_cell(root, spec, CELL, SEED, 1.2, False,
                              time.perf_counter())
    contract.check_result(result, spec, CELL, False)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s",
                                      "serve_gap_p95_ms", "setup_s"}
    assert result["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert harness.print_result(result, spec, CELL, False) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert "kind serve-closed-model" in out


def test_the_model_cell_runs_end_to_end_traced(root, monkeypatch):
    ops = [("fusion.%d" % i, 100 * i, 60) for i in range(6)]
    monkeypatch.setattr(
        trace_reduce, "reduce_dir",
        lambda d, host_window_s: trace_reduce.reduce_events({
            "/host:CPU": {"python": [(trace_reduce.SLICE_NAME, 0, 600)]},
            "/device:TPU:0": {"XLA Ops": ops}}))
    # the span readers look for the profile under the checkout they are in
    monkeypatch.setattr(spans, "ROOT", root)
    spec = harness.load_spec(root)
    result = harness.run_cell(root, spec, CELL, SEED, 1.2, True,
                              time.perf_counter())
    contract.check_result(result, spec, CELL, True)
    assert result["correct"] is True
    # the accepted readers read this kind's run as they read serve-closed's
    for name in ("engine_step_ms", "steps_counted", "host_gap_ms_per_step",
                 "ttft_p95_ms.closed"):
        assert result["metrics"][name]["value"] > 0
    # the CPU's profile has no device plane, so no operation lies under the
    # scopes: the three readers find nothing and the line leaves them out
    assert not set(SSM_METRICS) & set(result["metrics"])


def test_scan_cost_by_hand():
    config = {"num_hidden_layers": 28, "attn_layer_period": 14,
              "attn_layer_offset": 7, "hidden_size": 2560, "mamba_expand": 2,
              "mamba_d_state": 16, "dtype": "bfloat16"}
    assert kernel_costs_ssm.mamba_layers(config) == 26
    cost = kernel_costs_ssm.scan_step(
        config, {"state_rows": 128, "fed_tokens": 160})
    # a layer: 128 rows x 2 x (5120 x 16 x 4 B) of state = 83,886,080 B,
    # and 160 tokens x (3 x 5120 + 2 x 16) x 2 B = 4,925,440 B
    assert cost["bytes"] == 26 * (83_886_080 + 4_925_440)
    assert cost["flops"] == 26 * 160 * 5120 * 16 * 6
    idle = kernel_costs_ssm.scan_step(
        config, {"state_rows": 0, "fed_tokens": 0})
    assert idle == {"bytes": 0, "flops": 0}


@pytest.mark.parametrize("name", SSM_METRICS)
def test_a_state_space_reader_finds_nothing_in_an_untraced_run(name):
    read = harness.load_module(os.path.join(
        bench_testlib.REPO, "benchmark", "layers", name + ".py")).read
    assert read({"samples": {}, "trace": None, "kernels": [],
                 "counters": {}}) is None
