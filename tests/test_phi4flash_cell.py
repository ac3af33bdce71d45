"""The benchmark's side of the Phi-4-mini-flash cell, off the chip: the
arithmetic of ``benchmark/kernel_costs_phi4flash.py`` by hand, the cell's
files as ``BENCHMARK.json`` names them, its four readers on a run that has
nothing for them, and the kind ``serve-closed-model`` end to end on this
model at a debug width on the CPU (traced and untraced, contract checked),
in a benchmark root made of new files only."""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark_harness"))
import bench_testlib  # noqa: E402
from benchmark import (contract, harness, kernel_costs_phi4flash, spans,  # noqa: E402
                       trace_reduce)
from paddle_tpu.models import phi4flash  # noqa: E402

CELL = "phi-4-mini-flash-reasoning.serve-longreason-closed"
DEBUG_CELL = "phi4flash-debug.tiny-longreason"
SEED = 2**31 + 3131
NEW_METRICS = ("attn_window_ms_per_step", "attn_shared_kv_ms_per_step",
               "gmu_ms_per_step", "rpa_roofline_pct.hybrid")
# what the catalog of public architectures gives for this model
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


def _file(rel):
    with open(os.path.join(bench_testlib.REPO, rel)) as f:
        return json.load(f)


def test_attention_cost_by_hand():
    config = _file("benchmark/configs/phi-4-mini-flash-reasoning.json")
    traffic = _file("benchmark/traffic/serve-longreason-closed.json")
    # a chunk of 16 at positions 1000..1015 and a decode row at 3000:
    step = {"fed_tokens": 17, "kv_tokens": 1016 + 3001,
            "qk_pairs": 16 * 1016 + 3001,
            "window_kv_tokens": (512 + 16 - 1) + 512,
            "window_qk_pairs": 17 * 512}
    # (the two rows are one group of 8 for the scan, which walks 16)
    assert phi4flash.step_counts(
        phi4flash.config_from_fields(config), [1016, 3001], [16, 1]) == dict(
            {k: step[k] for k in ("window_kv_tokens", "window_qk_pairs")},
            scan_positions=8 * 16)
    cost = kernel_costs_phi4flash.rpa_step(config, traffic, step)
    # K and V of a token in one layer: 2 x 20 heads x 64 x 2 B = 5,120 B;
    # eight window layers read 1,039 tokens, eight layers the pool's 4,017;
    # q and o of 17 tokens in sixteen layers, 2,560 x 2 B each
    assert cost["bytes"] == 8 * 5120 * 1039 + 8 * 5120 * 4017 \
        + 16 * 2 * 17 * 2560 * 2
    # a head and pair: 2 x 64 for the score, 2 x 128 for the wide V
    assert cost["flops"] == (8 * 8704 + 8 * 19257) * 40 * (128 + 256)
    assert kernel_costs_phi4flash.rpa_step(config, traffic, dict.fromkeys(
        step, 0)) == {"bytes": 0, "flops": 0}


def test_the_cell_is_the_published_model_uncut():
    spec = harness.load_spec(bench_testlib.REPO)
    cell = harness.find_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-longreason-closed"
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == []
    config = _file(entry["file"])
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == [] and config["model"] == "phi4flash"
    assert "attn_layer_period" not in config and len(config["assumed"]) >= 10
    cfg = phi4flash.config_from_fields(config)
    assert cfg == phi4flash.preset("phi4-mini-flash")
    assert phi4flash.param_count(cfg) == config["parameters"]
    # every request crosses the window and a ring's wrap
    traffic = _file("benchmark/traffic/serve-longreason-closed.json")
    assert traffic["prompt"]["min"] + traffic["output"]["min"] \
        > cfg.sliding_window
    assert traffic["engine"]["max_model_len"] \
        == traffic["prompt"]["max"] + traffic["output"]["max"]
    reported = set(contract.cell_metrics(spec, CELL, "end_to_end"))
    assert reported == {"serve_gap_p95_ms", "setup_s"}
    layers = contract.cell_metrics(spec, CELL, "per_layer")
    assert set(NEW_METRICS) <= set(layers)
    assert not {"rpa_roofline_pct", "ssm_scan_roofline_pct",
                "kv_pool_copy_ms_per_step"} & set(layers)
    assert all(m["moves"] == "serve_gap_p95_ms" for m in layers.values())


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_in_an_untraced_run(name):
    spec = harness.load_spec(bench_testlib.REPO)
    read = harness.load_module(harness.find_reader(
        bench_testlib.REPO, spec, name)).read
    assert read({"samples": {}, "trace": None, "kernels": [],
                 "counters": {}}) is None


PHI_DEBUG = {    # eight layers, all five kinds: a CPU test size
    "source": "tests only", "model": "phi4flash", "vocab_size": 256,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 8, "num_key_value_heads": 4, "sliding_window": 20,
    "mb_per_layer": 2, "layer_norm_eps": 1e-5, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "max_position_embeddings": 512, "dtype": "bfloat16", "reduced": []}

TINY_LONGREASON = dict(
    bench_testlib.TINY_CLOSED, kind="serve-closed-model",
    check=dict(bench_testlib.TINY_CLOSED["check"], state_rel_tol=0.05,
               state_slow_rel_tol=0.05))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s benchmark with this model's cell added to it, and the
    repo's own four readers and cost functions copied beside it."""
    root = bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))
    for rel, body in (("configs/phi4flash-debug.json", PHI_DEBUG),
                      ("traffic/tiny-longreason.json", TINY_LONGREASON)):
        with open(os.path.join(root, "extra", rel), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    serve = "debug.tiny-closed"
    spec["configs"].append({
        "name": "phi4flash-debug", "source": "tests only",
        "file": "extra/configs/phi4flash-debug.json", "reduced": [],
        "why": "CPU test size"})
    spec["workloads"].append({
        "name": DEBUG_CELL, "config": "phi4flash-debug",
        "traffic": "tiny-longreason", "chips": 1,
        "why": "serve-closed-model kind on window rings and shared K/V"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if serve in m.get("workloads", []):
            m["workloads"].append(DEBUG_CELL)
    spec["per_layer"] += [
        bench_testlib.metric(name, "%" if "pct" in name else "ms",
                             layer="kernels", moves="serve_gap_p95_ms",
                             workloads=[DEBUG_CELL]) for name in NEW_METRICS]
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(autouse=True)
def _cpu_reports_no_memory(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 123456)


def test_the_cell_runs_end_to_end_untraced(root, capsys):
    spec = harness.load_spec(root)
    result = harness.run_cell(root, spec, DEBUG_CELL, SEED, 1.2, False,
                              time.perf_counter())
    contract.check_result(result, spec, DEBUG_CELL, False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["serve_gap_p95_ms"]["value"] > 0
    assert harness.print_result(result, spec, DEBUG_CELL, False) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert '"state": true' in out and "state_slow_rel_err" in out


def test_the_cell_runs_end_to_end_traced(root, monkeypatch):
    ops = [("fusion.%d" % i, 100 * i, 60) for i in range(6)]
    monkeypatch.setattr(
        trace_reduce, "reduce_dir",
        lambda d, host_window_s: trace_reduce.reduce_events({
            "/host:CPU": {"python": [(trace_reduce.SLICE_NAME, 0, 600)]},
            "/device:TPU:0": {"XLA Ops": ops}}))
    # the span readers look for the profile under the checkout they are in
    monkeypatch.setattr(spans, "ROOT", root)
    spec = harness.load_spec(root)
    result = harness.run_cell(root, spec, DEBUG_CELL, SEED, 1.2, True,
                              time.perf_counter())
    contract.check_result(result, spec, DEBUG_CELL, True)
    assert result["correct"] is True
    for name in ("engine_step_ms", "steps_counted", "host_gap_ms_per_step"):
        assert result["metrics"][name]["value"] > 0
    # the CPU's profile has no device plane, so no operation lies under the
    # scopes: the four readers find nothing and the line leaves them out
    assert not set(NEW_METRICS) & set(result["metrics"])
    # the model's counters reached the profile's engine-step spans
    steps = spans.in_dir(os.path.join(root, ".bench_trace")).step_args()
    assert steps and all(s["window_qk_pairs"] >= s["fed_tokens"]
                         and s["window_kv_tokens"] <= s["kv_tokens"]
                         for s in steps)
