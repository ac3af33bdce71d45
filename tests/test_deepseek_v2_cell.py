"""The benchmark's side of the DeepSeek-V2-Lite cell, off the chip: the
arithmetic of ``benchmark/kernel_costs_deepseek_v2.py`` by hand, the cell's
files as ``BENCHMARK.json`` names them against the catalog's row, its four
readers on a run that has nothing for them, and the kind
``serve-closed-model`` end to end on this model at a debug width on the CPU
(traced and untraced, contract checked), in a benchmark root made of new
files only."""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark_harness"))
import bench_testlib  # noqa: E402
from benchmark import (contract, harness, kernel_costs_deepseek_v2,  # noqa: E402
                       spans, trace_reduce)
from paddle_tpu.models import deepseek_v2  # noqa: E402

CELL = "deepseek-v2-lite.serve-longdoc-closed"
DEBUG_CELL = "deepseek-v2-debug.tiny-longdoc"
SEED = 2**31 + 3333
NEW_METRICS = ("moe_ms_per_step", "moe_experts_roofline_pct",
               "mla_ms_per_step", "mla_attn_roofline_pct")
# what the catalog of public architectures gives for this model
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}


def _file(rel):
    with open(os.path.join(bench_testlib.REPO, rel)) as f:
        return json.load(f)


def test_the_expert_cost_by_hand():
    config = _file("benchmark/configs/deepseek-v2-lite.json")
    # 240 fed tokens, 6 experts each, in 8 expert layers; 61 of 64 hit a layer
    step = {"moe_pairs": 240 * 6 * 8, "experts_hit": 61 * 8}
    cost = kernel_costs_deepseek_v2.moe_step(config, step)
    # an expert: three matrices of 2048 x 1408 in bf16, 17,301,504 B; a pair:
    # a row of 2048 in and one out, bf16
    assert cost["bytes"] == 488 * 17_301_504 + 11_520 * 2 * 2048 * 2
    assert cost["flops"] == 11_520 * 6 * 2048 * 1408
    assert kernel_costs_deepseek_v2.moe_step(
        config, {"moe_pairs": 0, "experts_hit": 0}) == {"bytes": 0,
                                                        "flops": 0}


def test_the_latent_attention_cost_by_hand():
    config = _file("benchmark/configs/deepseek-v2-lite.json")
    # a chunk of 16 at positions 1000..1015 and a decode row at 3000
    step = {"fed_tokens": 17, "latent_kv_tokens": 1016 + 3001,
            "latent_qk_pairs": 16 * 1016 + 3001}
    assert deepseek_v2.step_counts(
        deepseek_v2.config_from_fields(config), [1016, 3001], [16, 1]) \
        == dict(moe_pairs=17 * 6 * 8, **{
            k: step[k] for k in ("latent_kv_tokens", "latent_qk_pairs")})
    cost = kernel_costs_deepseek_v2.mla_step(config, step)
    # a cached token: 512 + 64 elements of 2 B, once a layer, 9 layers; a
    # fed token and head: a query of 576 in, a latent of 512 out
    assert cost["bytes"] == 9 * (1152 * 4017 + 17 * 16 * (576 + 512) * 2)
    assert cost["flops"] == 9 * 19257 * 16 * (2 * 576 + 2 * 512)


def test_the_cell_is_the_published_model_cut_in_depth_alone():
    spec = harness.load_spec(bench_testlib.REPO)
    cell = harness.find_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-longdoc-closed"
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == ["num_hidden_layers"]
    config = _file(entry["file"])
    differ = {k for k in PUBLISHED if config[k] != PUBLISHED[k]}
    assert differ == {"num_hidden_layers"} and config["num_hidden_layers"] == 9
    assert config["published"]["num_hidden_layers"] == 27
    assert config["reduced"] == ["num_hidden_layers"] \
        and config["reduced_why"].startswith("num_hidden_layers: 27 -> 9")
    assert config["model"] == "deepseek_v2" and len(config["assumed"]) >= 4
    cfg = deepseek_v2.config_from_fields(config)
    assert cfg == deepseek_v2.preset("deepseek-v2-lite", num_hidden_layers=9)
    assert deepseek_v2.param_count(cfg) == config["parameters"] \
        == 5_179_222_528
    assert deepseek_v2.param_count(deepseek_v2.preset("deepseek-v2-lite")) \
        == config["published"]["parameters"]
    traffic = _file("benchmark/traffic/serve-longdoc-closed.json")
    assert traffic["engine"] == {"max_running": 32, "max_model_len":
                                 traffic["prompt"]["max"]
                                 + traffic["output"]["max"]}
    assert traffic["clients"] == traffic["round"] == 32
    assert traffic["check"]["require_pallas_kernel"] is True
    reported = set(contract.cell_metrics(spec, CELL, "end_to_end"))
    assert reported == {"serve_gap_p95_ms", "setup_s"}
    layers = contract.cell_metrics(spec, CELL, "per_layer")
    assert set(NEW_METRICS) <= set(layers)
    assert not {"rpa_roofline_pct", "kernel_ms_per_step.rpa",
                "kv_pool_copy_ms_per_step"} & set(layers)
    assert all(m["moves"] == "serve_gap_p95_ms" for m in layers.values())
    for name in NEW_METRICS:
        (m,) = [m for m in spec["per_layer"] if m["name"] == name]
        # (a later model's cell may follow it on the two times' lists)
        assert m["workloads"][0] == CELL and m["layer"] == "kernels"


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_in_an_untraced_run(name):
    spec = harness.load_spec(bench_testlib.REPO)
    read = harness.load_module(harness.find_reader(
        bench_testlib.REPO, spec, name)).read
    assert read({"samples": {}, "trace": None, "kernels": [],
                 "counters": {}}) is None


DS_DEBUG = {    # one dense and two expert layers: a CPU test size
    "source": "tests only", "model": "deepseek_v2", "vocab_size": 256,
    "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 128,
    "q_lora_rank": None, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "first_k_dense_replace": 1,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": dict(PUBLISHED["rope_scaling"],
                         original_max_position_embeddings=16),
    "max_position_embeddings": 2048, "dtype": "bfloat16", "reduced": []}

TINY_LONGDOC = dict(bench_testlib.TINY_CLOSED, kind="serve-closed-model")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s benchmark with this model's cell added to it."""
    root = bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))
    for rel, body in (("configs/deepseek-v2-debug.json", DS_DEBUG),
                      ("traffic/tiny-longdoc.json", TINY_LONGDOC)):
        with open(os.path.join(root, "extra", rel), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    serve = "debug.tiny-closed"
    spec["configs"].append({
        "name": "deepseek-v2-debug", "source": "tests only",
        "file": "extra/configs/deepseek-v2-debug.json", "reduced": [],
        "why": "CPU test size"})
    spec["workloads"].append({
        "name": DEBUG_CELL, "config": "deepseek-v2-debug",
        "traffic": "tiny-longdoc", "chips": 1,
        "why": "serve-closed-model kind on latent pages and routed experts"})
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


def test_the_debug_config_is_the_programs_debug_preset():
    assert deepseek_v2.config_from_fields(DS_DEBUG) \
        == deepseek_v2.preset("deepseek-v2-debug")


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
    assert '"logits": true' in out and "route_flip_share" in out


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
    # the model's counters, the device's among them, reached the profile's
    # engine-step spans
    steps = spans.in_dir(os.path.join(root, ".bench_trace")).step_args()
    assert steps and all(
        s["moe_pairs"] == s["fed_tokens"] * 2 * 2
        and s["latent_kv_tokens"] == s["kv_tokens"]
        and s["latent_qk_pairs"] == s["qk_pairs"] for s in steps)
    # the engine keeps one step in flight: what the device counted in the
    # step a call dispatched is on the span of the next call, which fetched it
    fetched = [(a, b) for a, b in zip(steps, steps[1:])
               if b["step"] == a["step"] + 1 and "experts_hit" in b]
    assert len(fetched) >= len(steps) // 2 and all(
        4 <= b["experts_hit"] <= 16
        and 1 <= b["expert_rows_max"] <= a["fed_tokens"] for a, b in fetched)
