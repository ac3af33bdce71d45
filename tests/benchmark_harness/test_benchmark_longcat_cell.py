"""The benchmark's side of the LongCat-Flash-Chat cell, off the chip: the
arithmetic of ``benchmark/kernel_costs_longcat_flash.py`` by hand, the cell's
files as ``BENCHMARK.json`` names them against the catalog's row, its new
readers on a run that has nothing for them, and the kind
``serve-closed-model`` end to end on this model at a debug width on the CPU
(traced and untraced, contract checked), in a benchmark root made of new
files only."""
import json
import os
import time

import numpy as np
import pytest

import bench_testlib
from benchmark import (contract, harness, kernel_costs_longcat_flash, spans,
                       trace_reduce)
from paddle_tpu.models import longcat_flash

CELL = "longcat-flash-chat.serve-agent-closed"
DEBUG_CELL = "longcat-flash-debug.tiny-agent"
SEED = 2**31 + 3737
NEW_METRICS = ("moe_experts_roofline_pct.scmoe", "mla_attn_roofline_pct.scmoe",
               "dense_ffn_ms_per_step", "moe_zero_pair_pct")
# the accepted readers under a name of this cell's own: tests/benchmark_harness
# pins the accepted entries' lists of cells (PERF.md section 7)
SPLIT = ("layout_ms_per_step.scmoe", "kernel_ms_per_step.kv_write.scmoe",
         "unnamed_ms_per_step.scmoe", "host_headroom_ms_per_step.scmoe",
         "pipelined_step_pct.scmoe")
APPENDED = ("engine_step_ms", "engine_sched_ms", "engine_batch_ms",
            "engine_commit_ms", "host_gap_ms_per_step", "moe_ms_per_step",
            "mla_ms_per_step")
# what the catalog of public architectures gives for this model
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]


def _file(rel):
    with open(os.path.join(bench_testlib.REPO, rel)) as f:
        return json.load(f)


def test_the_expert_cost_by_hand():
    config = _file("benchmark/configs/longcat-flash-chat.json")
    # 61 of the 64 (expert, layer) pairs hit, 200 rows on held experts
    step = {"experts_hit": 61, "held_rows": 200, "moe_pairs": 200 * 12 * 4}
    cost = kernel_costs_longcat_flash.moe_step(config, step)
    # an expert: three matrices of 6144 x 2048 in bf16, 75,497,472 B; a held
    # pair: a row of 6144 in and one out, bf16; the other pairs cost nothing
    assert cost["bytes"] == 61 * 75_497_472 + 200 * 2 * 6144 * 2
    assert cost["flops"] == 200 * 6 * 6144 * 2048
    assert kernel_costs_longcat_flash.moe_step(
        config, {"experts_hit": 0, "held_rows": 0}) == {"bytes": 0,
                                                        "flops": 0}


def test_the_latent_attention_cost_by_hand():
    config = _file("benchmark/configs/longcat-flash-chat.json")
    # a chunk of 16 at positions 1000..1015 and a decode row at 3000
    step = {"fed_tokens": 17, "latent_kv_tokens": 1016 + 3001,
            "latent_qk_pairs": 16 * 1016 + 3001}
    assert longcat_flash.step_counts(
        longcat_flash.config_from_fields(config), [1016, 3001], [16, 1]) \
        == dict(moe_pairs=17 * 12 * 4, **{
            k: step[k] for k in ("latent_kv_tokens", "latent_qk_pairs")})
    cost = kernel_costs_longcat_flash.mla_step(config, step)
    # a cached token: 512 + 64 elements of 2 B, once a sublayer, 8 sublayers;
    # a fed token and head: a query of 576 in, a latent of 512 out
    assert cost["bytes"] == 8 * (1152 * 4017 + 17 * 64 * (576 + 512) * 2)
    assert cost["flops"] == 8 * 19257 * 64 * (2 * 576 + 2 * 512)


def test_the_cell_is_the_published_model_cut_to_one_chips_share():
    spec = harness.load_spec(bench_testlib.REPO)
    cell = harness.find_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-agent-closed"
    assert [w["name"] for w in spec["workloads"]][-1] == CELL \
        and len(spec["workloads"]) == 6 \
        and all(w["chips"] == 1 for w in spec["workloads"])
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == REDUCED
    config = _file(entry["file"])
    differ = {k for k in PUBLISHED if config[k] != PUBLISHED[k]}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    assert config["experts_held"] == list(range(16))
    assert {k: config["published"][k] for k in REDUCED} \
        == {k: PUBLISHED[k] for k in REDUCED}
    assert config["model"] == "longcat_flash" and len(config["assumed"]) >= 5
    assert "32 chips share each layer's routed experts" \
        in config["deployment"] and "10,240 B a token" in config["deployment"]
    assert any("1/32 of the rows" in d for d in config["departures"])
    cfg = longcat_flash.config_from_fields(config)
    assert cfg == longcat_flash.preset(
        "longcat-flash-chat", num_layers=4, n_routed_experts=16,
        experts_held=tuple(range(16)), n_routed_experts_published=512,
        vocab_size=16384, vocab_size_published=131072)
    assert longcat_flash.param_count(cfg) == config["parameters"] \
        == 5_172_749_312
    assert longcat_flash.param_count(longcat_flash.preset(
        "longcat-flash-chat")) == config["published"]["parameters"] \
        == 560_664_980_480
    assert longcat_flash.cache_bytes(cfg)["per_token"] == 10_240
    traffic = _file("benchmark/traffic/serve-agent-closed.json")
    assert traffic["engine"] == {"max_running": 64, "max_model_len":
                                 traffic["prompt"]["max"]
                                 + traffic["output"]["max"]}
    assert traffic["clients"] == traffic["round"] == 64
    assert traffic["prompt"] == {"median": 1024, "sigma": 0.6, "min": 256,
                                 "max": 3584}
    assert traffic["output"] == {"median": 384, "sigma": 0.6, "min": 64,
                                 "max": 1536}
    assert (traffic["n_lengths"], traffic["ramp_s"],
            traffic["trace_slice_s"]) == (512, 10, 2)
    check = traffic["check"]
    assert check["require_pallas_kernel"] is True and check["sample"] == 4 \
        and check["sample_max_tokens"] == 2048
    reported = set(contract.cell_metrics(spec, CELL, "end_to_end"))
    assert reported == {"serve_gap_p95_ms", "setup_s"}
    layers = contract.cell_metrics(spec, CELL, "per_layer")
    assert set(layers) == set(NEW_METRICS + SPLIT + APPENDED)
    assert all(m["moves"] == "serve_gap_p95_ms" for m in layers.values())
    for name in NEW_METRICS + SPLIT:
        (m,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]
    for name in APPENDED:
        (m,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert m["workloads"][-1] == CELL
    # the new entries are the last nine, in this order
    assert [m["name"] for m in spec["per_layer"]][-9:] \
        == list(NEW_METRICS + SPLIT)


@pytest.mark.parametrize("name", NEW_METRICS + SPLIT)
def test_a_reader_finds_nothing_in_an_untraced_run(name):
    spec = harness.load_spec(bench_testlib.REPO)
    read = harness.load_module(harness.find_reader(
        bench_testlib.REPO, spec, name)).read
    assert read({"samples": {}, "trace": None, "kernels": [],
                 "counters": {}}) is None


def test_the_zero_pair_share_reads_the_spans_counters(monkeypatch):
    spec = harness.load_spec(bench_testlib.REPO)
    read = harness.load_module(harness.find_reader(
        bench_testlib.REPO, spec, "moe_zero_pair_pct")).read

    class Slice:
        def step_args(self):
            # the first span fetched nothing yet; a program without the
            # counter (the parent) has neither key
            return [{"moe_pairs": 9600}, {"moe_pairs": 9600,
                                          "zero_pairs": 3100},
                    {"moe_pairs": 2400, "zero_pairs": 900}, {"steps": 1}]
    monkeypatch.setattr(spans, "traced", lambda run: Slice())
    assert read({}) == pytest.approx(100 * 4000 / 12000)
    monkeypatch.setattr(spans, "traced", lambda run: None)
    assert read({}) is None


# Two layers (four attention sublayers): a CPU test size.  float32: at this
# width ONE expert chosen otherwise under bfloat16's rounding moves a request's
# logits by 0.1-0.17 (1 run in 13 over seeds, and the requests a run samples
# follow the machine's load), which would make the kind's ``correct`` a coin.
# The bfloat16 program is held to the reference on the chip, at the cell's
# width, where one such choice is a near-tie of small weight.
LC_DEBUG = {
    "source": "tests only", "model": "longcat_flash", "vocab_size": 256,
    "hidden_size": 128, "ffn_hidden_size": 256,
    "expert_ffn_hidden_size": 128, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 128, "q_lora_rank": 64,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4,
    "experts_held": [1, 2, 5, 6], "published": {"n_routed_experts": 8},
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "rms_norm_eps": 1e-5, "rope_theta": 1e7,
    "max_position_embeddings": 2048, "dtype": "float32",
    "reduced": ["n_routed_experts"]}

TINY_AGENT = dict(bench_testlib.TINY_CLOSED, kind="serve-closed-model")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s benchmark with this model's cell added to it."""
    root = bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))
    for rel, body in (("configs/longcat-flash-debug.json", LC_DEBUG),
                      ("traffic/tiny-agent.json", TINY_AGENT)):
        with open(os.path.join(root, "extra", rel), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    serve = "debug.tiny-closed"
    spec["configs"].append({
        "name": "longcat-flash-debug", "source": "tests only",
        "file": "extra/configs/longcat-flash-debug.json",
        "reduced": ["n_routed_experts"], "why": "CPU test size"})
    spec["workloads"].append({
        "name": DEBUG_CELL, "config": "longcat-flash-debug",
        "traffic": "tiny-agent", "chips": 1,
        "why": "serve-closed-model kind on a share of a shortcut-connected "
               "expert layer"})
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


def test_the_debug_config_is_a_share_of_the_programs_debug_preset():
    assert longcat_flash.config_from_fields(LC_DEBUG) \
        == longcat_flash.preset(
            "longcat-flash-debug", n_routed_experts=4,
            experts_held=(1, 2, 5, 6), n_routed_experts_published=8,
            dtype=np.float32)


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
    # scopes: the three device readers find nothing and the line leaves them
    # out; the zero-pair share is the program's counters and is there
    assert set(NEW_METRICS) & set(result["metrics"]) == {"moe_zero_pair_pct"}
    assert 10 < result["metrics"]["moe_zero_pair_pct"]["value"] < 60
    # the model's counters, the device's among them, reached the profile's
    # engine-step spans
    steps = spans.in_dir(os.path.join(root, ".bench_trace")).step_args()
    assert steps and all(
        s["moe_pairs"] == s["fed_tokens"] * 3 * 2
        and s["latent_kv_tokens"] == s["kv_tokens"]
        and s["latent_qk_pairs"] == s["qk_pairs"] for s in steps)
    # the engine keeps one step in flight: what the device counted in the
    # step a call dispatched is on the span of the next call, which fetched it
    fetched = [(a, b) for a, b in zip(steps, steps[1:])
               if b["step"] == a["step"] + 1 and "experts_hit" in b]
    assert len(fetched) >= len(steps) // 2 and all(
        0 <= b["experts_hit"] <= 8 and b["experts_hit"] <= b["held_rows"]
        and b["held_rows"] + b["zero_pairs"] <= a["moe_pairs"]
        and b["expert_rows_max"] <= a["fed_tokens"] for a, b in fetched)
    assert sum(b["held_rows"] for _, b in fetched) > 0
