"""The benchmark's side of the Mellum 2 cell, off the chip: the arithmetic of
``benchmark/kernel_costs_mellum.py`` by hand, the cell's files as
``BENCHMARK.json`` names them against the published config, its new readers on
runs that have nothing for them, and the kind ``serve-closed-model`` end to
end on this model at a debug width on the CPU (traced and untraced, contract
checked), in a benchmark root made of new files only.  The cell's presence
and its lists are pinned by membership, never by count or position, so that
the next cell added leaves these tests as they are."""
import json
import os
import time

import numpy as np
import pytest

import bench_testlib
from benchmark import (contract, harness, kernel_costs_mellum,
                       reference_mellum, spans, traffic, trace_reduce)
from paddle_tpu.models import mellum, rings

CELL = "mellum2-12b-a2.5b-instruct.serve-repo-closed"
CONFIG = "benchmark/configs/mellum2-12b-a2.5b-instruct.json"
TRAFFIC = "benchmark/traffic/serve-repo-closed.json"
DEBUG_CELL = "mellum-debug.tiny-repo"
SEED = 2**31 + 4545
NEW_METRICS = ("rpa_roofline_pct.mellum", "moe_experts_roofline_pct.mellum")
APPENDED = ("engine_step_ms", "engine_sched_ms", "engine_batch_ms",
            "engine_commit_ms", "host_gap_ms_per_step", "moe_ms_per_step",
            "attn_window_ms_per_step")
# the accepted entries that tests/benchmark_harness pins by equality
# (PERF.md section 7): the cell is in none of them
PINNED = ("layout_ms_per_step", "ssm_proj_ms_per_step",
          "kernel_ms_per_step.kv_write", "unnamed_ms_per_step",
          "host_headroom_ms_per_step", "pipelined_step_pct")
# the published config.json (Mellum2-12B-A2.5B-Instruct), every key the
# configuration file keeps
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def _file(rel):
    with open(os.path.join(bench_testlib.REPO, rel)) as f:
        return json.load(f)


def test_the_attention_cost_by_hand():
    config = _file(CONFIG)
    # a chunk of 16 at positions 9000..9015 and a decode row at 300
    seq, q = [9016, 301], [16, 1]
    counts = mellum.step_counts(mellum.config_from_fields(config), seq, q)
    assert counts == {"window_kv_tokens": (1024 + 15) + 301,
                      "window_qk_pairs": 16 * 1024 + 301,
                      "stretched_tokens": 16}
    step = dict(counts, fed_tokens=17, kv_tokens=9016 + 301,
                qk_pairs=16 * 9016 + 301)
    cost = kernel_costs_mellum.attn_step(config, step)
    # a token's K and V: 4 heads x 128 x 2 B each, 2,048 B, once a layer
    # that reads it (nine window layers, three full); a fed token and head:
    # a query of 128 in and an output of 128 out in 12 layers, bf16
    assert cost["bytes"] == 2048 * (9 * 1340 + 3 * 9317) \
        + 12 * 17 * 32 * 128 * 2 * 2
    assert cost["flops"] == 32 * 4 * 128 * (9 * (16 * 1024 + 301)
                                            + 3 * (16 * 9016 + 301))


def test_the_expert_cost_by_hand():
    config = _file(CONFIG)
    # 700 of the 768 (expert, layer) pairs hit, 24,576 rows: 256 tokens x 8
    # experts x 12 layers
    cost = kernel_costs_mellum.moe_step(
        config, {"experts_hit": 700, "held_rows": 24576})
    # an expert: three matrices of 2304 x 896 in bf16, 12,386,304 B; a
    # pair: a row of 2304 in and one out, bf16
    assert cost["bytes"] == 700 * 12_386_304 + 24576 * 2 * 2304 * 2
    assert cost["flops"] == 24576 * 6 * 2304 * 896
    assert kernel_costs_mellum.moe_step(
        config, {"experts_hit": 0, "held_rows": 0}) == {"bytes": 0,
                                                        "flops": 0}


def test_the_config_is_the_published_model_cut_in_depth_alone():
    config = _file(CONFIG)
    differ = {k for k in PUBLISHED if config.get(k) != PUBLISHED[k]}
    assert differ == {"num_hidden_layers"} \
        and config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 12
    assert config["published"]["num_hidden_layers"] == 28
    assert config["reduced_why"].startswith("num_hidden_layers: 28 -> 12")
    assert config["source"] == "https://huggingface.co/JetBrains/" \
        "Mellum2-12B-A2.5B-Instruct/blob/main/config.json"
    assert config["model"] == "mellum" and len(config["assumed"]) >= 5
    assert "12-layer stage" in config["deployment"]
    assert any("MTP" in d for d in config["departures"])
    cfg = mellum.config_from_fields(config)
    assert cfg == mellum.preset("mellum2-12b-a2.5b", num_hidden_layers=12)
    assert cfg.layer_kinds() == 3 * (["window"] * 3 + ["global"])
    assert mellum.param_count(cfg) == config["parameters"] \
        == 5_465_956_608
    assert mellum.param_count(mellum.config_from_fields(
        dict(config, **config["published"]))) \
        == config["published"]["parameters"] == 12_149_915_904
    layout = mellum.cache_bytes(cfg)
    assert layout["per_token"] == 6144 \
        and rings.ring_pages(cfg.sliding_window, 128) == 10


def test_the_cell_is_in_the_benchmark_with_its_traffic():
    spec = harness.load_spec(bench_testlib.REPO)
    cell = harness.find_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-repo-closed"
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == ["num_hidden_layers"] \
        and entry["file"] == CONFIG
    mix = _file(TRAFFIC)
    assert mix["kind"] == "serve-closed-model"
    assert mix["engine"] == {"max_running": 24, "max_model_len":
                             mix["prompt"]["max"] + mix["output"]["max"]} \
        and mix["engine"]["max_model_len"] == 21504
    assert mix["clients"] == mix["round"] == 24
    assert mix["prompt"] == {"median": 6144, "sigma": 0.7, "min": 1024,
                             "max": 20480}
    assert mix["output"] == {"median": 128, "sigma": 0.7, "min": 16,
                             "max": 1024}
    assert (mix["n_lengths"], mix["ramp_s"], mix["trace_slice_s"]) \
        == (480, 20, 2)
    check = mix["check"]
    assert check["require_pallas_kernel"] is True and check["sample"] == 24 \
        and check["sample_max_tokens"] == 12288
    assert set(contract.cell_metrics(spec, CELL, "end_to_end")) \
        == {"serve_gap_p95_ms", "setup_s"}
    layers = contract.cell_metrics(spec, CELL, "per_layer")
    assert set(layers) == set(NEW_METRICS + APPENDED)
    assert all(m["moves"] == "serve_gap_p95_ms" for m in layers.values())
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["layer"] == "kernels" \
                and m["source"] == "device_trace" and m["unit"] == "%"
        elif m["name"] in APPENDED:
            assert CELL in m["workloads"]
        elif m["name"] in PINNED:
            assert CELL not in m["workloads"]


def test_the_sample_runs_past_the_stretch_on_nearly_every_seed():
    """The check replays the longest of its samples of at most 12,288
    tokens: on the traffic's own quantiles, the share of such requests past
    8,192 tokens is about 0.22, so 24 samples all miss it with probability
    0.24%."""
    mix = _file(TRAFFIC)
    check = mix["check"]
    P = np.array(traffic.lognormal_quantiles(mix["prompt"], mix["n_lengths"]))
    O = np.array(traffic.lognormal_quantiles(mix["output"], mix["n_lengths"]))
    total = P[:, None] + O[None, :]
    pool = total <= check["sample_max_tokens"]
    past = ((total > 8192) & pool).sum() / pool.sum()
    assert 0.2 < past < 0.25
    assert (1 - past) ** check["sample"] < 0.003
    # a third of the prompts run past the stretch; nearly every request
    # wraps its ring of 10 pages
    assert 0.3 < (P > 8192).mean() < 0.4 and (total > 1280).mean() > 0.99


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_in_an_untraced_run(name):
    spec = harness.load_spec(bench_testlib.REPO)
    read = harness.load_module(harness.find_reader(
        bench_testlib.REPO, spec, name)).read
    assert read({"samples": {}, "trace": None, "kernels": [],
                 "counters": {}}) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_spans_lack_its_counters(
        name, monkeypatch):
    """The parent's program has no such model: its spans carry no window
    or device counters, and the reader gives nothing and does not raise."""
    spec = harness.load_spec(bench_testlib.REPO)
    read = harness.load_module(harness.find_reader(
        bench_testlib.REPO, spec, name)).read

    class Slice:
        def step_args(self):
            return [{"fed_tokens": 200, "kv_tokens": 9000,
                     "qk_pairs": 90000}]
    monkeypatch.setattr(spans, "traced", lambda run: Slice())
    assert read({"counters": {"trace_steps": 3}}) is None


# Two periods (six window layers, two full): a CPU test size, float32 as the
# other expert models' CPU cells (one expert chosen otherwise under
# bfloat16's rounding would make the kind's ``correct`` a coin at this
# width); the bfloat16 program is held to the reference on the chip.
M_DEBUG = dict(mellum.PRESETS["mellum-debug"], source="tests only",
               model="mellum", dtype="float32", reduced=[])

# prompts and outputs long enough that the window's rings (4 pages of 16)
# wrap in most requests and the longest run past YaRN's 64
TINY_REPO = dict(bench_testlib.TINY_CLOSED, kind="serve-closed-model",
                 prompt={"median": 40, "sigma": 0.6, "min": 8, "max": 96},
                 engine={"max_running": 4, "max_model_len": 112,
                         "page_size": 16},
                 check=dict(bench_testlib.TINY_CLOSED["check"], sample=3,
                            sample_max_tokens=112, logits_rel_tol=1e-4))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s benchmark with this model's cell added to it."""
    root = bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))
    for rel, body in (("configs/mellum-debug.json", M_DEBUG),
                      ("traffic/tiny-repo.json", TINY_REPO)):
        with open(os.path.join(root, "extra", rel), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    serve = "debug.tiny-closed"
    spec["configs"].append({
        "name": "mellum-debug", "source": "tests only",
        "file": "extra/configs/mellum-debug.json", "reduced": [],
        "why": "CPU test size"})
    spec["workloads"].append({
        "name": DEBUG_CELL, "config": "mellum-debug", "traffic": "tiny-repo",
        "chips": 1, "why": "serve-closed-model kind on window rings beside a "
                           "full pool and softmax-routed experts"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if serve in m.get("workloads", []):
            m["workloads"].append(DEBUG_CELL)
    spec["per_layer"] += [
        bench_testlib.metric(name, "%", layer="kernels",
                             moves="serve_gap_p95_ms",
                             workloads=[DEBUG_CELL]) for name in NEW_METRICS]
    with open(path, "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(autouse=True)
def _cpu_reports_no_memory(monkeypatch):
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda: 123456)
    # the reference pads its rows to 2,048 for the chip's sake
    monkeypatch.setattr(reference_mellum, "PAD_TO", 128)


def test_the_debug_config_is_the_programs_debug_preset():
    assert mellum.config_from_fields(M_DEBUG) == mellum.preset(
        "mellum-debug", dtype=np.float32)


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
    # scopes: both roofline readers find nothing and the line leaves them out
    assert not set(NEW_METRICS) & set(result["metrics"])
    # the model's counters, the device's among them, reached the profile's
    # engine-step spans
    steps = spans.in_dir(os.path.join(root, ".bench_trace")).step_args()
    assert steps and all(
        s["window_kv_tokens"] <= s["kv_tokens"]
        and s["fed_tokens"] <= s["window_qk_pairs"] <= s["qk_pairs"]
        and 0 <= s["stretched_tokens"] <= s["fed_tokens"] for s in steps)
    # the engine keeps one step in flight: what the device counted in the
    # step a call dispatched is on the span of the next call, which fetched it
    fetched = [(a, b) for a, b in zip(steps, steps[1:])
               if b["step"] == a["step"] + 1 and "experts_hit" in b]
    assert len(fetched) >= len(steps) // 2 and all(
        0 <= b["experts_hit"] <= 8 * 8 and b["experts_hit"] <= b["held_rows"]
        and b["held_rows"] <= a["fed_tokens"] * 3 * 8
        and b["expert_rows_max"] <= a["fed_tokens"] for a, b in fetched)
    assert sum(b["held_rows"] for _, b in fetched) > 0
