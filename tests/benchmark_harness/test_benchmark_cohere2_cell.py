"""The benchmark's side of the Command A+ cell, off the chip: the arithmetic
of ``benchmark/kernel_costs_cohere2_moe.py`` by hand, the cell's files as
``BENCHMARK.json`` names them against the catalog's row, its new readers on
a run that has nothing for them, and the kind ``serve-closed-model`` end to
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
from benchmark import (contract, harness, kernel_costs_cohere2_moe,
                       reference_cohere2_moe, spans, trace_reduce)
from paddle_tpu.models import cohere2_moe, rings

CELL = "command-a-plus-05-2026.serve-mixedlen-closed"
DEBUG_CELL = "cohere2-moe-debug.tiny-mixedlen"
SEED = 2**31 + 3939
NEW_METRICS = ("rpa_roofline_pct.cohere2", "moe_experts_roofline_pct.cohere2")
APPENDED = ("engine_step_ms", "engine_sched_ms", "engine_batch_ms",
            "engine_commit_ms", "host_gap_ms_per_step", "moe_ms_per_step",
            "attn_window_ms_per_step")
# the accepted entries that tests/benchmark_harness pins by equality
# (PERF.md section 7): the cell is in none of them
PINNED = ("layout_ms_per_step", "ssm_proj_ms_per_step",
          "kernel_ms_per_step.kv_write", "unnamed_ms_per_step",
          "host_headroom_ms_per_step", "pipelined_step_pct")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _file(rel):
    with open(os.path.join(bench_testlib.REPO, rel)) as f:
        return json.load(f)


def _published() -> dict:
    """The catalog's ``config`` of this model, or a skip where the catalog
    is not beside the repository."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        (row,) = [json.loads(line) for line in f
                  if '"command-a-plus-05-2026"' in line]
    assert row["config"]["model_type"] == "cohere2_moe"
    return row["config"]


def test_the_attention_cost_by_hand():
    config = _file("benchmark/configs/command-a-plus-05-2026.json")
    # a chunk of 16 at positions 5000..5015 and a decode row at 300
    seq, q = [5016, 301], [16, 1]
    counts = cohere2_moe.step_counts(
        cohere2_moe.config_from_fields(config), seq, q)
    assert counts == {"window_kv_tokens": (4096 + 15) + 301,
                      "window_qk_pairs": 16 * 4096 + 301}
    step = dict(counts, fed_tokens=17, kv_tokens=5016 + 301,
                qk_pairs=16 * 5016 + 301)
    cost = kernel_costs_cohere2_moe.attn_step(config, step)
    # a token's K and V: 8 heads x 128 x 2 B each, 4,096 B, once a layer
    # that reads it (three window layers, one global); a fed token and head:
    # a query of 128 in and an output of 128 out in 4 layers, bf16
    assert cost["bytes"] == 4096 * (3 * 4412 + 5317) \
        + 4 * 17 * 128 * 128 * 2 * 2
    assert cost["flops"] == 128 * 4 * 128 * (3 * (16 * 4096 + 301)
                                             + 16 * 5016 + 301)


def test_the_expert_cost_by_hand():
    config = _file("benchmark/configs/command-a-plus-05-2026.json")
    # 60 of the 64 (expert, layer) pairs hit, 900 rows on held experts
    cost = kernel_costs_cohere2_moe.moe_step(
        config, {"experts_hit": 60, "held_rows": 900})
    # an expert: three matrices of 4096 x 4096 in bf16, 100,663,296 B; a
    # held pair: a row of 4096 in and one out, bf16
    assert cost["bytes"] == 60 * 100_663_296 + 900 * 2 * 4096 * 2
    assert cost["flops"] == 900 * 6 * 4096 * 4096
    assert kernel_costs_cohere2_moe.moe_step(
        config, {"experts_hit": 0, "held_rows": 0}) == {"bytes": 0,
                                                        "flops": 0}


def test_the_config_is_the_catalogs_row_cut_to_one_chips_share():
    published = _published()
    config = _file("benchmark/configs/command-a-plus-05-2026.json")
    differ = {k for k in published if config.get(k) != published[k]}
    assert differ == set(REDUCED) and config["reduced"] == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 16, 32768)
    assert {k: config["published"][k] for k in REDUCED} \
        == {k: published[k] for k in REDUCED}
    assert config["source"] == "https://huggingface.co/CohereLabs/" \
        "command-a-plus-05-2026/blob/main/config.json"
    assert config["experts_held"] == list(range(16))
    assert config["model"] == "cohere2_moe" and len(config["assumed"]) >= 8
    assert "8 chips share each layer's routed experts" in config["deployment"]
    assert any("1/8 of the rows" in d for d in config["departures"])
    cfg = cohere2_moe.config_from_fields(config)
    assert cfg.layer_kinds() == ["window"] * 3 + ["global"]
    assert cohere2_moe.param_count(cfg) == config["parameters"] \
        == 4_733_292_544
    assert cohere2_moe.param_count(cohere2_moe.config_from_fields(
        dict(config, **config["published"], experts_held=None))) \
        == config["published"]["parameters"] == 218_254_938_112
    layout = cohere2_moe.cache_bytes(cfg)
    assert layout["per_token"] == 4096 \
        and rings.ring_pages(cfg.sliding_window, 128) == 34
    assert layout["per_slot"] == 34 * 3 * 128 * 4096


def test_the_cell_is_in_the_benchmark_with_its_traffic():
    spec = harness.load_spec(bench_testlib.REPO)
    cell = harness.find_cell(spec, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-mixedlen-closed"
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == "benchmark/configs/command-a-plus-05-2026.json"
    traffic = _file("benchmark/traffic/serve-mixedlen-closed.json")
    assert traffic["kind"] == "serve-closed-model"
    assert traffic["engine"] == {"max_running": 32, "max_model_len":
                                 traffic["prompt"]["max"]
                                 + traffic["output"]["max"]} \
        and traffic["engine"]["max_model_len"] == 17920
    assert traffic["clients"] == traffic["round"] == 32
    assert traffic["prompt"] == {"median": 4096, "sigma": 0.8, "min": 512,
                                 "max": 16384}
    assert traffic["output"] == {"median": 384, "sigma": 0.6, "min": 64,
                                 "max": 1536}
    assert (traffic["n_lengths"], traffic["ramp_s"],
            traffic["trace_slice_s"]) == (512, 20, 2)
    check = traffic["check"]
    assert check["require_pallas_kernel"] is True and check["sample"] == 12 \
        and check["sample_max_tokens"] == 8192
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


# Two periods (six window layers, two global): a CPU test size, float32 as
# the other expert models' CPU cells (one expert chosen otherwise under
# bfloat16's rounding would make the kind's ``correct`` a coin at this
# width); the bfloat16 program is held to the reference on the chip.
C2_DEBUG = {
    "source": "tests only", "model": "cohere2_moe", "vocab_size": 256,
    "hidden_size": 128, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_experts": 4, "experts_held": [1, 2, 5, 6],
    "published": {"num_experts": 8}, "num_experts_per_tok": 3,
    "num_shared_experts": 2, "sliding_window": 20, "layer_switch": 4,
    "rope_theta": 50000, "layer_norm_eps": 1e-5, "logit_scale": 1,
    "max_position_embeddings": 1024, "dtype": "float32",
    "reduced": ["num_experts"]}

# prompts and outputs long enough that the window's rings (4 pages of 16)
# wrap in most requests
TINY_MIXED = dict(bench_testlib.TINY_CLOSED, kind="serve-closed-model",
                  prompt={"median": 40, "sigma": 0.6, "min": 8, "max": 96},
                  engine={"max_running": 4, "max_model_len": 112,
                          "page_size": 16},
                  check=dict(bench_testlib.TINY_CLOSED["check"], sample=3,
                             sample_max_tokens=112, logits_rel_tol=1e-4))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``make_root``'s benchmark with this model's cell added to it."""
    root = bench_testlib.make_root(tmp_path_factory.mktemp("bench_root"))
    for rel, body in (("configs/cohere2-moe-debug.json", C2_DEBUG),
                      ("traffic/tiny-mixedlen.json", TINY_MIXED)):
        with open(os.path.join(root, "extra", rel), "w") as f:
            json.dump(body, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    serve = "debug.tiny-closed"
    spec["configs"].append({
        "name": "cohere2-moe-debug", "source": "tests only",
        "file": "extra/configs/cohere2-moe-debug.json",
        "reduced": ["num_experts"], "why": "CPU test size"})
    spec["workloads"].append({
        "name": DEBUG_CELL, "config": "cohere2-moe-debug",
        "traffic": "tiny-mixedlen", "chips": 1,
        "why": "serve-closed-model kind on window rings beside a global "
               "pool and a share of sigmoid-routed experts"})
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
    monkeypatch.setattr(reference_cohere2_moe, "PAD_TO", 128)


def test_the_debug_config_is_a_share_of_the_programs_debug_preset():
    assert cohere2_moe.config_from_fields(C2_DEBUG) == cohere2_moe.preset(
        "cohere2-moe-debug", num_experts=4, experts_held=(1, 2, 5, 6),
        num_experts_published=8, dtype=np.float32)


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
        for s in steps)
    # the engine keeps one step in flight: what the device counted in the
    # step a call dispatched is on the span of the next call, which fetched it
    fetched = [(a, b) for a, b in zip(steps, steps[1:])
               if b["step"] == a["step"] + 1 and "experts_hit" in b]
    assert len(fetched) >= len(steps) // 2 and all(
        0 <= b["experts_hit"] <= 4 * 8 and b["experts_hit"] <= b["held_rows"]
        and b["held_rows"] <= a["fed_tokens"] * 3 * 8
        and b["expert_rows_max"] <= a["fed_tokens"] for a, b in fetched)
    assert sum(b["held_rows"] for _, b in fetched) > 0
