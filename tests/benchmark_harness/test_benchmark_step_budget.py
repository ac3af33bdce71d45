"""The step-budget readers of PR 35 (``benchmark/step_budget.py`` and six
files under ``benchmark/layers/``): each on a hand-made slice, on an empty
run, on PR 25's cut (a program without the new names) and on a cut of this
PR's Jamba trace; the ``unnamed`` rule on scope paths copied from this PR's
traces of all four model families; and ``BENCHMARK.json``'s entries."""
import json

import pytest

import bench_testlib
import test_benchmark_spans as base
from benchmark import contract, harness, spans, step_budget

CHAT = "internlm2-1.8b.serve-chat-closed"
JAMBA = "ai21-jamba2-3b.serve-reason-closed"
PHI = "phi-4-mini-flash-reasoning.serve-longreason-closed"
DSV2 = "deepseek-v2-lite.serve-longdoc-closed"
SERVE = [CHAT, JAMBA, PHI, DSV2]
CELLS = {    # metric -> (cells, unit, better, source, layer)
    "layout_ms_per_step": (SERVE, "ms", "lower", "device_trace", "kernels"),
    "ssm_proj_ms_per_step": ([JAMBA, PHI], "ms", "lower", "device_trace",
                             "kernels"),
    "kernel_ms_per_step.kv_write": (SERVE, "ms", "lower", "device_trace",
                                    "kernels"),
    "unnamed_ms_per_step": (SERVE, "ms", "lower", "device_trace", "kernels"),
    "host_headroom_ms_per_step": (SERVE, "ms", "higher", "program_span",
                                  "device"),
    "pipelined_step_pct": (SERVE, "%", "higher", "program_counter",
                           "engine"),
}

BODY = "jit(serve_step_tc16)/layers/while/body/closed_call/"
SCAN_KERNEL = BODY + "mamba/ssm_scan/jit(_ssm_scan_call)/pallas/" \
    "_ssm_scan_kernel/pallas_call:"
KV_KERNEL = "jit(serve_step_tc16)/layers/attn/kv_write/pallas/" \
    "_kv_write_kernel/pallas_call:"


def mamba_slice():
    """Two engine steps of 1000 us (the times below are us). The host: schedule 20, batch 60,
    dispatch 30, a fetch of 700 and 720 (the wait for the device), commit
    20; the first step dispatched on an empty device, the second behind the
    first. The device, a step: the layer loop 100..800 with a weight slice
    (25), a mixer (w_in 100, two layout moves 40, the convolution 30, the
    scan kernel 50, w_out 60, the residual add 5) and 390 of the loop's
    own; then an attention layer's K/V into their rows (10) and pages
    (20), a sampled token's move (5) and an operation with no path (3)."""
    def ev(name, start, end, **stats):
        return base.ev(name, 1e3 * start, 1e3 * end, **stats)

    def op(name, start, end, *args, **kw):
        return base.op(name, 1e3 * start, 1e3 * end, *args, **kw)

    host, ops = [], []
    for i, t in enumerate((0, 1000)):
        host += [ev("serve/engine_step", t, t + 990, step=i, bucket=16,
                         fed_tokens=100, slot_tokens=256, state_rows=8,
                         in_flight=i),
                 ev("serve/schedule", t, t + 20),
                 ev("serve/batch", t + 20, t + 80),
                 ev("serve/step", t + 90, t + 960),
                 ev("serve/dispatch", t + 100, t + 130),
                 ev("serve/fetch", t + 140, t + 840 + 20 * i),
                 ev("serve/commit", t + 960, t + 980)]
        ops += [
            op("while.9", t + 100, t + 800,
                    "jit(serve_step_tc16)/layers/while:", opcode="while"),
            op("constant_dynamic-slice_fusion.2", t + 100, t + 125,
                    BODY[:-len("closed_call/")] + "dynamic_slice:"),
            op("fusion.1", t + 130, t + 230,
                    BODY + "mamba/ssm_proj/dot_general:"),
            op("fusion.2", t + 230, t + 250,
                    BODY + "mamba/step_layout/jit(_take)/gather:"),
            op("fusion.3", t + 250, t + 280, BODY + "mamba/ssm_conv/mul:"),
            op("fusion.4", t + 280, t + 300,
                    BODY + "mamba/step_layout/jit(_take)/gather:"),
            op("_ssm_scan_kernel.5", t + 300, t + 350, SCAN_KERNEL,
                    opcode="custom-call", tail=base.MOSAIC),
            op("fusion.6", t + 350, t + 410,
                    BODY + "mamba/ssm_proj/dot_general:"),
            op("fusion.7", t + 410, t + 415, BODY + "mamba/add:"),
            op("fusion.8", t + 800, t + 810,
                    "jit(serve_step_tc16)/layers/attn/step_layout/"
                    "jit(_take)/gather:"),
            op("_kv_write_kernel.3", t + 810, t + 830, KV_KERNEL,
                    opcode="custom-call", tail=base.MOSAIC),
            op("fusion.10", t + 830, t + 835,
                    "jit(serve_step_tc16)/sample/step_layout/jit(_take)/"
                    "gather:"),
            op("copy.11", t + 835, t + 838)]
    return spans.Slice.of(base.planes(ops, host, lo=0, hi=2e6))


WANT = {    # metric -> its value on mamba_slice(), two steps
    "layout_ms_per_step": (40 + 10 + 5) * 1e-3,
    "ssm_proj_ms_per_step": 160e-3,
    "kernel_ms_per_step.kv_write": 20e-3,
    "unnamed_ms_per_step": (25 + 390 + 3) * 1e-3,
    "host_headroom_ms_per_step": 710e-3,
    "pipelined_step_pct": 50.0,
}


def run_of():
    return {"trace": {"window_s": 2e-3}, "counters": {"trace_steps": 2},
            "config": {}, "traffic": {}, "device_kind": "TPU v5 lite"}


def bench_line(out, what):
    """The JSON of the log line ``bench: <what> ...: {json}`` in ``out``."""
    (line,) = [l for l in out.splitlines() if l.startswith(f"bench: {what}")]
    return json.loads(line[line.index("): ") + 3:])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_step_budget_reader_on_a_hand_made_run(metric, monkeypatch):
    sl = mamba_slice()
    monkeypatch.setattr(spans, "traced", lambda run: sl)
    assert base.read(metric, run_of()) == pytest.approx(
        WANT[metric], rel=1e-9, abs=1e-15)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_step_budget_reader_finds_nothing_on_an_empty_or_untraced_run(
        metric, monkeypatch):
    empty = spans.Slice.of(base.planes())
    monkeypatch.setattr(spans, "traced", lambda run: empty)
    assert base.read(metric, run_of()) is None
    monkeypatch.undo()
    assert base.read(metric, dict(run_of(), trace=None)) is None


def test_the_log_lines_split_the_mixer_the_layout_and_the_whole_step(
        monkeypatch, capsys):
    sl = mamba_slice()
    monkeypatch.setattr(spans, "traced", lambda run: sl)
    for metric in ("ssm_proj_ms_per_step", "layout_ms_per_step",
                   "unnamed_ms_per_step"):
        base.read(metric, run_of())
    out = capsys.readouterr().out
    ms = lambda us: round(us * 1e-3, 3)                    # noqa: E731
    parts = bench_line(out, "device_by_mamba_part")
    assert set(parts) == {"ssm_proj", "ssm_scan", "step_layout", "ssm_conv",
                          "(rest of mamba)"}
    assert parts["(rest of mamba)"] == ms(5)
    assert bench_line(out, "layout_by_mixer") == {
        "mamba": ms(40), "attn": ms(10), "sample": ms(5)}
    dear = bench_line(out, "unnamed_ops")
    assert [row[0] for row in dear] == [
        "%while.9 while", "%constant_dynamic-slice_fusion.2 fusion",
        "%copy.11 fusion"]
    assert dear[1][1:] == [0.025, 2, BODY[:-len("closed_call/")]
                           + "dynamic_slice:"]
    budget = bench_line(out, "step_budget")
    assert list(budget["device"])[:2] == ["unnamed", "ssm_proj"]
    assert budget["device"]["_kv_write_kernel"] == ms(20)
    assert budget["host_medians"] == {
        "schedule": ms(20), "batch": ms(60), "dispatch": ms(30),
        "commit": ms(20), "fetch": ms(710)}
    assert budget["host_sum"] == ms(840)


def test_the_budgets_parts_add_up_to_the_busy_time():
    sl = mamba_slice()
    exact = sl.self_ns_by(lambda e: step_budget.leaf_of(spans.scope_of(e)))
    assert sum(exact.values()) == sum(sl.self_ns) == 2 * 738e3
    assert exact == {"unnamed": 836e3, "ssm_proj": 320e3,
                     "step_layout": 110e3, "_ssm_scan_kernel": 100e3,
                     "ssm_conv": 60e3, "_kv_write_kernel": 40e3,
                     "mamba": 10e3}
    assert step_budget.budget(sl, 2)["device_busy"] == 0.738


# -- the rule, on scope paths copied from this PR's traces -------------------

T16 = "jit(serve_step_tc16)/"
LOOP = T16 + "layers/while/body/closed_call/"
PATHS = {   # family -> {scope path of a device event: its leaf}
    "chat, models/llama.py": {
        T16 + "layers/while/body/dynamic_slice:": "unnamed",
        T16 + "layers/while:": "unnamed",
        T16 + "sin:": "unnamed",
        T16 + "mul:": "unnamed",
        "": "unnamed",
        LOOP + "attn/pallas/_rpa_kernel/pallas_call:": "_rpa_kernel",
        LOOP + "attn/kv_write/pallas/_kv_write_kernel/pallas_call:":
            "_kv_write_kernel",
        LOOP + "attn/kv_write/transpose;attn/step_layout/transpose:":
            "kv_write",
        LOOP + "attn/step_layout/jit(_take)/gather:": "step_layout",
        LOOP + "attn/dot_general:": "attn",
        LOOP + "mlp/dot_general:": "mlp",
        T16 + "step_layout/gather:": "step_layout",
        T16 + "embed/step_layout/jit(_take)/jit(_where)/select_n:":
            "step_layout",
        T16 + "embed/jit(_take)/gather:": "embed",
        T16 + "lm_head/dot_general:": "lm_head",
        T16 + "sample/gather:": "sample",
        T16 + "sample/step_layout/jit(_take)/gather:": "step_layout",
    },
    "Jamba, models/jamba.py": {
        T16 + "layers/while:": "unnamed",
        LOOP + "dynamic_slice:": "unnamed",
        "jit(serve_step_tc1)/layers/while/body/closed_call/dynamic_slice:":
            "unnamed",
        T16 + "layers/slice:": "unnamed",
        "tbl:": "unnamed",
        LOOP + "mamba/ssm_proj/dot_general:": "ssm_proj",
        LOOP + "mamba/step_layout/jit(_take)/gather:": "step_layout",
        LOOP + "mamba/ssm_conv/jit(_where)/select_n:": "ssm_conv",
        LOOP + "mamba/ssm_scan/neg:": "ssm_scan",
        SCAN_KERNEL: "_ssm_scan_kernel",
        LOOP + "mlp/dot_general:": "mlp",
        T16 + "layers/attn/pallas/_rpa_kernel/pallas_call:": "_rpa_kernel",
        KV_KERNEL: "_kv_write_kernel",
        T16 + "layers/attn/kv_write/transpose;" + T16
        + "layers/attn/step_layout/transpose:": "kv_write",
        T16 + "layers/attn/step_layout/jit(_take)/gather:": "step_layout",
        T16 + "lm_head/td,vd->tv/dot_general:": "lm_head",
    },
    "Phi-4-mini-flash, models/phi4flash.py": {
        LOOP + "dynamic_slice:": "unnamed",
        T16 + "layers/convert_element_type:": "unnamed",
        T16 + "jit(remainder)/select_n:": "unnamed",
        LOOP + "attn/attn_cross/pallas/_rpa_kernel/pallas_call:":
            "_rpa_kernel",
        LOOP + "attn/attn_cross/step_layout/transpose:": "step_layout",
        LOOP + "attn/attn_cross/reshape:": "attn_cross",
        LOOP + "attn/attn_window/kv_write/pallas/_kv_write_kernel/"
        "pallas_call:": "_kv_write_kernel",
        LOOP + "attn/attn_window/kv_write/transpose:": "kv_write",
        LOOP + "attn/attn_window/step_layout/jit(_take)/gather:":
            "step_layout",
        T16 + "layers/attn/attn_global/step_layout/jit(_take)/gather:":
            "step_layout",
        T16 + "layers/attn/attn_global/dot_general:": "attn_global",
        LOOP + "gmu/dot_general:": "gmu",
        LOOP + "mamba/ssm_proj/dot_general:": "ssm_proj",
        LOOP + "mamba/step_layout/jit(_take)/gather:": "step_layout",
    },
    "DeepSeek-V2-Lite, models/deepseek_v2.py": {
        LOOP + "dynamic_slice:": "unnamed",
        LOOP + "reduce_max:": "unnamed",
        "params['dense']['wq']:": "unnamed",
        T16 + "cos:": "unnamed",
        LOOP + "moe/moe_experts/pallas/_moe_experts_kernel/pallas_call:":
            "_moe_experts_kernel",
        LOOP + "moe/moe_experts/gather:": "moe_experts",
        LOOP + "moe/moe_router/top_k:": "moe_router",
        LOOP + "moe/moe_shared/dot_general:": "moe_shared",
        LOOP + "moe/gather:": "moe",
        LOOP + "attn_mla/mla_core/pallas/_rpa_kernel_latent/pallas_call:":
            "_rpa_kernel_latent",
        LOOP + "attn_mla/kv_write/pallas/_kv_write_kernel/pallas_call:":
            "_kv_write_kernel",
        LOOP + "attn_mla/kv_write/transpose;attn_mla/broadcast_in_dim:":
            "kv_write",
        LOOP + "attn_mla/step_layout/jit(_take)/gather:": "step_layout",
        LOOP + "attn_mla/thc,chv->thv/dot_general:": "attn_mla",
        T16 + "layers/mlp/dot_general:": "mlp",
    },
}
SITES = {   # the scope that encloses step_layout, by family
    LOOP + "mamba/step_layout/jit(_take)/gather:": "mamba",
    LOOP + "attn/step_layout/jit(_take)/gather:": "attn",
    LOOP + "attn/attn_cross/step_layout/transpose:": "attn_cross",
    LOOP + "attn_mla/step_layout/jit(_take)/gather:": "attn_mla",
    T16 + "sample/step_layout/jit(_take)/gather:": "sample",
    T16 + "step_layout/gather:": "(top)",
    LOOP + "attn/dot_general:": None,
}


@pytest.mark.parametrize("family", sorted(PATHS))
def test_the_unnamed_rule_and_the_leaf_on_real_scope_paths(family):
    for path, leaf in PATHS[family].items():
        assert step_budget.leaf_of(path) == leaf, path
        assert step_budget.is_unnamed(path) == (leaf == "unnamed"), path


def test_the_layouts_site_is_the_scope_around_it():
    for path, site in SITES.items():
        assert step_budget.layout_site_of(path) == site, path


def test_scopes_are_the_path_less_jaxs_own_components():
    assert step_budget.scopes_of(SCAN_KERNEL) == (
        "layers", "mamba", "ssm_scan", "pallas", "_ssm_scan_kernel")
    assert step_budget.scopes_of(
        "jit(f)/layers/cond/branch_1_fun/checkpoint/attn/mul:") == (
        "layers", "attn")
    assert step_budget.scopes_of("gather:") == ()
    # two fused paths, joined by XLA: the first one counts
    joined = ("jit(serve_step_tc16)/layers/attn/kv_write/transpose;"
              "jit(serve_step_tc16)/layers/attn/step_layout/transpose:")
    assert step_budget.leaf_of(joined) == "kv_write"
    assert step_budget.layout_site_of(joined) is None
    assert step_budget.layout_site_of(
        "jit(s)/step_layout/reduce_sum:") == "(top)"
    assert step_budget.mamba_part_of(KV_KERNEL) is None


# -- cuts of real traces -------------------------------------------------------

def on_fixture(name, monkeypatch):
    sl = spans.Slice.of(base.fixture_planes(name))
    monkeypatch.setattr(spans, "traced", lambda run: sl)
    run = dict(run_of(), counters={"trace_steps": 1})
    return {metric: base.read(metric, run) for metric in sorted(WANT)}


def test_on_pr_25s_program_only_the_unnamed_reader_finds_something(
        monkeypatch, capsys):
    got = on_fixture("serve_trace_named_cut.json", monkeypatch)
    unnamed = got.pop("unnamed_ms_per_step")
    assert set(got.values()) == {None}
    # that program's pool copies and weight slices, which no scope held
    assert unnamed == pytest.approx(10.266, abs=0.001)
    dear = bench_line(capsys.readouterr().out, "unnamed_ops")
    assert dear[0][0].startswith("%constant_dynamic-slice_fusion")


def test_on_a_cut_of_this_prs_jamba_trace_every_reader_finds_a_number(
        monkeypatch, capsys):
    got = on_fixture("serve_trace_jamba_budget_cut.json", monkeypatch)
    # 2.6 ms of one step's device time (two Mamba layers, then attention
    # layer 7) under one whole call of LLMEngine.step()
    assert got == pytest.approx({
        "layout_ms_per_step": 0.402431, "ssm_proj_ms_per_step": 0.336414,
        "kernel_ms_per_step.kv_write": 0.100646,
        "unnamed_ms_per_step": 0.086111,
        "host_headroom_ms_per_step": 17.707058,
        "pipelined_step_pct": 100.0}, abs=1e-6)
    out = capsys.readouterr().out
    parts = bench_line(out, "device_by_mamba_part")
    assert list(parts) == ["ssm_scan", "step_layout", "ssm_proj", "ssm_conv"]
    assert bench_line(out, "layout_by_mixer") == {"mamba": 0.36,
                                                  "attn": 0.042}
    budget = bench_line(out, "step_budget")
    assert budget["device_busy"] == 2.6
    assert sum(budget["device"].values()) == pytest.approx(2.6, abs=0.006)
    assert budget["host_medians"]["fetch"] == 17.707


# -- BENCHMARK.json ------------------------------------------------------------

@pytest.mark.parametrize("metric", sorted(CELLS))
def test_benchmark_json_lists_a_step_budget_metric_for_its_cells(metric):
    spec = harness.load_spec(bench_testlib.REPO)
    cells, unit, better, source, layer = CELLS[metric]
    (m,) = [m for m in spec["per_layer"] if m["name"] == metric]
    assert m == {"name": metric, "unit": unit, "better": better,
                 "source": source, "layer": layer,
                 "moves": "serve_gap_p95_ms", "workloads": cells}
    for cell in cells:
        assert metric in contract.cell_metrics(spec, cell, "per_layer")
        assert "serve_gap_p95_ms" in contract.cell_metrics(
            spec, cell, "end_to_end")
    reader = harness.find_reader(bench_testlib.REPO, spec, metric)
    assert reader.endswith(f"benchmark/layers/{metric}.py")
    assert callable(harness.load_module(reader).read)


def test_the_step_budget_metrics_follow_the_thirty_two_that_were_there():
    # entries may only ever be appended, so these places are theirs for good
    spec = harness.load_spec(bench_testlib.REPO)
    names = [m["name"] for m in spec["per_layer"]]
    assert names[32:38] == ["layout_ms_per_step", "ssm_proj_ms_per_step",
                          "kernel_ms_per_step.kv_write",
                          "unnamed_ms_per_step", "host_headroom_ms_per_step",
                          "pipelined_step_pct"]
    assert names[31] == "mla_attn_roofline_pct"
