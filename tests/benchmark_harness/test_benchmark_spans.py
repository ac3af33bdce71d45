"""The readers of the program's own names in a profile (PR 25): the profile
decoded with its metadata stats, the slice's host spans and device scopes,
idle time by what the host was doing, the RPA kernel's cost, and every new
per-layer reader on a hand-made run, on an empty one and on cuts of the
first real traces."""
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import bench_testlib
from benchmark import harness, kernel_costs, spans, trace_reduce, xplane

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
BENCH = os.path.join(bench_testlib.REPO, "benchmark")
SERVE = "internlm2-1.8b.serve-chat-closed"
TRAIN = "deepseek-coder-1.3b.train-2k"
MOSAIC = ', custom_call_target="tpu_custom_call"'


def load(rel):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


def ev(name, start, end, **stats):
    return spans.Event(name, float(start), float(end), stats)


def op(name, start, end, scope="", result="f32[8]{0}", opcode="fusion",
       tail=""):
    """A device event named like a whole HLO instruction."""
    return ev(f"%{name} = {result} {opcode}(f32[8]{{0}} %p){tail}", start,
              end, **({"tf_op": scope} if scope else {}))


def planes(ops=(), host=(), modules=(), lo=0, hi=1000):
    return {"/device:TPU:0": {"XLA Ops": list(ops),
                              "XLA Modules": list(modules)},
            "/host:CPU": {"python3": [ev("benchmark_slice", lo, hi),
                                      *host]}}


def fixture_planes(name):
    with open(os.path.join(FIXTURES, name)) as f:
        cut = json.load(f)["planes"]
    return {p: {line: [spans.Event(n, s, s + d, st) for n, s, d, st in evs]
                for line, evs in lines.items()} for p, lines in cut.items()}


# -- the decoder --------------------------------------------------------------

@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A real profile of a jitted call on the CPU, with one annotation that
    has arguments."""
    tmp = str(tmp_path_factory.mktemp("profile"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation(trace_reduce.SLICE_NAME):
        with jax.profiler.TraceAnnotation("serve/unit", step=3, rows=48):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tmp, spans.newest_trace(tmp, 0.0)


def test_the_decoder_reads_what_profile_data_reads(cpu_profile):
    _, path = cpu_profile
    mine = xplane.read(path)
    data = jax.profiler.ProfileData.from_file(path)
    n = 0
    for plane in data.planes:
        for line in plane.lines:
            ref = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            got = mine[plane.name][line.name]
            assert len(got) == len(ref)
            for (name, start, dur), (n2, s2, d2, _) in zip(ref, got):
                assert name == n2
                assert s2 == pytest.approx(start, abs=1)
                assert d2 == pytest.approx(dur, abs=1)
                n += 1
    assert n > 10


def test_the_decoder_gives_an_annotations_arguments_and_metadata_stats(
        cpu_profile):
    _, path = cpu_profile
    events = [e for lines in xplane.read(path).values()
              for evs in lines.values() for e in evs]
    (unit,) = [e for e in events if e[0] == "serve/unit"]
    assert {k: int(v) for k, v in unit[3].items()} == {"step": 3, "rows": 48}
    # stats of the metadata that ProfileData leaves out or shows: the
    # module of a CPU thunk
    assert any(st.get("hlo_module", "").startswith("jit_")
               for _, _, _, st in events)


def test_newest_trace_takes_the_newest_written_since_the_process_began(
        tmp_path):
    old = tmp_path / "a" / "old.xplane.pb"
    new = tmp_path / "b" / "c" / "new.xplane.pb"
    for p in (old, new):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
    now = time.time()
    os.utime(old, (now - 100, now - 100))
    os.utime(new, (now - 10, now - 10))
    assert spans.newest_trace(str(tmp_path), 0.0) == str(new)
    assert spans.newest_trace(str(tmp_path), now - 50) == str(new)
    with pytest.raises(trace_reduce.TraceError, match="since this process"):
        spans.newest_trace(str(tmp_path), now)


def test_in_dir_reads_a_real_profile_with_no_tpu_plane(cpu_profile):
    sl = spans.in_dir(cpu_profile[0])
    assert [e.name for e in sl.spans] == ["serve/unit"]
    assert sl.ops == [] and sl.idle_ns() is None and sl.lead_ns == 0.0
    assert sl.median_ms("serve/unit") > 0
    assert sl.median_ms("serve/none") is None


def test_a_trace_without_the_slices_annotation_is_an_error():
    with pytest.raises(trace_reduce.TraceError, match="0 host annotations"):
        spans.Slice.of({"/host:CPU": {"python3": [ev("serve/step", 0, 1)]}})


# -- intervals ---------------------------------------------------------------

def test_complement_and_overlap_of_intervals():
    assert spans.merged([(5, 8), (1, 3), (2, 4)]) == [(1, 4), (5, 8)]
    assert spans.complement([(1, 3), (2, 5), (8, 9)], 0, 10) == \
        [(0, 1), (5, 8), (9, 10)]
    assert spans.complement([(-5, 2), (9, 20)], 0, 10) == [(2, 9)]
    assert spans.complement([], 0, 10) == [(0, 10)]
    assert spans.overlap_ns([(0, 1), (5, 8), (9, 10)],
                            [(0.5, 6), (5.5, 7)]) == pytest.approx(2.5)


# -- idle time, by what the host was doing -----------------------------------

def engine_spans(start, sched, batch, step, end):
    """One engine step's spans: engine_step ``start..end`` holding
    ``schedule``, ``batch``, ``step`` (each ``(a, b)``) and a commit from
    the end of ``step`` to ``end``."""
    return [ev("serve/engine_step", start, end, fed_tokens=4),
            ev("serve/schedule", *sched), ev("serve/batch", *batch),
            ev("serve/step", *step), ev("serve/commit", step[1], end)]


IDLE_CASES = {
    # device busy 100..400 of 0..1000: idle 0..100 and 400..1000
    "under one forward span": (
        [ev("serve/engine_step", 0, 1000), ev("serve/step", 0, 1000)],
        0, {"forward": 700, "engine_host": 0, "caller": 0}),
    "under the engine's other spans": (
        engine_spans(0, (0, 40), (40, 90), (90, 450), 500),
        0, {"forward": 10 + 50, "engine_host": 90 + 50, "caller": 500}),
    "across two engine steps and the caller between them": (
        engine_spans(0, (0, 40), (40, 90), (90, 450), 500)
        + engine_spans(600, (600, 640), (640, 700), (700, 1000), 1000),
        0, {"forward": 60 + 300, "engine_host": 140 + 100, "caller": 100}),
    "under none": (       # the one span lies where the device is busy
        [ev("serve/step", 150, 300)], 0,
        {"forward": 0, "engine_host": 0, "caller": 700}),
    # the device's clock leads the host's by 50: the spans move back by it
    "with the device's clock ahead": (
        engine_spans(50, (50, 90), (90, 140), (140, 500), 550),
        50, {"forward": 10 + 50, "engine_host": 90 + 50, "caller": 500}),
}


@pytest.mark.parametrize("case", sorted(IDLE_CASES))
def test_idle_time_goes_to_the_span_open_on_the_host(case):
    host, lead, want = IDLE_CASES[case]
    extra, modules = [], []
    if lead:
        modules = [ev("jit_serve_step_tc16(1)", 100, 400, run_id=7)]
        extra = [ev("DoEnqueueProgram", 100 + lead, 110 + lead, run_id=7),
                 ev("CompleteCallbacks", 400 + lead + 30, 470, run_id=7)]
    sl = spans.Slice.of(planes([op("a", 100, 400)], host + extra, modules))
    assert sl.lead_ns == lead
    idle = sl.idle_ns()
    assert idle["total"] == 700
    assert {k: idle[k] for k in want} == want
    assert idle["forward"] + idle["engine_host"] + idle["caller"] == 700


def test_the_clock_offset_is_bounded_by_enqueue_and_completion():
    modules = [ev("jit_s(1)", 100, 200, run_id=1),
               ev("jit_s(1)", 300, 400, run_id=2)]
    host = [ev("serve/step", 0, 1000),
            ev("DoEnqueueProgram", 140, 150, run_id=1),
            ev("DoEnqueueProgram", 345, 350, run_id=2),
            ev("CompleteCallbacks", 290, 295, run_id=1),
            ev("CompleteCallbacks", 470, 480, run_id=2),
            ev("DoEnqueueProgram", 900, 910, run_id=99)]   # not in slice
    sl = spans.Slice.of(planes([op("a", 100, 200), op("b", 300, 400)],
                               host, modules))
    assert (sl.lead_ns, sl.lead_max_ns) == (45, 70)
    assert sl.programs_inside_forward() == (2, 2)
    # without the shift a program would start before its dispatch
    early = spans.Slice.of(planes(
        [op("a", 100, 200)],
        [ev("serve/step", 120, 1000),
         ev("DoEnqueueProgram", 130, 135, run_id=1)], modules[:1]))
    assert early.lead_ns == 30 and early.programs_inside_forward() == (1, 1)


# -- device time, by the program's names -------------------------------------

RPA = "jit(serve_step_tc16)/layers/while/body/closed_call/attn/pallas/" \
    "_rpa_kernel/pallas_call:"
REMAT_QKV = "jit(train_step)/fwd_bwd/transpose(jvp(layers))/while/body/" \
    "closed_call/checkpoint/rematted_computation/attn/pallas/" \
    "_qkv_fused_kernel/pallas_call:"


def test_scopes_kernels_and_layers_of_device_events():
    rpa = op("_rpa_kernel.5", 0, 1, RPA, opcode="custom-call", tail=MOSAIC)
    assert spans.scope_of(rpa) == RPA and spans.kernel_of(rpa) == "_rpa_kernel"
    assert spans.is_mosaic(rpa)
    assert spans.layer_of(rpa) == "layers/attn/_rpa_kernel"
    remat = op("_qkv_fused_kernel.14", 0, 1, REMAT_QKV)
    assert spans.layer_of(remat) == \
        "bwd/remat/layers/attn/_qkv_fused_kernel"
    plain = op("fusion.1", 0, 1, "jit(serve_step_tc16)/lm_head/dot_general:")
    assert spans.kernel_of(plain) is None and not spans.is_mosaic(plain)
    assert spans.layer_of(plain) == "lm_head"
    assert spans.layer_of(op("copy.120", 0, 1)) == "(no scope)"
    assert spans.layer_of(op("x", 0, 1, "jit(f)/mul:")) == "(other)"


def test_self_time_by_kernel_with_nested_events():
    # a while 0..100 holds a kernel 10..50 (itself holding a child 20..30),
    # a fusion 50..80; an operation after it 120..130
    ops = [op("while.2", 0, 100, "jit(s)/layers/while:", opcode="while"),
           op("_rpa_kernel.5", 10, 50, RPA, opcode="custom-call",
              tail=MOSAIC),
           op("inner", 20, 30, RPA),
           op("fusion.9", 50, 80, "jit(s)/layers/while/body/mlp/dot:"),
           op("after", 120, 130, "jit(s)/sample/reduce:")]
    sl = spans.Slice.of(planes(ops, lo=0, hi=200))
    assert sl.self_ns_by(spans.kernel_of) == {"_rpa_kernel": 40}
    assert sl.self_ns_by(spans.layer_of) == {
        "layers/attn/_rpa_kernel": 40, "layers": 30, "layers/mlp": 30,
        "sample": 10}
    assert sl.self_ns_where(spans.is_mosaic) == 30
    assert sum(sl.self_ns) == trace_reduce.union_s(
        (e.start, e.end) for e in sl.ops) == 110


POOL = "bf16[24,8,385,128,128]{4,3,2,1,0:T(8,128)(2,1)}"
SHAPE_CASES = {
    "a copy of the stacked pool": (
        f"%copy.120 = {POOL} copy({POOL} %p)", [(24, 8, 385, 128, 128)]),
    "a fusion": (
        "%fusion.153 = bf16[8,49280,128]{2,0,1:T(8,128)(2,1)S(1)} "
        "fusion(bf16[8,49280,128]{2,0,1} %a, s32[768]{0} %b), kind=kLoop",
        [(8, 49280, 128)]),
    "a copy-start's elements": (
        "%copy-start.1 = (bf16[8,49280,128]{2,0,1}, bf16[8,49280,128]{2,1,0}"
        ", u32[]{:S(2)}) copy-start(bf16[8,49280,128]{2,1,0} %x)",
        [(8, 49280, 128), (8, 49280, 128), ()]),
    "a while's tuple is not a result to compare": (
        f"%while.2 = (s32[]{{:T(128)}}, {POOL}, {POOL}) "
        "while((s32[]) %tuple.1), condition=%c, body=%b", []),
    "a host annotation": ("benchmark_slice", []),
}


@pytest.mark.parametrize("case", sorted(SHAPE_CASES))
def test_result_shapes_of_an_instruction(case):
    text, want = SHAPE_CASES[case]
    assert spans.result_shapes(text) == want


def test_pool_shapes_and_pool_copies_of_the_serving_cell():
    shapes = kernel_costs.pool_shapes(load("configs/internlm2-1.8b.json"),
                                      load("traffic/serve-chat-closed.json"))
    assert shapes == [(24, 8, 385, 128, 128), (1, 8, 385, 128, 128),
                      (8, 385, 128, 128), (8, 49280, 128)]
    yes = ev(SHAPE_CASES["a copy of the stacked pool"][0], 0, 1)
    done = ev("%copy-done.1 = bf16[8,49280,128]{2,0,1} copy-done((bf16[8,"
              "49280,128]{2,0,1}, bf16[8,49280,128]{2,1,0}, u32[]) %cs)", 0, 1)
    no = ev("%fusion.158 = bf16[48,16,2048]{2,1,0} fusion(bf16[48,16,8192]"
            "{2,1,0} %a), kind=kOutput", 0, 1)
    kernel = ev(f"%_rpa_kernel.5 = {POOL} custom-call(s32[48] %a){MOSAIC}",
                0, 1)
    assert [spans.is_pool_copy(e, shapes) for e in (yes, done, no, kernel)] \
        == [True, True, False, False]


# -- the RPA kernel's cost ------------------------------------------------------

def test_rpa_cost_against_counts_worked_by_hand():
    cfg = {"num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "hidden_size": 512, "dtype": "bfloat16"}
    mix = {"engine": {"num_pages": 9}}
    # two rows: a decode row (q_len 1, seq_len 100) and a prefill chunk
    # (q_len 16, seq_len 48): head_dim 128
    step = {"fed_tokens": 17, "kv_tokens": 148, "qk_pairs": 100 + 16 * 48}
    cost = kernel_costs.rpa_step(cfg, mix, step)
    kv = 2 * 148 * 2 * 128 * 2          # K and V, 2 kv heads, bf16
    qo = 2 * 17 * 4 * 128 * 2           # q and o, 4 heads, bf16
    assert cost == {"bytes": 2 * (kv + qo),
                    "flops": 2 * 4 * 868 * 4 * 128}
    int8 = kernel_costs.rpa_step(cfg, {"engine": {"kv_dtype": "int8"}}, step)
    assert int8["bytes"] == 2 * (kv // 2 + qo)
    with pytest.raises(KeyError, match="float8"):
        kernel_costs.kv_itemsize(cfg, {"engine": {"kv_dtype": "float8"}})
    peaks = {"hbm_bytes_per_s": 800e9, "bf16_flops_per_s": 200e12}
    least = kernel_costs.least_time_s({"bytes": 8e9, "flops": 1e12}, peaks)
    assert least["bound"] == "memory" and least["seconds"] == 0.01
    assert kernel_costs.least_time_s({"bytes": 8e6, "flops": 1e12},
                                     peaks)["bound"] == "compute"


# -- every new reader, on a hand-made run and on an empty one ---------------

def read(metric, run):
    spec = harness.load_spec(bench_testlib.REPO)
    return harness.load_module(
        harness.find_reader(bench_testlib.REPO, spec, metric)).read(run)


def serve_slice():
    """Two engine steps of 500 ns: schedule 20, batch 60, forward 400 (the
    device busy for 300 of it: a pool copy, the RPA kernel, a matmul),
    commit 20; 0 ns of caller between them."""
    host, ops = [], []
    for i, t in enumerate((0, 500)):
        host += [ev("serve/engine_step", t, t + 500, step=i, bucket=16,
                    fed_tokens=96 + 32 * i, slot_tokens=768,
                    kv_tokens=20000, qk_pairs=40000),
                 ev("serve/schedule", t, t + 20),
                 ev("serve/batch", t + 20, t + 80 + 20 * i),
                 ev("serve/step", t + 100, t + 480),
                 ev("serve/commit", t + 480, t + 500)]
        ops += [op("copy.120", t + 150, t + 250, result=POOL, opcode="copy"),
                op("_rpa_kernel.5", t + 250, t + 400, RPA,
                   opcode="custom-call", tail=MOSAIC),
                op("fusion.1", t + 400, t + 450,
                   "jit(s)/layers/while/body/mlp/dot_general:")]
    return spans.Slice.of(planes(ops, host))


def train_slice():
    ops = [op("_qkv_fused_kernel.13", 0, 100, REMAT_QKV.replace(
               "checkpoint/rematted_computation/", "")),
           op("_qkv_fused_kernel.14", 100, 180, REMAT_QKV),
           op("_flash_bwd_dq_kernel_resident.9", 180, 300,
              "jit(train_step)/fwd_bwd/transpose(jvp(layers))/while/body/"
              "attn/pallas/_flash_bwd_dq_kernel_resident/pallas_call:"),
           op("_mlp_fused_kernel.4", 300, 500,
              "jit(train_step)/fwd_bwd/jvp(layers)/while/body/mlp/pallas/"
              "_mlp_fused_kernel/pallas_call:"),
           op("fusion.2", 500, 560,
              "jit(train_step)/fwd_bwd/transpose(jvp(layers))/while/body/"
              "checkpoint/rematted_computation/attn/mul:"),
           op("fusion.3", 560, 900, "jit(train_step)/optimizer/add:")]
    return spans.Slice.of(planes(ops, [ev("train/step", 0, 5, step=0)]))


def run_of(cell):
    serve = cell == SERVE
    return {"trace": {"window_s": 1e-6}, "counters": {"trace_steps": 2},
            "config": load("configs/internlm2-1.8b.json" if serve
                           else "configs/deepseek-coder-1.3b.json"),
            "traffic": load("traffic/serve-chat-closed.json" if serve
                            else "traffic/train-2k.json"),
            "device_kind": "TPU v5 lite"}


def rpa_roofline_by_hand():
    cfg = load("configs/internlm2-1.8b.json")
    kv = 2 * 20000 * 8 * 128 * 2
    qo = 2 * (96 + 128) * 16 * 128 * 2
    seconds = 24 * (2 * kv + qo) / 819e9      # memory-bound, two steps
    assert cfg["num_hidden_layers"] == 24
    return 100.0 * seconds / 300e-9


WANT = {    # metric -> (cell, value on the hand-made slice)
    "engine_sched_ms": (SERVE, 20e-6),
    "engine_batch_ms": (SERVE, 70e-6),
    "engine_commit_ms": (SERVE, 20e-6),
    "idle_ms_per_step.forward": (SERVE, (50 + 30) * 1e-6),
    "idle_ms_per_step.engine_host": (SERVE, (20 + 60 + 20 + 20) * 1e-6),
    "idle_ms_per_step.caller": (SERVE, 0.0),
    "step_fill_pct": (SERVE, 100.0 * (96 + 128) / 1536),
    "kernel_ms_per_step.rpa": (SERVE, 150e-6),
    "rpa_roofline_pct": (SERVE, None),          # by hand, below
    "kv_pool_copy_ms_per_step": (SERVE, 100e-6),
    "kernel_ms_per_step.fused_attn": (TRAIN, (100 + 80 + 120) / 2 * 1e-6),
    "kernel_ms_per_step.fused_mlp": (TRAIN, 200 / 2 * 1e-6),
    "recompute_ms_per_step": (TRAIN, (80 + 60) / 2 * 1e-6),
}


def test_benchmark_json_lists_exactly_these_new_metrics_for_their_cells():
    spec = harness.load_spec(bench_testlib.REPO)
    layers = {m["layer"] for m in spec["per_layer"][:8]}
    for name, (cell, _) in WANT.items():
        (m,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert m["workloads"] == [cell] and m["layer"] in layers
        e2e = {e["name"] for e in spec["end_to_end"]
               if cell in e.get("workloads", [cell])}
        assert m["moves"] in e2e
    assert [m["name"] for m in spec["per_layer"][8:]] == list(WANT)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_on_a_hand_made_run(metric, monkeypatch, capsys):
    cell, want = WANT[metric]
    sl = serve_slice() if cell == SERVE else train_slice()
    monkeypatch.setattr(spans, "traced", lambda run: sl)
    got = read(metric, run_of(cell))
    if metric == "rpa_roofline_pct":
        want = rpa_roofline_by_hand()
        assert '"bound": "memory"' in capsys.readouterr().out
    assert got == pytest.approx(want, rel=1e-9, abs=1e-15)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_reader_finds_nothing_on_the_other_cells_or_an_empty_slice(
        metric, monkeypatch):
    cell, _ = WANT[metric]
    # the other cell's slice has none of this metric's names; an empty
    # slice (the parent commit on a CPU) has nothing at all
    other = train_slice() if cell == SERVE else serve_slice()
    empty = spans.Slice.of(planes())
    for sl in (other, empty):
        monkeypatch.setattr(spans, "traced", lambda run, sl=sl: sl)
        if metric == "kv_pool_copy_ms_per_step" and sl is other:
            continue        # needs no name: the shapes decide, see below
        assert read(metric, run_of(cell)) is None
    # and an untraced run is never read
    monkeypatch.undo()
    assert read(metric, dict(run_of(cell), trace=None)) is None


def test_the_three_idle_parts_add_up_to_host_gap_ms_per_step(monkeypatch):
    sl = serve_slice()
    monkeypatch.setattr(spans, "traced", lambda run: sl)
    plain = {p: {line: [(e.name, e.start, e.ns) for e in evs]
                 for line, evs in lines.items()}
             for p, lines in planes(sl.ops, sl.spans).items()}
    run = dict(run_of(SERVE), trace=trace_reduce.reduce_events(plain))
    total = sum(read(f"idle_ms_per_step.{part}", run)
                for part in ("forward", "engine_host", "caller"))
    assert total == pytest.approx(read("host_gap_ms_per_step", run))


# -- cuts of the first real traces -----------------------------------------

def plain_of(named):
    return {p: {line: [(e.name, e.start, e.ns) for e in evs]
                for line, evs in lines.items()} for p, lines in named.items()}


def test_the_serve_cut_names_its_kernel_its_pool_copies_and_its_idle_time():
    named = fixture_planes("serve_trace_named_cut.json")
    sl = spans.Slice.of(named)
    reduced = trace_reduce.reduce_events(plain_of(named))
    # one kernel, and its time is all of the Mosaic time
    by_kernel = sl.self_ns_by(spans.kernel_of)
    assert list(by_kernel) == ["_rpa_kernel"]
    assert by_kernel["_rpa_kernel"] / 1e9 == pytest.approx(
        reduced["mosaic_s"], rel=1e-9)
    assert sl.self_ns_where(
        lambda e: spans.is_mosaic(e) and not spans.kernel_of(e)) == 0
    # four layers in 25 ms: the pool copies are the largest part of them
    shapes = kernel_costs.pool_shapes(load("configs/internlm2-1.8b.json"),
                                      load("traffic/serve-chat-closed.json"))
    pool_ms = sl.self_ns_where(lambda e: spans.is_pool_copy(e, shapes)) / 1e6
    assert pool_ms == pytest.approx(11.766, abs=0.001)
    assert pool_ms > by_kernel["_rpa_kernel"] / 1e6 > 8.2
    # the device's line leads the host's: its program "starts" 1.247 ms
    # before the host enqueued it
    assert sl.lead_ns / 1e6 == pytest.approx(1.2468, abs=1e-4)
    idle = sl.idle_ns()
    assert idle["total"] / 1e9 == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    assert idle["caller"] == 0
    assert idle["engine_host"] / 1e6 == pytest.approx(0.8011, abs=1e-4)
    assert idle["forward"] / 1e6 == pytest.approx(0.5578, abs=1e-4)
    (step,) = [e for e in sl.spans if e.name == spans.ENGINE_SPAN]
    assert {k: int(v) for k, v in step.stats.items()} == {
        "step": 249, "bucket": 16, "rows": 48, "prefill_rows": 7,
        "decode_rows": 41, "fed_tokens": 143, "slot_tokens": 768,
        "kv_tokens": 29882, "qk_pairs": 73672}
    assert set(sl.self_ns_by(spans.layer_of)) >= {
        "layers/attn/_rpa_kernel", "layers/attn/kv_write", "layers/mlp",
        "embed"}


def test_the_train_cut_tells_its_fused_kernels_apart():
    named = fixture_planes("train_trace_named_cut.json")
    sl = spans.Slice.of(named)
    reduced = trace_reduce.reduce_events(plain_of(named))
    by_kernel = sl.self_ns_by(spans.kernel_of)
    assert list(by_kernel) == ["_mlp_fused_kernel", "_attn_epi_kernel",
                               "_qkv_fused_kernel"]
    assert sum(by_kernel.values()) / 1e9 == pytest.approx(
        reduced["mosaic_s"], rel=1e-9)
    assert [e.name for e in sl.spans] == ["train/step"]
    assert sl.lead_ns / 1e6 == pytest.approx(0.2614, abs=1e-4)
    assert sl.idle_ns() is None          # no engine span: nothing to split
