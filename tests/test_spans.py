"""The one span primitive (``profiler.trace.span``) and the names the
program writes with it: a profiler annotation always, the flight
recorder's event under ``FLAGS_tpu_trace``; the phases of
``LLMEngine.step`` and the trainer's ``train/step`` on a real
``jax.profiler`` CPU trace; ``jax.named_scope`` around every Pallas call
and at the model's layer boundaries."""
import functools
import os
import re
import statistics

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.distributed import plan as plan_mod
from paddle_tpu.models import llama
from paddle_tpu.ops import pallas_ops
from paddle_tpu.profiler import trace

from benchmark import spans

CHILDREN = ["serve/schedule", "serve/batch", "serve/step", "serve/commit"]


@pytest.fixture
def trace_on():
    paddle.set_flags({"FLAGS_tpu_trace": True})
    trace.clear()
    yield
    paddle.set_flags({"FLAGS_tpu_trace": False})
    trace.clear()


@pytest.fixture
def profiled(tmp_path):
    """``profiled(fn)`` runs ``fn`` under a ``jax.profiler`` trace with the
    benchmark's slice annotation around it and returns the slice."""
    def run(fn):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("benchmark_slice"):
                fn()
        finally:
            jax.profiler.stop_trace()
        return spans.in_dir(str(tmp_path))
    return run


def tiny_engine(**kw):
    cfg = llama.preset("llama-debug")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(dict(max_running=4, chunk=4, page_size=8, max_model_len=32),
              **kw)
    return serving.LLMEngine(cfg, params, **kw)


# -- the primitive -------------------------------------------------------------

def test_off_a_span_is_the_inert_annotation_and_the_ring_stays_empty():
    trace.clear()
    assert not trace.enabled()
    s = trace.span("unit/off", step=3)
    assert type(s) is jax.profiler.TraceAnnotation
    with s:
        s.set_metadata(rows=2)      # no profiler session: nothing happens
    assert trace.events() == []


def test_on_the_ring_event_is_what_it_was_and_takes_late_fields(trace_on):
    ticks = iter([10.0, 11.0, 11.5, 13.0])
    trace.set_clock(lambda: next(ticks))
    try:
        with trace.span("unit/outer", step=7) as outer:
            with trace.span("unit/inner"):
                pass
            outer.set_metadata(rows=5)
    finally:
        import time
        trace.set_clock(time.monotonic)
    inner, outer = trace.events()
    assert (inner["name"], inner["kind"], inner["t"], inner["dur"],
            inner["depth"], inner["parent"]) == \
        ("unit/inner", "span", 11.0, 0.5, 1, "unit/outer")
    assert (outer["t"], outer["dur"], outer["depth"], outer["parent"]) == \
        (10.0, 3.0, 0, None)
    assert outer["step"] == 7 and outer["rows"] == 5


@pytest.mark.parametrize("flag", [False, True])
def test_under_a_profiler_trace_the_span_and_its_arguments_are_on_the_host_plane(
        flag, profiled):
    paddle.set_flags({"FLAGS_tpu_trace": flag})
    trace.clear()
    try:
        def work():
            with trace.span("serve/unit", step=4, bucket=16) as s:
                s.set_metadata(fed_tokens=9)
        sl = profiled(work)
        assert len(trace.events()) == (1 if flag else 0)
    finally:
        paddle.set_flags({"FLAGS_tpu_trace": False})
        trace.clear()
    (ev,) = [e for e in sl.spans if e.name == "serve/unit"]
    assert sl.lo <= ev.start <= ev.end <= sl.hi
    assert {k: int(v) for k, v in ev.stats.items()} == \
        {"step": 4, "bucket": 16, "fed_tokens": 9}


def test_the_module_still_loads_without_jax_until_a_span_is_opened():
    # tools/fleet_sim.py loads profiler/trace.py with no jax about
    src = open(trace.__file__).read()
    assert not re.search(r"^(import|from) jax", src, re.M)
    assert "from jax.profiler import TraceAnnotation" in src


# -- LLMEngine.step ---------------------------------------------------------

@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """A debug-width engine drained under a CPU profiler trace: the slice
    and, step by step, what the scheduler planned.  Its mixed step computes
    a budget of 8 tokens, not its 4 x 4 positions, so that three prompts at
    once make the scheduler defer a row.  The first step is dispatched
    before the trace starts (it compiles the prefill bucket) and is in
    flight when it does: ``plans`` are those of the steps dispatched inside
    the trace, ``eng.fed_before`` the tokens of the one before."""
    from test_token_major import with_budget
    eng = with_budget(tiny_engine(), 8)
    for i in range(3):
        eng.add_request(list(range(1, 8 + 3 * i)), 4 + i)
    eng.step()                          # compile the prefill bucket
    eng.fed_before = sum(r.fed for r in eng._requests.values())
    plans = []
    schedule = eng.scheduler.schedule

    def recording():
        plan = schedule()
        if plan.seqs:
            plans.append(plan)
        return plan
    eng.scheduler.schedule = recording
    tmp = tmp_path_factory.mktemp("engine_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("benchmark_slice"):
            while eng.has_work():
                eng.step()
            eng.step()                  # an idle step: schedule only
    finally:
        jax.profiler.stop_trace()
    eng.shutdown()
    return spans.in_dir(str(tmp)), plans, eng


def test_every_engine_step_has_one_engine_step_span_with_its_children_in_order(
        engine_trace):
    sl, plans, eng = engine_trace
    steps = sl.whole("serve/engine_step")
    fed = [s for s in steps if "fed_tokens" in s.stats]
    # after the calls that dispatched a step, one that only fetched the
    # last and an idle one
    assert len(fed) == len(plans) >= 6 and len(steps) == len(plans) + 2
    assert fed == steps[:-2]
    # every device step is on exactly one span: the tokens of the spans are
    # the tokens the requests were fed (all they know but the last sampled)
    assert eng.fed_before + sum(int(s.stats["fed_tokens"]) for s in fed) == \
        sum(r.num_known - 1 for r in eng._requests.values()) == 42
    # each was dispatched behind the step before it, still on the device
    assert {int(s.stats["in_flight"]) for s in fed} == {1}
    uncovered = []
    for step in fed:
        kids = [e for e in sl.spans if e.name in CHILDREN
                and step.start <= e.start and e.end <= step.end]
        assert [k.name for k in kids] == CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start             # disjoint, in order
        uncovered.append(step.ns - sum(k.ns for k in kids))
        assert uncovered[-1] >= 0
        (fwd,) = [k for k in kids if k.name == "serve/step"]
        inner = [e for e in sl.spans
                 if e.name in ("serve/dispatch", "serve/fetch")
                 and fwd.start <= e.start and e.end <= fwd.end]
        assert [e.name for e in inner] == ["serve/dispatch", "serve/fetch"]
        assert inner[0].end <= inner[1].start
    # the children cover the step but for the few statements between them:
    # 0.3 ms in the median step (one step may lose the CPU between two
    # spans on a loaded machine)
    assert statistics.median(uncovered) <= 3e5


def test_an_idle_engine_step_has_a_schedule_span_and_nothing_else(
        engine_trace):
    sl, _, _ = engine_trace
    landing, idle = [s for s in sl.whole("serve/engine_step")
                     if "fed_tokens" not in s.stats]

    def kids(step):
        return [e.name for e in sl.spans if e is not step
                and step.start <= e.start and e.end <= step.end]
    # the call that found nothing to dispatch and the last step to fetch
    assert kids(landing) == ["serve/schedule", "serve/step", "serve/fetch",
                             "serve/commit"]
    assert kids(idle) == ["serve/schedule"]


def test_the_engine_step_spans_arguments_are_what_the_scheduler_planned(
        engine_trace):
    sl, plans, eng = engine_trace
    args = sl.step_args()
    assert len(args) == len(plans)
    for got, plan in zip(args, plans):
        q = [s.q_len for s in plan.seqs]
        kv = [s.seq_len for s in plan.seqs]
        assert got["bucket"] == plan.bucket and got["rows"] == len(q)
        assert got["fed_tokens"] == sum(q)
        # the positions the step's program computes: a row each in a decode
        # step, the scheduler's token budget in a mixed one
        assert got["slot_tokens"] == (eng.max_running if plan.bucket == 1
                                      else eng.scheduler.step_tokens) == \
            (4 if plan.bucket == 1 else 8) >= got["fed_tokens"]
        assert got["deferred_rows"] == len(plan.deferred)
        assert got["rows"] + got["deferred_rows"] <= eng.max_running
        assert got["kv_tokens"] == sum(kv)
        assert got["qk_pairs"] == sum(a * b for a, b in zip(q, kv))
        assert got["decode_rows"] == sum(n == 1 for n in q)
        assert got["prefill_rows"] + got["decode_rows"] == got["rows"]
        # the pages the attention kernel walks, of the table's entries
        assert got["kv_pages"] == sum(-(-n // eng.page_size) for n in kv)
        assert got["table_pages"] == eng.max_running * eng.max_blocks
        assert 0 < got["kv_pages"] < got["table_pages"]
    assert [a["step"] for a in args] == \
        list(range(args[0]["step"], args[0]["step"] + len(args)))
    assert sum(a["deferred_rows"] for a in args) > 0
    assert any(a["deferred_rows"] == 0 and a["bucket"] > 1 for a in args)


def test_serving_stats_sum_the_walked_pages_and_the_tables_entries():
    serving.reset_stats()
    eng = tiny_engine()
    eng.add_request(list(range(1, 12)), 3)      # 11 tokens: 2 pages of 8
    eng.add_request([5, 6, 7], 2)
    walked = steps = 0
    schedule = eng.scheduler.schedule

    def recording():
        nonlocal walked, steps
        plan = schedule()
        if plan.seqs:
            steps += 1
            walked += sum(-(-s.seq_len // eng.page_size) for s in plan.seqs)
        return plan
    eng.scheduler.schedule = recording
    while eng.has_work():
        eng.step()
    eng.shutdown()
    stats = serving.serving_stats()
    assert stats["kv_pages"] == walked > steps
    assert stats["table_pages"] == steps * eng.max_running * eng.max_blocks


def test_serve_step_keeps_its_fields_and_the_ring_gets_every_phase(trace_on):
    eng = tiny_engine()
    eng.add_request([1, 2, 3, 4, 5], 3)
    while eng.has_work():
        eng.step()
    eng.shutdown()
    evs = [e for e in trace.events() if e["kind"] == "span"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    n = len(by_name["serve/engine_step"])
    assert n >= 5
    # n - 1 device steps: the first call only dispatches, the last only
    # fetches
    assert len(by_name["serve/schedule"]) == len(by_name["serve/step"]) == n
    for name in ("serve/batch", "serve/dispatch", "serve/fetch",
                 "serve/commit"):
        assert len(by_name[name]) == n - 1
    assert [e.get("in_flight") for e in by_name["serve/engine_step"]] == \
        [0] + [1] * (n - 2) + [None]
    # what tools/fleet_sim.py calibrates from: the step the span fetched
    # and its cost to the service, once each
    only_dispatched, *landed = by_name["serve/step"]
    assert only_dispatched["landed"] == 0 and not \
        {"bucket", "batch", "wall_s"} & set(only_dispatched)
    assert all({"dur", "bucket", "batch", "step", "wall_s"} <= set(e)
               and "landed" not in e for e in landed)
    assert [e["step"] for e in landed] == list(range(n - 1))
    assert sorted(e["wall_s"] for e in landed) == sorted(
        t for ts in eng._step_wall_s.values() for t in ts)
    assert {e["parent"] for e in by_name["serve/step"]} == \
        {"serve/engine_step"}
    assert {e["parent"] for e in by_name["serve/fetch"]} == {"serve/step"}
    assert all(e["fed_tokens"] <= e["slot_tokens"]
               for e in by_name["serve/engine_step"] if "fed_tokens" in e)


def test_the_engines_programs_are_named_for_their_bucket():
    eng = tiny_engine()
    try:
        for Tc in (1, eng.chunk):
            assert f"jit_serve_step_tc{Tc}" in eng._lower(Tc).as_text()
    finally:
        eng.shutdown()


# -- the trainer -------------------------------------------------------------

def test_train_step_is_spanned_with_the_flag_off_and_the_ring_stays_empty(
        profiled):
    trace.clear()
    calls = []

    def step_fn(params, opt_state, batch):
        calls.append(batch)
        return batch
    traced = plan_mod._wrap_step_tracing(plan_mod.Plan(), step_fn)
    sl = profiled(lambda: [traced(0, 0, i) for i in range(3)])
    assert calls == [0, 1, 2] and trace.events() == []
    found = [e for e in sl.spans if e.name == "train/step"]
    assert [int(e.stats["step"]) for e in found] == [0, 1, 2]


def test_the_train_step_program_and_its_halves_are_named():
    cfg = llama.preset("llama-debug")
    step_fn, init_fn = plan_mod.Plan().train_step(cfg, jax.devices()[:1])
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    text = step_fn.lower(params, opt_state, batch).as_text(debug_info=True)
    assert "jit_train_step" in text
    for scope in ("fwd_bwd", "optimizer", "embed", "layers", "attn", "mlp",
                  "lm_head"):
        assert re.search(rf'"[^"]*\b{scope}\b[^"]*"', text), scope


# -- names on the device -------------------------------------------------------

def scopes_of(fn, *args):
    """``(primitive, scope path)`` of every equation of ``fn``'s jaxpr,
    sub-jaxprs included; an equation inside a scan's or a call's body
    lies under the scopes of the equation that holds the body."""
    found = []

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            own = str(eqn.source_info.name_stack)
            path = "/".join(filter(None, (outer, own)))
            found.append((eqn.primitive.name, path))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)
    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return found


@pytest.fixture
def interpret():
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = False


def test_every_pallas_call_of_the_module_goes_through_the_one_helper():
    src = open(pallas_ops.__file__).read()
    assert len(re.findall(r"\bpl\.pallas_call\(", src)) == 1
    assert len(re.findall(r"\b_pallas_call\(", src)) >= 14   # 13 sites + def
    # interpret follows _INTERPRET; a kernel with its own copies takes
    # the TPU interpreter through the same door
    assert "interpret = _INTERPRET" in src
    assert "pl.pallas_call(kernel, interpret=interpret, **kwargs)" in src


KERNEL_CALLS = {
    "_flash_fwd_kernel_resident": lambda: functools.partial(
        pallas_ops._flash_fwd_resident, bq=128, bk=128),
    "_flash_fwd_kernel_streamed": lambda: functools.partial(
        pallas_ops._flash_fwd_streamed, bq=128, bk=128),
}


@pytest.mark.parametrize("kernel", sorted(KERNEL_CALLS))
def test_a_pallas_call_is_made_under_the_scope_of_its_kernels_name(
        kernel, interpret):
    x = jnp.zeros((1, 128, 128), jnp.float32)
    calls = [stack for prim, stack in scopes_of(KERNEL_CALLS[kernel](),
                                                x, x, x)
             if prim == "pallas_call"]
    assert calls and all(f"pallas/{kernel}" in s for s in calls)


def test_the_rpa_kernel_is_under_attn_and_the_new_tokens_under_kv_write(
        interpret):
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=256, intermediate_size=256,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=256)
    params = jax.eval_shape(functools.partial(llama.init_params, cfg),
                            jax.random.PRNGKey(0))
    R, Tc, P, page = 2, 4, 3, 128
    pool = jax.ShapeDtypeStruct((1, 1, P, page, 128), cfg.dtype)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    found = scopes_of(functools.partial(llama.forward_paged, cfg), params,
                      i32(R, Tc), pool, pool, i32(R, 2), i32(R), i32(R))
    write, rpa = [s for p, s in found if p == "pallas_call"]
    assert rpa.endswith("layers/attn/pallas/_rpa_kernel")
    # the new tokens go in through the write kernel: no XLA scatter
    assert write.endswith("layers/attn/kv_write/pallas/_kv_write_kernel")
    assert not [s for p, s in found if p == "scatter"]
    stacks = {s for _, s in found}
    for scope in ("embed", "layers/mlp", "lm_head"):
        assert any(s == scope or s.endswith("/" + scope) or
                   s.startswith(scope) for s in stacks), scope


def test_the_kernel_lint_names_the_kernels_caller_not_the_helper(interpret):
    from paddle_tpu.analysis import kernel_checks
    sites = []
    x = jnp.zeros((1, 128, 128), jnp.float32)
    with kernel_checks.capture_sites(sites):
        jax.eval_shape(functools.partial(pallas_ops._flash_fwd_resident,
                                         bq=128, bk=128), x, x, x)
    (site,) = sites
    helper = pallas_ops._pallas_call.__code__
    assert os.path.samefile(site.file, pallas_ops.__file__)
    assert not helper.co_firstlineno <= site.line <= helper.co_firstlineno + 20
    first = pallas_ops._flash_fwd_resident.__code__.co_firstlineno
    assert first < site.line < first + 40
