"""distributed.plan: the planner → compile → run path.

Covers the Titanax compile-selection rule (both shardings → pjit, one →
error, specs → shard_map), portable-spec binding onto meshes that lack an
axis (→ replicated), the plan-spec round-trip (incl. ``tools/pod_report.py
--plan-out`` → ``Plan.from_report``), the 1F1B overlap schedule model with
an injectable event log, the SPMD verification gate, dryrun-vs-Plan parity
for the four MULTICHIP variants, and the elastic 4→2 resize through
``Plan.run_train_loop``.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.distributed import overlap
from paddle_tpu.distributed.plan import (
    Plan, PlanCompilationError, PlanError, PlanVerificationError,
    _as_sharding_tree)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# construction + validation
# ---------------------------------------------------------------------------

def test_plan_validates_schedule_and_degrees():
    with pytest.raises(PlanError):
        Plan(schedule="zigzag")
    with pytest.raises(PlanError):
        Plan(schedule="1f1b")           # pipeline schedule needs pp > 1
    with pytest.raises(PlanError):
        Plan(dp=0)
    p = Plan(dp=2, pp=2, schedule="1f1b", n_microbatches=4)
    assert p.world_size == 4
    assert p.dims == {"dp": 2, "pp": 2, "sharding": 1, "sp": 1, "mp": 1}


def test_plan_needs_enough_devices():
    with pytest.raises(PlanError):
        Plan(dp=2, mp=8).topology(jax.devices())  # 16 > the 8 virtual


def test_for_world_size_keeps_model_axes_when_divisible():
    p = Plan(dp=4, pp=2, schedule="1f1b", n_microbatches=4, overlap=True)
    q = p.for_world_size(4)
    assert (q.dp, q.pp, q.schedule) == (2, 2, "1f1b")
    # indivisible by the model block (pp=2) -> collapse to pure dp
    r = p.for_world_size(3)
    assert (r.dp, r.pp, r.schedule) == (3, 1, "none")


# ---------------------------------------------------------------------------
# compile: the Titanax selection rule
# ---------------------------------------------------------------------------

def test_compile_both_shardings_selects_pjit():
    plan = Plan(dp=2)
    c = plan.compile(lambda x: x * 2.0, in_shardings=(P("dp"),),
                     out_shardings=P("dp"), verify=False)
    assert c.path == "pjit"
    np.testing.assert_allclose(np.asarray(c(np.arange(8.0))),
                               np.arange(8.0) * 2.0)


def test_compile_binds_one_sharding_per_argument():
    """A tuple of PartitionSpecs is one spec per argument, not one JSON
    spec: each argument keeps its own rank and placement."""
    plan = Plan(dp=2, mp=2)
    c = plan.compile(lambda a, b: (a * 2.0, b + 1.0),
                     in_shardings=(P("dp", None, "mp"), P("mp")),
                     out_shardings=(P("dp", None, "mp"), P("mp")),
                     verify=False)
    a, b = c(np.ones((2, 3, 4)), np.ones(4))
    assert tuple(a.sharding.spec) == ("dp", None, "mp")
    assert tuple(b.sharding.spec) == ("mp",)


def test_compile_specs_selects_shard_map():
    plan = Plan(dp=2)
    c = plan.compile(lambda x: lax.psum(x, "dp"), in_specs=(P("dp"),),
                     out_specs=P(), axis_names={"dp"}, verify=False)
    assert c.path == "shard_map"
    out = np.asarray(c(np.arange(2.0)))
    np.testing.assert_allclose(out, [1.0])   # 0 + 1 summed over dp


def test_compile_neither_selects_plain_jit():
    plan = Plan(dp=2)
    c = plan.compile(lambda x: x + 1.0, verify=False)
    assert c.path == "jit"


def test_compile_traces_under_the_plan_mesh(monkeypatch, caplog):
    """Every compile path traces under ``jax.set_mesh``, so kernel
    placement (``pallas_ops.kernel_axes``) sees the plan's mesh: a GSPMD
    program is handed every axis to wrap a kernel in, a fully manual
    shard_map calls it directly, and a partially manual one gets no
    kernel — said once, by kernel name."""
    from paddle_tpu.ops import pallas_ops
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    monkeypatch.setattr(pallas_ops, "_ANNOUNCED", set())
    q_shape = (2, 256, 2, 128)
    seen = []

    def probe(x):
        seen.append((pallas_ops.kernel_axes(),
                     pallas_ops.flash_attention_available(q_shape)))
        return x

    plan = Plan(dp=2, mp=2)
    x = np.arange(8.0)
    axes = plan.topology().mesh.axis_names
    plan.compile(probe, in_shardings=(P("dp"),), out_shardings=P("dp"),
                 verify=False)(x)
    plan.compile(probe, in_specs=(P("dp"),), out_specs=P("dp"),
                 verify=False)(x)
    with caplog.at_level("WARNING", logger=pallas_ops.__name__):
        for _ in range(2):
            plan.compile(probe, in_specs=(P("dp"),), out_specs=P("dp"),
                         axis_names={"dp"}, verify=False)(x)
    assert seen == [(axes, True), ((), True), (None, False), (None, False)]
    dropped = [r.getMessage() for r in caplog.records]
    assert len(dropped) == 1 and dropped[0].startswith("flash_attention:")


def test_compile_half_specified_sharding_raises():
    plan = Plan(dp=2)
    with pytest.raises(PlanCompilationError):
        plan.compile(lambda x: x, in_shardings=(P("dp"),), verify=False)
    with pytest.raises(PlanCompilationError):
        plan.compile(lambda x: x, out_shardings=P("dp"), verify=False)
    # and shardings + specs together is also rejected
    with pytest.raises(PlanCompilationError):
        plan.compile(lambda x: x, in_shardings=(P("dp"),),
                     out_shardings=P("dp"), in_specs=(P("dp"),),
                     out_specs=P("dp"), verify=False)


def test_spec_binding_to_missing_axis_replicates():
    """JSON specs naming an axis the mesh lacks bind replicated — the
    portable form survives topology changes."""
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("dp",))
    sh = _as_sharding_tree([["mp"], None], mesh)
    assert sh.is_fully_replicated
    kept = _as_sharding_tree([["dp"], None], mesh)
    assert tuple(kept.spec) == ("dp", None)


# ---------------------------------------------------------------------------
# SPMD verification gate
# ---------------------------------------------------------------------------

def test_verify_gate_rejects_divergent_collective():
    """A rank-dependent collective (only rank 0 psums) must be caught at
    compile time, before the step can deadlock a real pod."""
    plan = Plan(dp=2)

    def bad(x):
        return lax.cond(lax.axis_index("dp") == 0,
                        lambda v: lax.psum(v, "dp"),
                        lambda v: v * 2.0, x)

    with pytest.raises(PlanVerificationError):
        plan.compile(bad, in_specs=(P("dp", None),),
                     out_specs=P("dp", None), axis_names={"dp"},
                     verify=True,
                     example_args=(np.ones((2, 4), np.float32),))


def test_verify_gate_passes_clean_collective():
    plan = Plan(dp=2)
    c = plan.compile(lambda x: lax.psum(x, "dp"), in_specs=(P("dp"),),
                     out_specs=P(), axis_names={"dp"}, verify=True,
                     example_args=(np.arange(2.0),))
    np.testing.assert_allclose(np.asarray(c(np.arange(2.0))), [1.0])


# ---------------------------------------------------------------------------
# spec round-trip
# ---------------------------------------------------------------------------

def test_plan_spec_roundtrip(tmp_path):
    p = Plan(dp=2, pp=2, mp=2, schedule="1f1b", n_microbatches=4,
             overlap=True,
             param_specs={"embed": [["mp"], None]})
    q = Plan.from_spec(p.to_spec())
    assert q == p
    path = str(tmp_path / "plan.json")
    p.save(path)
    assert Plan.load(path) == p
    # from_report accepts the executable spec form too
    assert Plan.from_report(path) == p


def test_from_report_topology_section():
    report = {"topology": {"dp": 4, "pp": 2, "sharding": 1, "sp": 1,
                           "mp": 1, "n_microbatches": 2,
                           "zero_axis": "dp"}}
    p = Plan.from_report(report)
    assert (p.dp, p.pp, p.schedule, p.n_microbatches, p.overlap) == \
        (4, 2, "1f1b", 2, True)
    with pytest.raises(PlanError):
        Plan.from_report({"no": "topology"})


@pytest.mark.slow
def test_pod_report_plan_out_roundtrip(tmp_path):
    """``tools/pod_report.py --plan-out`` writes an executable spec that
    Plan.from_report loads back with the winning topology and the
    model's param specs."""
    out = str(tmp_path / "plan.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pod_report.py"),
         "--preset", "llama-debug", "--mesh", "v5p-8",
         "--out", str(tmp_path / "report.json"), "--plan-out", out],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    plan = Plan.from_report(out)
    assert plan.world_size == 8
    assert plan.param_specs, "plan spec should carry param specs"
    spec = json.load(open(out))
    assert Plan.from_spec(spec) == plan


# ---------------------------------------------------------------------------
# 1F1B overlap schedule model (injectable event log)
# ---------------------------------------------------------------------------

def test_overlap_schedule_ordering_and_slack():
    pp, n_micro = 4, 8
    log = []
    ret = overlap.schedule_events(pp, n_micro, overlap=True, log=log)
    assert ret is log and log, "must append into the injected log"
    # every stage handoff is issued the tick AFTER its producer and
    # consumed a full tick later: 2 ticks of producer->consumer slack
    sends = [e for e in log if e["kind"] in ("send_fwd", "send_bwd")]
    assert sends
    for e in sends:
        assert e["tick"] == e["produced_tick"] + 1
        assert e["consumed_tick"] - e["produced_tick"] == 2
    # the log is tick-ordered
    ticks = [e["tick"] for e in log]
    assert ticks == sorted(ticks)
    # constants match the emitted events (simulator == scan kernel)
    const = overlap.schedule_constants(pp, n_micro, overlap=True)
    assert max(ticks) + 1 == const["T"]


def test_overlap_strictly_fewer_serialized_transfers():
    """The acceptance oracle: overlapped 1F1B has strictly fewer
    serialized transfer→compute ticks than the lockstep schedule."""
    for pp, n_micro in [(2, 4), (4, 8)]:
        lock = overlap.transfer_stats(
            overlap.schedule_events(pp, n_micro, overlap=False))
        over = overlap.transfer_stats(
            overlap.schedule_events(pp, n_micro, overlap=True))
        assert lock["total_transfers"] == over["total_transfers"]
        assert over["serialized_transfers"] < lock["serialized_transfers"]
        assert over["serialized_transfers"] == 0
    assert overlap.overlap_fraction(
        overlap.schedule_events(4, 8, overlap=True)) == 1.0
    assert overlap.overlap_fraction(
        overlap.schedule_events(4, 8, overlap=False)) == 0.0


def test_schedule_events_validates_args():
    with pytest.raises(ValueError):
        overlap.schedule_events(0, 4, overlap=True)


# ---------------------------------------------------------------------------
# dryrun parity matrix through Plan.compile (the regression oracle)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("kw", [
    dict(dp=2, pp=2, mp=2, label="pp+mp", overlap=True),
    dict(dp=2, sharding=2, mp=2, moe=True, label="zero+ep"),
    dict(dp=2, sp=2, mp=2, label="ring-sp"),
    dict(dp=2, pp=2, sp=2, schedule="gpipe", label="pp+sp"),
], ids=["pp+mp", "zero+ep", "ring-sp", "pp+sp"])
def test_multichip_variant_parity_through_plan(kw):
    """Each MULTICHIP variant runs a training step through
    Plan.train_step(verify=True) and must match the single-device
    reference bit-for-bit (the CE-parity assert inside _run_variant)."""
    import __graft_entry__ as g
    g._run_variant(jax.devices()[:8], **kw)


# ---------------------------------------------------------------------------
# elastic resize through the Plan train loop
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_run_train_loop_resize_4_to_2(tmp_path):
    """request_scale mid-run: checkpoint → refit plan → recompile →
    restore resharded, losses stay finite across the boundary."""
    import optax
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.distributed.fleet.elastic import request_scale

    class FakeStore:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = v

        def get(self, k):
            return self.d.get(k)

    cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      dtype=jnp.float32, use_remat=False)
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(0, 128, (8, 16)),
                "labels": rng.integers(0, 128, (8, 16))}
               for _ in range(6)]
    store = FakeStore()

    def feed():
        for i, b in enumerate(batches):
            if i == 3:
                request_scale("", "job", 2, store=store)
            yield b

    hist = Plan(dp=4).run_train_loop(
        cfg, feed(), devices=jax.devices(), optimizer=optax.sgd(1e-2),
        job_id="job", scale_store=store,
        ckpt_root=str(tmp_path / "ck"), verify=False)
    assert hist["world_sizes"] == [4, 4, 4, 2, 2, 2]
    assert hist["resizes"] == [(3, 4, 2)]
    assert all(np.isfinite(x) for x in hist["losses"])


def test_run_train_loop_resize_needs_ckpt_root():
    import optax
    from paddle_tpu.models.llama import LlamaConfig

    class Store:
        def get(self, k):
            return b"2"

    cfg = LlamaConfig(vocab_size=64, hidden_size=16,
                      intermediate_size=32, num_hidden_layers=1,
                      num_attention_heads=2, num_key_value_heads=2,
                      dtype=jnp.float32, use_remat=False)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (4, 8)),
             "labels": rng.integers(0, 64, (4, 8))}
    with pytest.raises(PlanError, match="ckpt_root"):
        Plan(dp=4).run_train_loop(
            cfg, [batch], devices=jax.devices(),
            optimizer=optax.sgd(1e-2), scale_store=Store(),
            verify=False)
