"""``pallas_ops.selective_scan``: the Mosaic kernel of a Mamba layer's scan in
the serve step (``_ssm_scan_kernel``), under the Pallas TPU interpreter on the
CPU, against the XLA body ``_ssm_scan_jnp`` it replaces: ragged ``q_lens``
inside one group of 8 rows, idle groups, ``fresh`` rows, the other layers of
the stack, dead positions that hold NaN; and ``scan_positions``, the counter
that says how many row-positions the kernel walks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import jamba, phi4flash
from paddle_tpu.ops import pallas_ops
from test_spans import interpret, scopes_of  # noqa: F401 (a fixture)

N, M, LAYER = 16, 3, 1


def inputs(R, Tc, E, q, fresh, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = np.asarray(q, np.int32)
    return dict(
        ssm=normal(M, N, R, E), dt=jax.nn.softplus(normal(R, Tc, E) - 2.0),
        x=normal(R, Tc, E), Bm=normal(R, Tc, N), Cm=normal(R, Tc, N),
        A=-jnp.exp(0.3 * normal(N, E)), q_lens=jnp.asarray(q),
        fresh=jnp.asarray(np.asarray(fresh, bool) & (q > 0)))


def poisoned(args):
    """The same step with NaN in every dead position of every input."""
    dead = (np.arange(args["dt"].shape[1])[None, :]
            >= np.asarray(args["q_lens"])[:, None])[:, :, None]
    return dict(args, **{k: jnp.where(dead, jnp.nan, args[k])
                         for k in ("dt", "x", "Bm", "Cm")})


# q_lens mix 0, 1, a few and Tc inside one group of 8
CASES = {
    "one_ragged_group": dict(
        R=8, Tc=16, E=128, q=[0, 1, 3, 16, 0, 2, 1, 16],
        fresh=[0, 0, 1, 0, 0, 0, 1, 1]),
    "an_idle_group_beside_a_ragged_one": dict(
        R=16, Tc=16, E=256, q=[0] * 8 + [1, 16, 0, 5, 1, 1, 0, 2],
        fresh=[0] * 8 + [1, 0, 0, 1, 0, 0, 0, 1]),
    "a_decode_group_beside_a_prefill_group": dict(
        R=16, Tc=16, E=128, q=[1] * 8 + [1, 1, 16, 1, 0, 1, 1, 7],
        fresh=[0] * 15 + [1]),
    "the_decode_program": dict(
        R=16, Tc=1, E=256, q=[1, 0, 1, 1, 0, 0, 1, 1] + [0] * 8,
        fresh=[0, 0, 1] + [0] * 13),
    "every_row_idle": dict(R=8, Tc=4, E=128, q=[0] * 8, fresh=[0] * 8),
    # a budget that one 128-lane tile fits and two do not: grid (1, 2)
    "two_lane_tiles": dict(
        R=8, Tc=4, E=256, q=[4, 0, 1, 2, 4, 3, 0, 1],
        fresh=[1, 0, 0, 0, 0, 1, 0, 0], budget=600_000),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_equals_the_xla_body(name, interpret, monkeypatch):
    case = dict(CASES[name])
    budget = case.pop("budget", None)
    if budget:
        monkeypatch.setattr(pallas_ops, "_VMEM_BUDGET", budget)
        assert pallas_ops._ssm_scan_tile(N, case["E"], case["Tc"]) == 128
    args = inputs(**case)
    assert pallas_ops.ssm_scan_available(args["ssm"].shape, jnp.float32,
                                         case["Tc"])
    want_y, want_s = pallas_ops._ssm_scan_jnp(*args.values(), LAYER)
    got_y, got_s = jax.jit(
        lambda kw: pallas_ops.selective_scan(**kw, layer=LAYER))(
            poisoned(args))
    got_y, got_s, before = (np.asarray(a) for a in (got_y, got_s,
                                                    args["ssm"]))
    q = np.asarray(args["q_lens"])
    live = np.arange(case["Tc"])[None, :] < q[:, None]
    # float32 rounding: the same equations in another order of operations
    assert np.abs(got_s - np.asarray(want_s)).max() < 2e-6 * max(
        1.0, np.abs(want_s).max())
    assert np.abs(got_y - np.asarray(want_y))[live].max(initial=0) < 2e-5
    # NaN in dead positions reached no state; rows that fed nothing and
    # every other layer of the stack come back bit for bit
    assert np.isfinite(got_s).all()
    assert np.array_equal(got_s[LAYER][:, q == 0], before[LAYER][:, q == 0])
    for other in set(range(M)) - {LAYER}:
        assert np.array_equal(got_s[other], before[other])
    # a fresh row starts from zero: its old state does not matter
    changed = dict(args, ssm=args["ssm"].at[LAYER].multiply(
        jnp.where(args["fresh"], 7.0, 1.0)[None, :, None]))
    again = pallas_ops.selective_scan(**poisoned(changed), layer=LAYER)[1]
    fresh = np.asarray(args["fresh"])
    assert np.array_equal(np.asarray(again)[LAYER][:, fresh],
                          got_s[LAYER][:, fresh])


@pytest.mark.parametrize("shape,dtype", [((M, N, 3, 128), jnp.float32),
                                         ((M, N, 8, 64), jnp.float32),
                                         ((M, N, 8, 128), jnp.bfloat16)])
def test_what_the_kernel_cannot_serve_goes_to_the_xla_body(shape, dtype,
                                                           interpret):
    assert not pallas_ops.ssm_scan_available(shape, dtype, 4)
    R, E = shape[2:]
    args = inputs(R, 4, E, q=[4, 0, 1] + [2] * (R - 3),
                  fresh=[1] + [0] * (R - 1))
    args["ssm"] = args["ssm"].astype(dtype)
    found = scopes_of(lambda kw: pallas_ops.selective_scan(**kw, layer=LAYER),
                      args)
    assert "pallas_call" not in {p for p, _ in found}
    y, s = pallas_ops.selective_scan(**args, layer=LAYER)
    want_y, want_s = pallas_ops._ssm_scan_jnp(*args.values(), LAYER)
    assert s.dtype == dtype and np.array_equal(np.asarray(y),
                                               np.asarray(want_y))
    assert np.array_equal(np.asarray(s, np.float32),
                          np.asarray(want_s, np.float32))


def test_without_a_tpu_or_the_interpreter_the_xla_body_serves():
    assert not pallas_ops.ssm_scan_available((M, N, 8, 128), jnp.float32, 16)


@pytest.mark.parametrize("R,Tc", [(128, 16), (128, 1), (48, 16), (48, 1)])
def test_the_kernel_lowers_for_the_tpu_at_the_cells_shapes(R, Tc):
    """Mosaic's lowering of the kernel body, without a chip, at the width of
    both Mamba configurations: E 5120, N 16, in a stack of 26 layers."""
    import jax.export
    E, sds = 5120, jax.ShapeDtypeStruct
    assert pallas_ops._ssm_scan_tile(N, E, Tc) == 2560
    text = jax.export.export(
        jax.jit(pallas_ops._ssm_scan_call, donate_argnums=0),
        platforms=["tpu"])(
            sds((26, N, R, E), jnp.float32), sds((R, Tc, E), jnp.float32),
            sds((R, Tc, E), jnp.float32), sds((R, Tc, N), jnp.float32),
            sds((R, Tc, N), jnp.float32), sds((N, E), jnp.float32),
            sds((R,), jnp.int32), sds((R,), jnp.bool_),
            sds((), jnp.int32)).mlir_module()
    assert "_ssm_scan_kernel" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("q_lens,want", [
    ([1] * 128, 128),                               # a decode step
    ([0] * 16, 0),
    ([1] * 7 + [16] + [1] * 8, 8 * 16 + 8),         # one prefill row
    ([0, 0, 5], 8 * 5),                             # three rows pad to a group
    ([16, 0, 0, 0, 0, 0, 0, 0, 0, 3], 8 * 16 + 8 * 3),
])
def test_scan_positions_counts_a_groups_longest_chunk(q_lens, want):
    assert pallas_ops.scan_positions(q_lens) == want
    assert pallas_ops.scan_positions(np.asarray(q_lens, np.int32)) == want


@pytest.mark.parametrize("module", [jamba, phi4flash])
def test_both_models_count_scan_positions(module):
    cfg = module.preset(f"{module.__name__.rsplit('.', 1)[1]}-debug")
    q = np.array([16, 1, 0, 1, 1, 1, 1, 1, 0, 2], np.int32)
    got = cfg.serving.step_counts(cfg, q + 100, q)
    assert got["scan_positions"] == pallas_ops.scan_positions(q) == 8 * 18
    assert all(isinstance(v, int) for v in got.values())


def test_the_registry_has_both_programs():
    cases = {c[0]: c for c in pallas_ops.kernel_verify_cases()}
    for name in ("selective_scan", "selective_scan_decode"):
        _, fn, avals = cases[name]
        y, ssm = jax.eval_shape(fn, *avals)
        assert ssm.shape == avals[0].shape and ssm.dtype == jnp.float32
    from paddle_tpu.analysis import kernel_checks
    found = kernel_checks.verify_registered(
        names=["selective_scan", "selective_scan_decode"])
    assert [f for f in found if f.severity == "error"] == []
