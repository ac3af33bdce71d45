"""``pallas_ops.selective_scan``: the Mosaic kernel of a Mamba layer's scan in
the serve step (``_ssm_scan_kernel``), under the Pallas interpreter on the
CPU, on the step's FLAT tokens (row r's position t is flat ``start[r] + t``),
against the XLA body ``_ssm_scan_jnp`` and against a plain recurrence a row:
ragged ``q_lens`` inside one group of 8 rows, idle groups, ``fresh`` rows, the
other layers of the stack, the compact batch and padded rows laid end to end,
NaN at every flat position that holds no fed token; and ``scan_positions``,
the counter that says how many row-positions the kernel walks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import jamba, phi4flash
from paddle_tpu.ops import pallas_ops
from test_spans import interpret, scopes_of  # noqa: F401 (a fixture)

N, M, LAYER = 16, 3, 1


def inputs(R, Tc, E, q, fresh, compact=True, T=None, seed=0):
    """A step's arguments, flat: the compact batch (``start = cumsum(q) -
    q``, ``T`` tokens or the fed tokens rounded up to a sublane tile) or
    padded rows laid end to end (``start = r x Tc``, ``T = R x Tc``)."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = np.asarray(q, np.int32)
    if compact:
        start = np.cumsum(q) - q
        T = T or max(8, -(-int(q.sum()) // 8) * 8)
    else:
        start, T = np.arange(R) * Tc, R * Tc
    return dict(
        ssm=normal(M, N, R, E), dt=jax.nn.softplus(normal(T, E) - 2.0),
        x=normal(T, E), Bm=normal(T, N), Cm=normal(T, N),
        A=-jnp.exp(0.3 * normal(N, E)), q_lens=jnp.asarray(q),
        start=jnp.asarray(start, jnp.int32),
        fresh=jnp.asarray(np.asarray(fresh, bool) & (q > 0)))


def fed_tokens(args):
    """[T] bool: the flat positions that hold a fed token."""
    fed = np.zeros(args["dt"].shape[0], bool)
    for s, q in zip(np.asarray(args["start"]), np.asarray(args["q_lens"])):
        fed[s:s + q] = True
    return fed


def poisoned(args):
    """The same step with NaN at every flat position that holds no fed
    token: past the fed tokens, and a padded row's dead positions."""
    dead = jnp.asarray(~fed_tokens(args))[:, None]
    return dict(args, **{k: jnp.where(dead, jnp.nan, args[k])
                         for k in ("dt", "x", "Bm", "Cm")})


def plain_recurrence(args, layer):
    """The scan a row and a token at a time, in float64."""
    a = {k: np.asarray(v, np.float64) for k, v in args.items()
         if k not in ("q_lens", "start", "fresh")}
    s = a["ssm"].copy()
    y = np.zeros(a["dt"].shape)
    for r, (q, at, fresh) in enumerate(zip(*(np.asarray(args[k]) for k in (
            "q_lens", "start", "fresh")))):
        if fresh:
            s[layer][:, r] = 0
        for i in range(at, at + q):
            s[layer][:, r] = (np.exp(a["dt"][i][None] * a["A"]) * s[layer][:, r]
                              + (a["dt"][i] * a["x"][i])[None]
                              * a["Bm"][i][:, None])
            y[i] = (s[layer][:, r] * a["Cm"][i][:, None]).sum(0)
    return y, s


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
    # a budget that one 128-lane tile fits and two do not: grid (2, 1)
    "two_lane_tiles": dict(
        R=8, Tc=4, E=256, q=[4, 0, 1, 2, 4, 3, 0, 1],
        fresh=[1, 0, 0, 0, 0, 1, 0, 0], budget=600_000),
    # every chunk length from 0 to 16 over three groups, a fresh row beside
    # a continuing one in each
    "every_length_of_a_chunk": dict(
        R=24, Tc=16, E=128,
        q=[0, 1, 2, 3, 4, 5, 6, 16, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 1,
           0, 2, 1, 16, 3],
        fresh=[0, 1, 0, 1, 0, 0, 1, 0, 1, 0, 0, 1, 0, 0, 1, 0, 0, 1, 0, 0, 1,
               0, 0, 1]),
    # the serve cells' mixed step: 128 rows on 256 flat tokens, most of them
    # decoding, a few whole chunks, idle rows, tokens left over
    "the_cells_flat_batch": dict(
        R=128, Tc=16, E=128, T=256,
        q=[16 if r in (5, 40, 77) else 7 if r == 100 else
           0 if r % 11 == 0 or 48 <= r < 56 else 1 for r in range(128)],
        fresh=[r in (40, 100, 3) for r in range(128)]),
}
LAYOUTS = {"compact": True, "padded_rows": False}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_equals_the_xla_body(name, layout, interpret, monkeypatch):
    case = dict(CASES[name])
    budget = case.pop("budget", None)
    if not LAYOUTS[layout]:
        case.pop("T", None)
    args = inputs(**case, compact=LAYOUTS[layout])
    T, Tc = args["dt"].shape[0], case["Tc"]
    if budget:
        monkeypatch.setattr(pallas_ops, "_VMEM_BUDGET", budget)
        assert pallas_ops._ssm_scan_tile(N, case["E"], T) == 128
    assert pallas_ops.ssm_scan_available(args["ssm"].shape, jnp.float32, T)
    want_y, want_s = pallas_ops._ssm_scan_jnp(*args.values(), LAYER, Tc)
    got_y, got_s = jax.jit(
        lambda kw: pallas_ops.selective_scan(**kw, Tc=Tc, layer=LAYER))(
            poisoned(args))
    got_y, got_s, before = (np.asarray(a) for a in (got_y, got_s,
                                                    args["ssm"]))
    q = np.asarray(args["q_lens"])
    fed = fed_tokens(args)
    # float32 rounding: the same equations in another order of operations
    assert np.abs(got_s - np.asarray(want_s)).max() < 2e-6 * max(
        1.0, np.abs(want_s).max())
    assert np.abs(got_y - np.asarray(want_y))[fed].max(initial=0) < 2e-5
    # the XLA body, on the same poisoned arguments, is zero off the fed tokens
    body_y, body_s = pallas_ops._ssm_scan_jnp(*poisoned(args).values(), LAYER,
                                              Tc)
    assert np.array_equal(np.asarray(body_s), np.asarray(want_s))
    assert not np.asarray(body_y)[~fed].any()
    # and both are the plain recurrence, a row and a token at a time
    plain_y, plain_s = plain_recurrence(args, LAYER)
    assert np.abs(got_s - plain_s).max() < 4e-6 * max(1.0,
                                                      np.abs(plain_s).max())
    assert np.abs(got_y - plain_y)[fed].max(initial=0) < 4e-5
    # NaN off the fed tokens reached no state; rows that fed nothing (an
    # idle group's whole tile among them) and every other layer of the stack
    # come back bit for bit
    assert np.isfinite(got_s).all() and np.isfinite(got_y[fed]).all()
    assert np.array_equal(got_s[LAYER][:, q == 0], before[LAYER][:, q == 0])
    for other in set(range(M)) - {LAYER}:
        assert np.array_equal(got_s[other], before[other])
    # a fresh row starts from zero: its old state does not matter
    changed = dict(args, ssm=args["ssm"].at[LAYER].multiply(
        jnp.where(args["fresh"], 7.0, 1.0)[None, :, None]))
    again = pallas_ops.selective_scan(**poisoned(changed), Tc=Tc,
                                      layer=LAYER)[1]
    fresh = np.asarray(args["fresh"])
    assert np.array_equal(np.asarray(again)[LAYER][:, fresh],
                          got_s[LAYER][:, fresh])


def test_nan_in_a_dead_rows_slab_reaches_nothing(interpret):
    """A row that ends before its group does keeps its state whatever the
    tokens after its own hold: here they are another row's, poisoned one row
    at a time, and the state of every OTHER row and its ``y`` stay finite
    and equal."""
    case = CASES["one_ragged_group"]
    args = inputs(**case)
    clean = pallas_ops.selective_scan(**args, Tc=case["Tc"], layer=LAYER)
    q, start = np.asarray(args["q_lens"]), np.asarray(args["start"])
    for r in (3, 5):                      # a whole chunk, two tokens
        own = np.zeros(args["dt"].shape[0], bool)
        own[start[r]:start[r] + q[r]] = True
        bad = dict(args, **{k: jnp.where(jnp.asarray(own)[:, None], jnp.nan,
                                         args[k])
                            for k in ("dt", "x", "Bm", "Cm")})
        y, s = pallas_ops.selective_scan(**bad, Tc=case["Tc"], layer=LAYER)
        others = np.arange(case["R"]) != r
        assert np.array_equal(np.asarray(s)[:, :, others],
                              np.asarray(clean[1])[:, :, others])
        assert np.array_equal(np.asarray(y)[fed_tokens(args) & ~own],
                              np.asarray(clean[0])[fed_tokens(args) & ~own])


@pytest.mark.parametrize("shape,dtype,T", [((M, N, 3, 128), jnp.float32, 16),
                                           ((M, N, 8, 64), jnp.float32, 16),
                                           ((M, N, 8, 128), jnp.bfloat16, 16),
                                           ((M, N, 8, 128), jnp.float32, 12)])
def test_what_the_kernel_cannot_serve_goes_to_the_xla_body(shape, dtype, T,
                                                           interpret):
    assert not pallas_ops.ssm_scan_available(shape, dtype, T)
    R, E = shape[2:]
    args = inputs(R, 4, E, q=[4, 0, 1] + [2] * (R - 3),
                  fresh=[1] + [0] * (R - 1), T=T)
    args["ssm"] = args["ssm"].astype(dtype)
    found = scopes_of(
        lambda kw: pallas_ops.selective_scan(**kw, Tc=4, layer=LAYER), args)
    assert "pallas_call" not in {p for p, _ in found}
    y, s = pallas_ops.selective_scan(**args, Tc=4, layer=LAYER)
    want_y, want_s = pallas_ops._ssm_scan_jnp(*args.values(), LAYER, 4)
    assert s.dtype == dtype and np.array_equal(np.asarray(y),
                                               np.asarray(want_y))
    assert np.array_equal(np.asarray(s, np.float32),
                          np.asarray(want_s, np.float32))


def test_without_a_tpu_or_the_interpreter_the_xla_body_serves():
    assert not pallas_ops.ssm_scan_available((M, N, 8, 128), jnp.float32, 16)


@pytest.mark.parametrize("R,Tc,T", [(128, 16, 256), (128, 1, 128),
                                    (48, 16, 256), (48, 1, 48)])
def test_the_kernel_lowers_for_the_tpu_at_the_cells_shapes(R, Tc, T):
    """Mosaic's lowering of the kernel body, without a chip, at the width of
    both Mamba configurations: E 5120, N 16, in a stack of 26 layers, on the
    flat batch of both programs."""
    import jax.export
    E, sds = 5120, jax.ShapeDtypeStruct
    # the [T, Et] blocks of dt, x and y stay in VMEM beside the state's
    assert pallas_ops._ssm_scan_tile(N, E, T) == (2560 if T == 48 else 1280)
    text = jax.export.export(
        jax.jit(pallas_ops._ssm_scan_call, donate_argnums=0,
                static_argnames="Tc"),
        platforms=["tpu"])(
            sds((26, N, R, E), jnp.float32), sds((T, E), jnp.float32),
            sds((T, E), jnp.float32), sds((T, N), jnp.float32),
            sds((T, N), jnp.float32), sds((N, E), jnp.float32),
            sds((R,), jnp.int32), sds((R,), jnp.int32), sds((R,), jnp.bool_),
            sds((), jnp.int32), Tc=Tc).mlir_module()
    assert "_ssm_scan_kernel" in text and "tpu_custom_call" in text


def test_padded_rows_of_the_benchmarks_replay_stay_inside_the_budget():
    """The identity layout of the cells' mixed bucket (``[128, 16]`` and
    ``[48, 16]`` padded: the benchmark's replay) is the same kernel on
    ``R x Tc`` flat tokens: a narrower tile, still one."""
    for R in (128, 48):
        Et = pallas_ops._ssm_scan_tile(N, 5120, R * 16)
        assert Et and 5120 % Et == 0 and Et % 128 == 0


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
        assert y.shape == avals[1].shape            # flat in, flat out
    from paddle_tpu.analysis import kernel_checks
    found = kernel_checks.verify_registered(
        names=["selective_scan", "selective_scan_decode"])
    assert [f for f in found if f.severity == "error"] == []
