"""The Mamba mixer on the step's flat tokens (PR 38): the convolution that
reads a row's fed tokens where they lie in the batch, against the padded one
it replaced (kept here as the reference), and a guard on the traced mixed
step of both models with Mamba layers: nothing under ``mamba`` has a padded
row's positions any more."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import jamba, phi4flash
from paddle_tpu.models.step_layout import StepLayout
from test_spans import interpret  # noqa: F401 (a fixture)

K, E = 4, 8


def padded_conv(lp, x, conv, q_lens):
    """``jamba._ssm_conv`` as it was before PR 38: ``x [R, Tc, E]`` padded
    rows, ``conv [K-1, R, E]``; activations ``[R, Tc, E]`` and the last K-1
    real inputs of each row."""
    Tc = x.shape[1]
    win = [conv[j] for j in range(K - 1)] + [x[:, t] for t in range(Tc)]
    w = lp["conv_w"].astype(jnp.float32)
    out = [lp["conv_b"].astype(jnp.float32)
           + sum(w[j] * win[t + j].astype(jnp.float32) for j in range(K))
           for t in range(Tc)]
    new = []
    for j in range(K - 1):
        kept = win[j]
        for q in range(1, Tc + 1):
            kept = jnp.where((q_lens == q)[:, None], win[q + j], kept)
        new.append(kept)
    return jax.nn.silu(jnp.stack(out, 1)), jnp.stack(new, 0)


# rows shorter than the window's carried part (0, 1, 2), as long (3), a
# whole chunk, a decode row after a whole chunk
Q_LENS = {
    "every_length_around_the_window": [0, 1, 2, 3, 16, 1, 4, 0, 2, 16],
    "decode_rows": [1, 1, 0, 1, 1, 1],
    "one_token_chunks": [1, 0, 1],
}


@pytest.mark.parametrize("compact", [True, False],
                         ids=["compact", "padded_rows"])
@pytest.mark.parametrize("name", sorted(Q_LENS))
def test_the_flat_convolution_equals_the_padded_one(name, compact):
    q = np.asarray(Q_LENS[name], np.int32)
    R, Tc = len(q), (1 if name == "one_token_chunks" else 16)
    rng = np.random.default_rng(len(q))
    normal = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s), jnp.float32)
    lp = {"conv_w": normal(K, E), "conv_b": normal(E)}
    rows, state = normal(R, Tc, E), normal(K - 1, R, E)
    fresh = jnp.asarray((np.arange(R) % 3 == 1) & (q > 0))
    # NaN where a row holds no token: it reaches neither side's results
    dead = jnp.asarray(np.arange(Tc)[None, :] >= q[:, None])[:, :, None]
    rows = jnp.where(dead, jnp.nan, rows)
    want, want_state = padded_conv(
        lp, rows, jnp.where(fresh[None, :, None], 0, state), jnp.asarray(q))

    lay = StepLayout(jnp.asarray(q), Tc, int(q.sum()) + 5 if compact else None)
    flat = np.full((lay.T, E), np.nan, np.float32)
    start = np.asarray(lay.start)
    for r in range(R):
        flat[start[r]:start[r] + q[r]] = np.asarray(rows)[r, :q[r]]
    stack = jnp.stack([normal(K - 1, R, E), state])   # layer 1 of two
    got, got_stack = jax.jit(
        lambda x, c: jamba._ssm_conv(lp, x, c, 1, jnp.asarray(q), fresh,
                                     lay))(jnp.asarray(flat), stack)
    assert np.array_equal(np.asarray(got_stack[0]), np.asarray(stack[0]))
    got_state = got_stack[1]
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (lay.T, E) and got.dtype == np.float32
    for r in range(R):
        fed = got[start[r]:start[r] + q[r]]
        assert np.isfinite(fed).all()
        # float32, the same order of sums
        np.testing.assert_allclose(fed, want[r, :q[r]], rtol=1e-6, atol=1e-6)
    # the carried inputs are copies of inputs: exactly the padded version's
    assert np.array_equal(np.asarray(got_state), np.asarray(want_state))
    assert np.array_equal(np.asarray(got_state)[:, q == 0],
                          np.asarray(state)[:, q == 0])


def shapes_under(fn, scope, *args):
    """The shapes of every equation's results under the scope ``scope`` in
    ``fn``'s jaxpr, bodies of loops and calls included (not a Pallas
    kernel's own body: its blocks live in VMEM)."""
    found = set()

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            path = "/".join(filter(None, (
                outer, str(eqn.source_info.name_stack))))
            if scope in path.split("/"):
                found.update(tuple(v.aval.shape) for v in eqn.outvars
                             if hasattr(v.aval, "shape"))
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, path)
    walk(jax.make_jaxpr(fn)(*args).jaxpr, "")
    return found


@pytest.mark.parametrize("module", [jamba, phi4flash])
def test_nothing_under_mamba_has_a_padded_rows_positions(module, interpret):
    """The traced mixed step under ``step_tokens``, the scan through its
    kernel as on the chip: no result under ``mamba`` has ``R x Tc`` rows
    (flat or as two axes, in either order) by ``mamba_inner`` columns.  The
    padded program, whose flat batch IS ``R x Tc`` tokens, has: the guard
    sees what it looks for."""
    name = module.__name__.rsplit(".", 1)[1]
    cfg = module.preset(f"{name}-debug", dtype=jnp.float32)
    R, Tc, T = 8, 4, 16
    E_ = cfg.mamba_inner
    padded = {(R * Tc, E_), (R, Tc, E_), (Tc, R, E_)}
    assert T != R * Tc and len({R, Tc, T, E_}) == 4
    params = jax.eval_shape(lambda: module.init_params(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: module.init_cache(cfg, R, 2 * R + 1, 128,
                                                     jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    args = (params, i32(R, Tc), cache, i32(R, 2), i32(R), i32(R))
    step = functools.partial(module.forward_paged, cfg)
    flat = shapes_under(functools.partial(step, step_tokens=T), "mamba",
                        *args)
    assert (T, E_) in flat and not flat & padded, flat & padded
    assert shapes_under(step, "mamba", *args) & padded
