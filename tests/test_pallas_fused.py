"""Fused decoder-block Pallas kernels: interpret-mode fwd+bwd parity vs
the jnp reference composition, hardware-free Mosaic lowering, decoder-layer
wiring, and the availability policy.

Mirrors test_pallas_kernels.py's OpTest discipline for the two block-level
fusions (fused_attention_block, fused_mlp_block): same decoder-layer
numerics (rmsnorm/rope/flash/wo/residual, rmsnorm/gate-up/silu/down/
residual), verified on CPU under tier-1 through the Pallas interpreter."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import codegen, pallas_ops


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    yield
    pallas_ops._INTERPRET = old


def _cases():
    return {name: (fused, ref, mk)
            for name, fused, ref, mk in pallas_ops.fused_parity_cases()}


def test_parity_registry_shape():
    cases = pallas_ops.fused_parity_cases()
    assert {name for name, *_ in cases} == {"fused_attention_block",
                                            "fused_mlp_block"}
    # and ops/codegen.py re-exports the same registry
    assert [c[0] for c in codegen.fused_parity_cases()] == \
        [c[0] for c in cases]


@pytest.mark.parametrize("name", ["fused_attention_block",
                                  "fused_mlp_block"])
def test_fused_forward_matches_reference(name):
    fused, ref, mk = _cases()[name]
    args = mk(jax.random.PRNGKey(0))
    out = fused(*args)
    expect = ref(*args)
    assert out.dtype == expect.dtype and out.shape == expect.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name", ["fused_attention_block",
                                  "fused_mlp_block"])
def test_fused_backward_matches_reference(name):
    fused, ref, mk = _cases()[name]
    args = mk(jax.random.PRNGKey(1))
    argnums = tuple(range(len(args)))

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))

    got = jax.grad(loss(fused), argnums=argnums)(*args)
    expect = jax.grad(loss(ref), argnums=argnums)(*args)
    for i, (g, e) in enumerate(zip(got, expect)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(e, np.float32),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"{name} darg{i} mismatch")


def test_fused_attention_nondefault_blocks():
    """A non-square tuned (bq, bk) exercises the generalized grid and the
    head-innermost epilogue accumulation."""
    _, ref, mk = _cases()["fused_attention_block"]
    args = mk(jax.random.PRNGKey(2))
    out = pallas_ops._fused_attention_call((128, 1e-6, 128, 256), *args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref(*args), np.float32),
                               rtol=2e-5, atol=2e-5)


def test_fused_mlp_nondefault_blocks():
    _, ref, mk = _cases()["fused_mlp_block"]
    args = mk(jax.random.PRNGKey(3))
    out = pallas_ops._fused_mlp_call((1e-6, 128, 256), *args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref(*args), np.float32),
                               rtol=2e-5, atol=2e-5)


def test_fused_lowering_hardware_free():
    """Lower the fused kernels for the TPU platform on CPU via jax.export
    — runs Mosaic's _check_block_mappings and full kernel-body lowering,
    catching TPU-only compile errors interpret-mode tests skip (the
    r01/r02 class; the RoPE rotation-as-matmul exists to pass this)."""
    import functools
    import jax.export
    B, S, H, D, I = 1, 256, 256, 128, 512
    x = jnp.zeros((B, S, H), jnp.bfloat16)
    ln2d = jnp.zeros((1, H), jnp.bfloat16)
    w = jnp.zeros((H, H), jnp.bfloat16)
    rope = jnp.zeros((S, D), jnp.float32)
    wg = jnp.zeros((H, I), jnp.bfloat16)
    wd = jnp.zeros((I, H), jnp.bfloat16)
    pallas_ops._INTERPRET = False
    try:
        jax.export.export(
            jax.jit(functools.partial(pallas_ops._fused_qkv_proj,
                                      D=D, bq=128, eps=1e-6)),
            platforms=["tpu"])(x, ln2d, w, w, w, rope, rope)
        jax.export.export(
            jax.jit(functools.partial(pallas_ops._fused_attn_epilogue,
                                      D=D, bq=128, bk=128)),
            platforms=["tpu"])(x, x, x, x, w)
        lse = jnp.zeros((B, H // D, S, 128), jnp.float32)
        jax.export.export(
            jax.jit(functools.partial(pallas_ops._fused_flash_bwd_heads,
                                      D=D, bq=128, bk=128)),
            platforms=["tpu"])(x, x, x, x, x, lse)
        mlp = functools.partial(
            pallas_ops._fused_mlp_call, (1e-6, 128, 128))
        jax.export.export(jax.jit(mlp),
                          platforms=["tpu"])(x, ln2d[0], wg, wg, wd)
    finally:
        pallas_ops._INTERPRET = True


def test_availability_gating():
    """Fused kernels refuse ineligible shapes and the CPU jnp path, and
    the public wrappers still produce reference numerics there."""
    shape = (1, 256, 256)
    assert pallas_ops.fused_attention_available(shape, 128,
                                                jnp.float32)
    assert pallas_ops.fused_mlp_available(shape, 512, jnp.float32)
    # head_dim not a lane multiple -> no kernel
    assert not pallas_ops.fused_attention_available(shape, 64, jnp.float32)
    # S that no candidate tiles -> no kernel
    assert not pallas_ops.fused_attention_available((1, 100, 256), 128,
                                                    jnp.float32)
    assert not pallas_ops.fused_mlp_available((1, 100, 256), 512,
                                              jnp.float32)
    # off the interpreter and off TPU: nothing is available, but the
    # wrapper silently runs the jnp reference
    pallas_ops._INTERPRET = False
    try:
        assert not pallas_ops.fused_attention_available(shape, 128,
                                                        jnp.float32)
        _, ref, mk = _cases()["fused_mlp_block"]
        args = mk(jax.random.PRNGKey(4))
        np.testing.assert_allclose(
            np.asarray(pallas_ops.fused_mlp_block(*args), np.float32),
            np.asarray(ref(*args), np.float32), rtol=1e-6, atol=1e-6)
    finally:
        pallas_ops._INTERPRET = True


def test_tuned_fused_config_consumed():
    """A cached fused_attention winner is consumed when legal; an illegal
    or stale entry falls back to the first legal candidate."""
    from paddle_tpu.ops import autotune
    saved = {op: dict(t) for op, t in autotune._CACHE.items()}
    autotune._CACHE.clear()
    try:
        S, H, D = 256, 256, 128
        first = pallas_ops._fused_attn_config(S, H, D, jnp.float32)
        assert first == pallas_ops.fused_attn_candidates(
            1, S, H, D, jnp.float32)[0]
        key = ["blocks", S, H, D] + autotune.context_key("float32")
        autotune.record("fused_attention", key, (256, 128))
        assert pallas_ops._fused_attn_config(S, H, D,
                                             jnp.float32) == (256, 128)
        autotune.record("fused_attention", key, (192, 192))  # illegal
        assert pallas_ops._fused_attn_config(S, H, D,
                                             jnp.float32) == first
    finally:
        autotune._CACHE.clear()
        autotune._CACHE.update(saved)


def test_decoder_layer_fused_matches_unfused():
    """models/llama.py wiring: a decoder layer traced with
    fused_blocks='on' (Pallas kernels under the interpreter) matches the
    'off' (unfused jnp) layer, fwd and bwd."""
    import dataclasses

    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=256,
        dtype=jnp.float32, use_remat=False, fused_blocks="on")
    assert cfg.head_dim == 128
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    lp = {k: v[0] for k, v in params["layers"].items()}
    S = 256
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, 256),
                          jnp.float32) * 0.5
    sin, cos = llama._rope_tables(cfg, S)

    cfg_off = dataclasses.replace(cfg, fused_blocks="off")

    def fwd(c, xx):
        y, _aux = llama.decoder_layer(c, lp, xx, sin, cos)
        return y

    y_on = fwd(cfg, x)
    y_off = fwd(cfg_off, x)
    np.testing.assert_allclose(np.asarray(y_on), np.asarray(y_off),
                               rtol=2e-5, atol=2e-5)

    g_on = jax.grad(lambda xx: jnp.sum(fwd(cfg, xx) ** 2))(x)
    g_off = jax.grad(lambda xx: jnp.sum(fwd(cfg_off, xx) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_on), np.asarray(g_off),
                               rtol=2e-4, atol=2e-4)


def test_decoder_layer_policy_defaults_off_on_cpu():
    """The default fused_blocks='off' keeps the unfused path (also under
    the interpreter, and on the chip: tests/test_remat_rule.py)."""
    from paddle_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, dtype=jnp.float32, use_remat=False)
    x = jnp.zeros((1, 256, 256), jnp.float32)
    attn_ok, mlp_ok = llama._fused_block_modes(cfg, x, None, False)
    assert not attn_ok and not mlp_ok
    with pytest.raises(AssertionError):
        llama.LlamaConfig(fused_blocks="sometimes")
